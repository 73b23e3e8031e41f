"""expert_pairs_per_held_expert, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: pairs a held expert a routed layer a step: about 4 where a chip of the deployment sees 64. The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("expert_pairs_per_held_expert")
