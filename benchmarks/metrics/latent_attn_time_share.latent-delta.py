"""latent_attn_time_share, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: `latent_attn`'s share of busy time at 64 heads in ONE layer of five. The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("latent_attn_time_share")
