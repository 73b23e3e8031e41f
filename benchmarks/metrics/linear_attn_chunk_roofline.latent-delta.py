"""linear_attn_chunk_roofline, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: `kda_chunk` against its roof with this model's needs (`kda_chunk_needs`). The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("linear_attn_chunk_roofline")
