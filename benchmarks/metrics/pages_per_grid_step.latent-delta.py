"""pages_per_grid_step, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: the latent walk's pages a grid step (a group of up to 8, sized from bfloat16 pages). The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("pages_per_grid_step")
