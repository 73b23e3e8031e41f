"""state_rows_per_step, under a name of its own in the cell that serves a latent-attention layer beside gated-delta-rule
layers in one model: the slots whose state a decode step rewrote, in each of the four delta layers alike. The lists it could join are held to their members by tests a PR that
adds a cell may not edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("state_rows_per_step")
