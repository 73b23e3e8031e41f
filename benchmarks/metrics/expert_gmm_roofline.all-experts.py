"""expert_gmm_roofline (the least time the chip could take for the grouped matmul's calls in the traced
decode steps, every live tile's expert read once, over the time they took; here every one of 64
experts is live every step), under a name of its own in the cell whose routed layers hold every
expert: the lists it could join are held to their members by tests a PR that adds a cell may not
edit (PERF.md section 7 asks the next benchmark PR to fold the names)."""


def read(ctx):
    return ctx.same_as("expert_gmm_roofline")
