"""expert_gmm_time_share, under a name of its own in the cell that brought delta layers: the
accepted metric's list of cells, and its second spelling's, are each held to
one entry by tests/test_pangu_metrics.py and tests/test_laguna_metrics.py,
files a PR that adds a cell may not edit (PERF.md section 7 asks the next
benchmark PR to fold the three names)."""


def read(ctx):
    return ctx.same_as("expert_gmm_time_share")
