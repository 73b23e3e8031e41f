"""expert_pairs_per_held_expert, under a name of its own in the cell that brought window layers: the
accepted metric's list of cells is held to one entry by
tests/test_pangu_metrics.py, a file a PR that adds a cell may not edit
(PERF.md section 7 asks the next benchmark PR to fold the two names)."""


def read(ctx):
    return ctx.same_as("expert_pairs_per_held_expert")
