"""engine_host_ms_per_step in the four-chip backlog cell, under a name of its
own: the list of cells of engine_host_ms_per_step.backlog is held as it is by
a test outside the benchmark (tests/test_bench_metrics.py)."""


def read(ctx):
    return ctx.same_as("engine_host_ms_per_step")
