"""Kernel bodies JAX traced during warm-up (`stages["warmup"]["traces"]` of the replica's start-up record: the
traces of `pallas_call`'s own jit, each inside a program's trace and its `setup_warmup_trace_s`). A kernel's call
is a jit with one identity (ray_tpu/ops), which JAX's trace cache serves, so a body counts once a shape signature
and not once a layer and a program; a count that grows by a layer's calls says a call reached the cache under a
key of its own. A record without the counter (the parent of the PR that added it) reads as None."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    traces = s["stages"]["warmup"].get("traces") if s else None
    return float(traces) if traces is not None else None
