"""Of setup_warmup_s, tracing: Python over the model's and every kernel's body, a nested jit counted once
(`stages["warmup"]["trace_s"]` of the replica's start-up record)."""
from metrics._startup_stages import staged


def read(ctx):
    s = staged(ctx)
    return s["stages"]["warmup"]["trace_s"] if s else None
