"""Of setup_s, the replica's warm-up: every prefill and decode program run
once (compiled, or read from the compile cache): the constructor's `warmup_s`."""
from metrics._startup import startup


def read(ctx):
    s = startup(ctx)
    return s["warmup_s"] if s else None
