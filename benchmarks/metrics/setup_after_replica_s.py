"""Of setup_s, what lies after the replica's constructor has returned
(`init_ended`) up to the window's start, where setup_s ends: serve marks the
replica ready, then the benchmark's own probe, set-up rounds and ramp. With
setup_before_replica_s, setup_weights_s and setup_warmup_s it adds up to
setup_s, but for the constructor's last statements after its warm-up (the
loop thread started: milliseconds)."""
from metrics._startup import startup


def read(ctx):
    s = startup(ctx)
    return ctx.window[0] - s["init_ended"] if s else None
