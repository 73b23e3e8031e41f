"""Of setup_s, what lies before the replica's constructor begins on what it
times: the cluster and serve started, the replica's process, its imports, the
backend. setup_s ends at the window's start, so it is setup_s less the seconds
from `init_began` to there."""
from metrics._startup import startup


def read(ctx):
    s = startup(ctx)
    return ctx.r["setup_s"] - (ctx.window[0] - s["init_began"]) if s else None
