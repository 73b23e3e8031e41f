"""Median of what tpot_p90_ms is the tail of."""


def read(ctx):
    xs = ctx.tpots()
    return ctx.percentile(xs, 50) if xs else None
