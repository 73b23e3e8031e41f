"""Median of what ttft_p90_ms is the tail of."""


def read(ctx):
    xs = ctx.ttfts()
    return ctx.percentile(xs, 50) if xs else None
