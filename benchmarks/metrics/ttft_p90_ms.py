"""Time from when a request was due to its first streamed token at the
client, 90th percentile over the measured requests."""


def read(ctx):
    xs = ctx.ttfts()
    return ctx.percentile(xs, 90) if xs else None
