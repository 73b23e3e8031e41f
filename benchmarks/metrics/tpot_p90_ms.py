"""Per request (t_last - t_first) / (n_out - 1) at the client, 90th
percentile over the measured requests."""


def read(ctx):
    xs = ctx.tpots()
    return ctx.percentile(xs, 90) if xs else None
