"""How late the load generator sent a request after it was due. A late
generator flatters the server, so this says whether the tails are valid."""


def read(ctx):
    xs = [(x["sent"] - x["due"]) * 1e3 for x in ctx.measured if x.get("sent") is not None]
    return ctx.percentile(xs, 99) if xs else None
