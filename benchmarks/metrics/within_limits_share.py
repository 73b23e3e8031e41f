"""Measured requests that met both limits of the traffic file (TTFT, and the
mean gap between tokens), over those attempted; a failed request misses.
Information: the limits were chosen from the sweep."""


def read(ctx):
    lim = ctx.traffic.get("limits")
    if not lim or not ctx.measured:
        return None
    good = sum(1 for x in ctx.finished
               if ctx.ttft_ms(x) <= lim["ttft_ms"] and (x["n_out"] < 2 or ctx.tpot_ms(x) <= lim["tpot_ms"]))
    return 100.0 * good / len(ctx.measured)
