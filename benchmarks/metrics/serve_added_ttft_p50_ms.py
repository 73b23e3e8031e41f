"""Client TTFT minus the engine's own (first_token_at - arrived_at, which the
first streamed frame carries): what proxy, handle, replica and the stream
back add, router queueing included. Median."""


def read(ctx):
    xs = [ctx.ttft_ms(x) - x["engine_ttft_s"] * 1e3 for x in ctx.timed
          if x.get("engine_ttft_s") is not None]
    return ctx.percentile(xs, 50) if xs else None
