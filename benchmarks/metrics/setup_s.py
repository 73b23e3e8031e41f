"""Process start to the first measured operation: start-up, weights, warm-up
of every program (compilation on a first run), and for an open-loop serve
cell the ramp that brings the server to a steady state."""


def read(ctx):
    return ctx.r["setup_s"]
