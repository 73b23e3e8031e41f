"""Engine arrival to the start of the step that gave the request a slot,
from the benchmark's wrapper around LLMEngine.step. Median over the window."""


def read(ctx):
    xs = ctx.r["window"]["queue_wait_s"]
    return ctx.percentile(xs, 50) * 1e3 if xs else None
