"""Tokens that carried loss (inside a document, not padding) in the steps of
the window, over the window's seconds, fenced at both ends."""


def read(ctx):
    w = ctx.r["worker"]
    return w["tokens"] / w["window_s"]
