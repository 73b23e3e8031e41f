"""Output tokens that reached the client inside the window, over the
window's seconds. Every token of every request counts, whenever the request
began or ended."""


def read(ctx):
    w0, w1 = ctx.window
    return ctx.tokens_in_window() / (w1 - w0)
