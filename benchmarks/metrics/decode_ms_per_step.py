"""Device time of the decode program in the traced window, over the decode
steps that ran in it (counted from the paged kernel's calls)."""


def read(ctx):
    m, steps = ctx.module("_decode_impl"), ctx.traced_decode_steps()
    return m[1] / steps * 1e3 if m and steps else None
