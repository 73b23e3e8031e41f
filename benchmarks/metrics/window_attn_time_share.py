"""The window layers' decode kernel's device time in the decode program (one
call a sliding layer), over the device's busy time in the traced window."""


def read(ctx):
    k = ctx.kernel_of("_decode_impl", "window_attn")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
