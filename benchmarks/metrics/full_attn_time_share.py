"""The paged decode kernel's device time in the decode program, the calls of
the layers that keep every token alone (a window layer's call has a name of
its own), over the device's busy time in the traced window."""


def read(ctx):
    k = ctx.kernel_of("_decode_impl", "paged_attn")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
