"""The state-space layers' one-token kernel's device time in the decode program
(one `ssd_step` call a state-space layer), over the device's busy time in the
traced window."""


def read(ctx):
    k = ctx.kernel_of("_decode_impl", "ssd_step")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
