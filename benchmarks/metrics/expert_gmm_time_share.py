"""The grouped matmul's device time in the decode program (three calls a
routed layer), over the device's busy time in the traced window."""


def read(ctx):
    k = ctx.kernel_of("_decode_impl", "expert_gmm")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
