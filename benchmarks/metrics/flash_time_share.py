"""The flash kernels' (forward, dq, dk/dv) device time, over the device's
busy time in the traced steps."""


def read(ctx):
    k = ctx.kernel_of("train_step")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
