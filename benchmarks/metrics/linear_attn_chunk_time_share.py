"""The delta layers' chunked kernel's device time in the prefill programs (one
`kda_chunk` call a delta layer and prompt), over the device's busy time in the
traced window."""


def read(ctx):
    k = ctx.kernel_of("_prefill_batch_impl", "kda_chunk")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
