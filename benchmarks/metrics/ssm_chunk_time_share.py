"""The state-space layers' chunked kernel's device time in the prefill programs
(one `ssd_chunk` call a state-space layer and prompt), over the device's busy
time in the traced window."""


def read(ctx):
    k = ctx.kernel_of("_prefill_batch_impl", "ssd_chunk")
    return 100.0 * k["seconds"] / ctx.traced["busy_s"] if k and ctx.traced["busy_s"] else None
