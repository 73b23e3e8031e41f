"""The least time the chip could take for the state-space layers' one-token
calls in the traced decode steps (a live row's state read and written once,
over peak bandwidth; or a state's operations over peak compute, whichever is
more), over the time they took. The rows a step are the program's own count
(`state_rows`) over the decode blocks that started in the window."""
from harness.cellspec import architecture, decode_kernels
from metrics._state_steps import rows_a_step


def read(ctx):
    k, steps, rows = ctx.kernel_of("_decode_impl", "ssd_step"), ctx.traced_decode_steps(), rows_a_step(ctx)
    needs_of = getattr(architecture(ctx.config), "ssd_step_needs", None)
    if not k or not steps or not k["seconds"] or needs_of is None or rows is None:
        return None
    layers = decode_kernels(ctx.config)["ssd_step"]  # one call a state-space layer
    needs = needs_of(ctx.config, rows=rows * steps * layers)
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
