"""The least time the chip could take for the window layers' calls in the
traced decode steps (the K and V of every position a row attends, at most the
window, read once over peak bandwidth; or 72 heads' operations over peak
compute, whichever is more), over the time they took. The positions a step
attends are the program's own count (`window_tokens`, one layer's) over the
decode blocks that started in the window; the rows are the replica's."""
from harness.cellspec import architecture, decode_kernels
from metrics._window_steps import decode_steps_in_window


def read(ctx):
    k, steps = ctx.kernel_of("_decode_impl", "window_attn"), ctx.traced_decode_steps()
    needs_of = getattr(architecture(ctx.config), "window_decode_needs", None)
    recs = decode_steps_in_window(ctx, "window_tokens")
    if not k or not steps or not k["seconds"] or needs_of is None or not recs:
        return None
    a, b = ctx.traced["counters_before"], ctx.traced["counters_after"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if d_steps <= 0:
        return None
    needs = needs_of(
        ctx.config,
        window_tokens=sum(s["window_tokens"] for s in recs) / sum(s["block"] for s in recs) * steps,
        rows=(b["slot_steps_active"] - a["slot_steps_active"]) / d_steps * steps)
    layers = decode_kernels(ctx.config)["window_attn"]  # one call a sliding layer
    needs = {key: v * layers for key, v in needs.items()}
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
