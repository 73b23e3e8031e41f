"""The least time the chip could take for the full-attention layers' paged
calls in the traced decode steps (every cached position's K and V read once,
over peak bandwidth; or a head's operations over peak compute, whichever is
more), over the time they took. The work of a step is the mean of the steps
the replica dispatched around the trace, as paged_attn_roofline takes it."""
from harness.cellspec import architecture, decode_kernels


def read(ctx):
    k, steps = ctx.kernel_of("_decode_impl", "paged_attn"), ctx.traced_decode_steps()
    needs_of = getattr(architecture(ctx.config), "full_decode_needs", None)
    if not k or not steps or not k["seconds"] or needs_of is None:
        return None
    a, b = ctx.traced["counters_before"], ctx.traced["counters_after"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if d_steps <= 0:
        return None
    needs = needs_of(
        ctx.config,
        context_tokens=(b["decode_context_tokens"] - a["decode_context_tokens"]) / d_steps * steps,
        rows=(b["slot_steps_active"] - a["slot_steps_active"]) / d_steps * steps)
    layers = decode_kernels(ctx.config)["paged_attn"]  # the kernel's calls a step: one a full layer
    needs = {key: v * layers for key, v in needs.items()}
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
