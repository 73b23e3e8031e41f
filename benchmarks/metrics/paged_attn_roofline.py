"""The least time the chip could take for the paged kernel's calls in the
traced window (bytes it must read over peak bandwidth, or operations over
peak compute, whichever is more), over the time they took. The work of a
step is the mean of the steps the replica dispatched around the trace."""


def read(ctx):
    k, steps = ctx.kernel_of("_decode_impl"), ctx.traced_decode_steps()
    if not k or not steps or not k["seconds"]:
        return None
    a, b = ctx.traced["counters_before"], ctx.traced["counters_after"]
    d_steps = b["decode_steps"] - a["decode_steps"]
    if d_steps <= 0:
        return None
    layers = ctx.config["num_hidden_layers"]
    chips = ctx.config["engine"].get("tensor_parallel", 1)
    needs = ctx.flops.paged_decode_needs(
        ctx.config,
        context_tokens=(b["decode_context_tokens"] - a["decode_context_tokens"]) / d_steps * steps,
        rows=(b["slot_steps_active"] - a["slot_steps_active"]) / d_steps * steps)
    needs = {key: v * layers / chips for key, v in needs.items()}  # kernel seconds are per device
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
