"""The least time the chip could take for the latent paged kernel's calls in
the traced window (the bytes the mathematics needs over peak bandwidth, or its
operations over peak compute, whichever is more: at 64 heads 121 operations a
byte against the chip's 240, so the bytes), over the time they took, for a
model in which SOME layers are latent: the kernel runs once a latent layer, as
the architecture's `decode_kernels` counts it, where latent_attn_roofline
multiplies by `num_hidden_layers` (right for a model that is latent
throughout, 5 times too much work here). The work of a step is the mean of the
steps the replica dispatched around the trace, as latent_attn_roofline takes it."""
from harness.cellspec import architecture, decode_kernels


def read(ctx):
    k, steps = ctx.kernel_of("_decode_impl", "latent_attn"), ctx.traced_decode_steps()
    needs_of = getattr(architecture(ctx.config), "latent_decode_needs", None)
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
    layers = decode_kernels(ctx.config)["latent_attn"]  # one call a latent layer
    needs = {key: v * layers for key, v in needs.items()}
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
