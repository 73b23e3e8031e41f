"""The least time the chip could take for the grouped matmul's calls in the
traced decode steps (every live tile's expert read once, over peak bandwidth;
or 2 operations a parameter a pair over peak compute, whichever is more), over
the time they took. Pairs and live tiles a step are the program's own counts
over the window."""
from harness.cellspec import architecture
from metrics._expert_steps import a_step_and_layer


def read(ctx):
    k, steps = ctx.kernel_of("_decode_impl", "expert_gmm"), ctx.traced_decode_steps()
    arch = architecture(ctx.config)
    pairs, tiles = a_step_and_layer(ctx, "expert_pairs"), a_step_and_layer(ctx, "expert_tiles")
    if not k or not steps or not k["seconds"] or None in (pairs, tiles) or not hasattr(arch, "expert_gmm_needs"):
        return None
    layer_steps = steps * arch.routing(ctx.config)  # a routed layer of a step: three calls
    needs = arch.expert_gmm_needs(ctx.config, pairs=pairs * layer_steps, tiles=tiles * layer_steps)
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
