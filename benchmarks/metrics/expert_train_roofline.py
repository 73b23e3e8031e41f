"""The least time the chip could take for the grouped matmuls of the traced
train steps, forward and backward (the architecture file's
`expert_train_needs`: nine products a routed layer over the pairs uniform
routing EXPECTS on the held experts for the steps' document tokens; whichever
of operations over peak compute and bytes over peak bandwidth is more), over
the time the three kernels took: `expert_gmm` (forward, and made again under
remat, which the needs do not count), `expert_gmm_dx` and `expert_tgmm`.
RIGHT ONLY WHILE THE ROUTING IS EVEN: the pairs are expected and not counted
(a train step's `expert_pairs` does not reach a reader), so a run whose
router sends the held experts 0.4-1.4 of their expected pairs, as this cut's
does at its coefficient of 0.001 (PERF.md section 6, PR 59), reads high or low
by expected over counted pairs: read it over several traced seeds, and beside
`expert_gmm`'s seconds a step in the line's `kernels`."""
from harness.cellspec import architecture


def read(ctx):
    k = ctx.kernel_of("train_step", "expert_")
    arch = architecture(ctx.config)
    if not k or not k["seconds"] or not hasattr(arch, "expert_train_needs"):
        return None
    tokens = sum(d for step in ctx.traced["rows"] for r in step for d in ctx.r["doc_lens"][r])
    needs = arch.expert_train_needs(ctx.config, tokens)
    layers = arch.routing(ctx.config)  # the routed layers
    needs = {key: v * layers / ctx.chips for key, v in needs.items()}
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
