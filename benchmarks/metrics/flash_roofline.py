"""The least time the chip could take for the attention of the traced steps'
documents (two matmuls forward, five backward, over the causal pairs inside
each document; recomputation not counted), over the flash kernels' time."""


def read(ctx):
    k = ctx.kernel_of("train_step")
    if not k or not k["seconds"]:
        return None
    docs = [d for step in ctx.traced["rows"] for r in step for d in ctx.r["doc_lens"][r]]
    needs = ctx.flops.flash_train_needs(ctx.config, docs)
    layers = ctx.config["num_hidden_layers"]
    needs = {key: v * layers / ctx.chips for key, v in needs.items()}
    return 100.0 * ctx.flops.roofline_seconds(needs, ctx.peaks)[0] / k["seconds"]
