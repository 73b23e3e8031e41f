"""The whole step's share of peak in the train cell of a model with held
experts and window layers: the operations the mathematics of forward and
backward requires for the trained tokens, per second, over chips times the
peak. The count is the architecture file's `train_needs`: 6 operations a
matmul parameter a token multiplies, of its K chosen experts the K x held /
scored that uniform routing EXPECTS on the experts held here (expected, not
counted: a train step's counters do not reach a reader; where the router
sends them fewer, the step does less than is counted here and reads the
higher for it: the held experts' products are 23% of the count), plus
attention over the causal pairs inside documents in the full layers and over the banded
pairs in the sliding ones. Nothing recomputed counts (remat, the flash
backward's second pass over the scores, the passes' routing made again)."""
from harness.cellspec import architecture


def read(ctx):
    w = ctx.r["worker"]
    arch = architecture(ctx.config)
    if not hasattr(arch, "train_needs"):
        return None
    docs = [d for row in ctx.r["doc_lens"] for d in row]
    per_token = arch.train_needs(ctx.config, docs)["flops"] / sum(d - 1 for d in docs if d > 1)
    return 100.0 * per_token * w["tokens"] / w["window_s"] / (ctx.chips * ctx.peaks["flops_bf16"])
