"""Operations the mathematics of forward and backward requires for the
trained tokens (6 per matmul parameter per token, plus causal attention
within documents; nothing recomputed counts), per second, over chips times
the peak."""


def read(ctx):
    w = ctx.r["worker"]
    docs = [d for row in ctx.r["doc_lens"] for d in row]
    per_token = ctx.flops.train_flops(ctx.config, sum(docs), docs) / sum(d - 1 for d in docs if d > 1)
    return 100.0 * per_token * w["tokens"] / w["window_s"] / (ctx.chips * ctx.peaks["flops_bf16"])
