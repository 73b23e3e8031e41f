"""Operations and bytes the algorithms need, from shapes. Kept with the
benchmark so that no later change can move its own yardstick.

Conventions: one multiply-add = 2 operations; a causal score matrix over a
document of l tokens has l*(l+1)/2 live entries; recomputed work (remat, the
flash backward's second pass over the scores) is NOT counted: these are the
operations the mathematics requires, which is what a utilisation is a share of.
`model` is the configuration file's dict (Hugging Face key names). What
depends on the architecture (the parameter counts, how heads are named) comes
from the architecture's file; the attention, kernel and roofline arithmetic is
here.
"""
from __future__ import annotations

from harness.cellspec import architecture


def _attn(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim), as the architecture's file reads them."""
    return architecture(model).attention_dims(model)


def param_counts(model: dict) -> dict:
    return architecture(model).param_counts(model)


def causal_pairs(doc_lens) -> int:
    """Live (query, key) pairs of causal attention within documents."""
    return sum(l * (l + 1) // 2 for l in doc_lens)


def train_flops(model: dict, tokens: int, doc_lens) -> float:
    """Forward + backward for `tokens` token positions holding documents of
    the given lengths: 6 per matmul parameter per token (the embedding lookup
    multiplies nothing), plus attention's two matmuls forward and four
    backward over the live pairs: 3 * 2 * 2 * pairs * H * hd per layer."""
    L, H, KV, hd = _attn(model)
    return 6.0 * param_counts(model)["matmul"] * tokens + 12.0 * L * H * hd * causal_pairs(doc_lens)


def flash_train_needs(model: dict, doc_lens, dtype_bytes: int = 2) -> dict:
    """One layer's flash forward + backward over documents of these lengths:
    operations as in train_flops; bytes: forward reads q,k,v and writes o,
    backward reads q,k,v,o,do and writes dq,dk,dv (row statistics ignored)."""
    L, H, KV, hd = _attn(model)
    n = sum(doc_lens)
    q, kv = n * H * hd * dtype_bytes, n * KV * hd * dtype_bytes
    return {"flops": 12.0 * H * hd * causal_pairs(doc_lens),
            "bytes": float((2 * q + 2 * kv) + (4 * q + 4 * kv))}


def paged_decode_needs(model: dict, context_tokens: int, rows: int, dtype_bytes: int = 2) -> dict:
    """One layer's decode attention, summed over calls: `rows` (slot, step)
    pairs attending to `context_tokens` cached positions in total. Each
    position's K and V are read once for all heads of its group; q and o are
    read and written once per row."""
    L, H, KV, hd = _attn(model)
    return {"flops": 4.0 * H * hd * context_tokens,
            "bytes": float(2 * KV * hd * dtype_bytes * context_tokens + 2 * rows * H * hd * dtype_bytes)}


def decode_weight_bytes(model: dict, param_bytes: int) -> float:
    """What one decode step must read of the weights: every matmul parameter
    that lies in memory, once (a batch's tokens between them reach nearly
    every expert; the embedding contributes a row per slot, ignored)."""
    return float(param_counts(model)["resident_matmul"] * param_bytes)


def roofline_seconds(needs: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take, and which peak bounds it."""
    t_f, t_b = needs["flops"] / peaks["flops_bf16"], needs["bytes"] / peaks["hbm_bytes_per_s"]
    return (t_f, "compute") if t_f >= t_b else (t_b, "memory")
