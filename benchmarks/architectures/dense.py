"""The dense GQA decoder as an architecture of the benchmark (InternLM2,
Mistral: RMSNorm, rotary positions, grouped-query attention, SwiGLU, no bias,
untied head). A configuration file without an "architecture" key gets this
one. The module holds what the harness has to know of an architecture and
nothing of the program's implementation of it: the plain reference, the
published keys' mapping onto the program's configuration, toy sizes for
--rehearse, and the parameter counts the operation counts are made from
(benchmarks/README.md, "An architecture").

The reference: straightforward jax.numpy in float32, no kernels, no cache, no
batching tricks, a Python loop over layers. It follows the published config
(rms_norm_eps from the file; the program fixes 1e-6, a departure listed in
the file under `assumed`). Callers set
jax.default_matmul_precision("highest"): on a TPU a float32 matmul otherwise
runs in lower precision.

It reads the program's parameter tree (stacked layers: wq [L,D,H,hd],
wk/wv [L,D,KV,hd], wo [L,H,hd,D], w_gate/w_up [L,D,F], w_down [L,F,D],
embed [V,D], lm_head [D,V]) because the weights under test are the only ones
there are; it shares no code with the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rotary(x, positions, theta):
    """x [B,S,H,hd]; rotate_half convention (first half with second half)."""
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * inv  # [B,S,half]
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32."""
    f32 = lambda t: jax.tree.map(lambda a: a.astype(jnp.float32), t)  # noqa: E731
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.tril(jnp.ones((S, S), bool))[None]
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"].astype(jnp.float32)[tokens]
    layers = params["layers"]
    for i in range(model["num_hidden_layers"]):
        lp = f32({k: v[i] for k, v in layers.items()})
        h = _rms_norm(x, lp["attn_norm"], eps)
        q = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), positions, theta)
        k = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), positions, theta)
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)
        s = jnp.einsum("bqhk,bthk->bhqt", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqt,bthk->bqhk", p, v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        h = _rms_norm(x, lp["ffn_norm"], eps)
        x = x + (jax.nn.silu(h @ lp["w_gate"]) * (h @ lp["w_up"])) @ lp["w_down"]
    x = _rms_norm(x, params["final_norm"].astype(jnp.float32), eps)
    return x @ params["lm_head"].astype(jnp.float32)


def packed_loss(params, batch: dict, model: dict):
    """Mean next-token cross entropy over the targets that carry loss: those
    inside a document (same segment as the token before, not padding)."""
    tok, seg = batch["tokens"], batch["segment_ids"]
    lg = logits(params, tok[:, :-1], model, seg[:, :-1], batch["positions"][:, :-1])
    logp = jax.nn.log_softmax(lg, axis=-1)
    nll = -jnp.take_along_axis(logp, tok[:, 1:, None], axis=-1)[..., 0]
    w = ((seg[:, 1:] == seg[:, :-1]) & (batch["mask"][:, 1:] > 0)).astype(jnp.float32)
    return jnp.sum(nll * w) / jnp.sum(w)


# ---------------------------------------------------------------------------
# What the harness asks of an architecture besides its reference
# ---------------------------------------------------------------------------

def transformer_kwargs(model: dict) -> dict:
    """The published config's keys -> ray_tpu.models.TransformerConfig's.
    head_dim is derived there as d_model // n_heads, which both published
    configs satisfy (128); rms_norm_eps has no counterpart (see `assumed`)."""
    d, h = model["hidden_size"], model["num_attention_heads"]
    if model.get("head_dim", d // h) != d // h:
        raise SystemExit("benchmark: TransformerConfig derives head_dim = hidden_size / heads")
    return dict(
        vocab_size=model["vocab_size"], d_model=d, n_layers=model["num_hidden_layers"],
        n_heads=h, n_kv_heads=model["num_key_value_heads"], d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], rope_theta=float(model["rope_theta"]),
        attention_impl="auto",
        # Further TransformerConfig fields the configuration sets (dtypes by name).
        **(model.get("transformer") or {}),
    )


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place."""
    model.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=4,
                 num_key_value_heads=2, intermediate_size=256, vocab_size=512,
                 max_position_embeddings=512)


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim): what harness/flops.py's
    attention-only counts read."""
    H = model["num_attention_heads"]
    return (model["num_hidden_layers"], H, model["num_key_value_heads"],
            model.get("head_dim") or model["hidden_size"] // H)


def param_counts(model: dict) -> dict:
    """`matmul`: the parameters one token multiplies (6 operations each in a
    train step); `resident_matmul`: the matmul parameters that lie in memory
    (what a decode step reads). In a dense model they are the same."""
    d, V, F = model["hidden_size"], model["vocab_size"], model["intermediate_size"]
    L, H, KV, hd = attention_dims(model)
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    ffn = 3 * d * F
    norms = 2 * d
    head = 0 if model.get("tie_word_embeddings") else d * V
    matmul = L * (attn + ffn) + d * V  # the head multiplies even when tied
    return {"embedding": V * d, "lm_head": head, "per_layer_matmul": attn + ffn,
            "matmul": matmul, "resident_matmul": matmul,
            "total": V * d + head + L * (attn + ffn + norms) + d}
