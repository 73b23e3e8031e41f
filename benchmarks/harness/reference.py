"""Plain reference of the dense decoder both configurations describe
(InternLM2, Mistral: RMSNorm, rotary positions, grouped-query attention,
SwiGLU, no bias, untied head): straightforward jax.numpy in float32, no
kernels, no cache, no batching tricks, a Python loop over layers. It follows
the published config (rms_norm_eps from the file; the program fixes 1e-6, a
departure listed in the file under `assumed`). Callers set
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
