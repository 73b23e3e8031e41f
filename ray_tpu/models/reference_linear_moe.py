"""The plain reference of the decoder with delta-rule linear-attention layers
beside gated softmax layers without positions, and routed experts, that
models/transformer.py serves (a ``layer_pattern`` with a ``mixer="delta"``
kind, ``attn_gate="elementwise"``, ``rope_share=0``, ``experts_held``): the
published layer of Solar-Open2-250B, written down once in float32
``jax.numpy`` with no kernel, chunk, cache or batching: a Python loop over
layers and over experts, the whole [S, S] score matrix masked, the delta rule
a ``lax.scan`` over positions. It imports nothing of transformer.py nor of
ops/ and reads that module's parameter tree because the weights under test
are the only ones there are: ``kind_layers`` {"gqa": the softmax layers,
"kda": the delta layers, each stacked in order}.
tests/test_linear_attention.py and tests/test_solar_serving.py hold the
program to it.

The layer (x [T, D]; N an RMSNorm with a learned weight before each sublayer,
none after; no bias). Layer l is a softmax layer if l is in `gqa_layers`,
else a delta layer:

    softmax layer: h = N(x); q = h Wq [H, d]; kk = h Wk, v = h Wv [KV, d];
      no rope and no other position signal; a_h = softmax(q_h kk_g^T /
      sqrt(d) + causal mask) v_g, g = h // (H / KV);
      x = x + (a * sigmoid(h Wg)) Wo, Wg [D, H, d] elementwise
    delta layer: h = N(x); q~ = h Wq, k~ = h Wk, v~ = h Wv [Hl, dl]; each
      through a causal depthwise convolution over time of T taps (y_t =
      sum_j c_j u_(t-T+1+j), zeros before position 0) and SiLU; a head's
      q = q' / |q'| / sqrt(dl), k = k' / |k'| (|.|^2 + 1e-6 under the root),
      v = v'; g_t = -exp(a_log) softplus((h Wf_a) Wf_b + dt_bias) a channel,
      beta_t = scale sigmoid(h Wb) a head, scale 2 with kda_allow_neg_eigval;
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T
      from S = 0, o_t = S_t^T q_t, all float32;
      x = x + (N_head(o) * sigmoid((h Wg_a) Wg_b)) Wo
    FFN on N(x): s = sigmoid(h2 Wr) in float32 over every expert; the K
      largest; weights scaling x s_e / (sum of the K); shared(h2) +
      sum_e w_e E_e(h2), every expert a SwiGLU

Departures from the published keys, all of them assumed (the configuration
file of the benchmark lists them): the low-rank decay and gate projections
and their rank (the delta heads' width), the state in float32, the
elementwise form of `use_gqa_gate` and no norm on q or k of a softmax layer,
the router's score (sigmoid, no bias, no groups), the norms' epsilon under
the root of q and k. `held` = (first, count) restricts the sum over chosen
experts to ids first .. first + count - 1, as the chip that holds those
serves it, weights normalised over all K chosen; None sums every expert in
the tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
GQA, KDA = "gqa", "kda"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def attention(h, lp, allowed):
    """A softmax layer's mixer. h [B,S,D] (already normed) -> [B,S,D];
    allowed [B,S,S] bool, causal (and same-document)."""
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[name].astype(F32)) for name in ("wq", "wk", "wv"))
    H, KV = q.shape[2], k.shape[2]
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)  # head h reads KV head h // (H / KV)
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqt,bthk->bqhk", p, v)
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", h, lp["wg"].astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", a * gate, lp["wo"].astype(F32))


def short_conv(u, taps):
    """u [B,S,...] through the causal depthwise convolution: taps [T,...],
    the oldest input's first; zeros before position 0."""
    T, S = taps.shape[0], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (T - 1, 0)) + ((0, 0),) * (u.ndim - 2))
    return sum(padded[:, j:j + S] * taps[j].astype(F32) for j in range(T))


def delta_rule(q, k, v, g, beta):
    """The rule a position at a time from an empty state. q, k, g
    [B,S,H,K], v [B,S,H,V], beta [B,S,H], float32 -> (o [B,S,H,V], the state
    after each position's own update is not kept: the last one [B,H,K,V])."""
    B, _, H, K = q.shape

    def one(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]  # Diag(a) S
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s, o = jax.lax.scan(one, jnp.zeros((B, H, K, v.shape[-1]), F32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def delta_inputs(h, lp, beta_scale: float):
    """(q, k, v, g, beta) of a delta layer from its normed input h [B,S,D]."""
    u = jnp.stack([jnp.einsum("bsd,dhk->bshk", h, lp[name].astype(F32)) for name in ("wq", "wk", "wv")], axis=2)
    q, k, v = (jax.nn.silu(short_conv(u, lp["conv"])[:, :, i]) for i in range(3))
    d = q.shape[-1]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(d)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    f = jnp.einsum("bsr,rhk->bshk", h @ lp["wf_a"].astype(F32), lp["wf_b"].astype(F32))
    g = -jnp.exp(lp["a_log"].astype(F32))[:, None] * jax.nn.softplus(f + lp["dt_bias"].astype(F32))
    beta = beta_scale * jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, lp["wb"].astype(F32)))
    return q, k, v, g, beta


def delta_attention(h, lp, beta_scale: float, eps: float):
    """A delta layer's mixer. h [B,S,D] (already normed) -> [B,S,D]."""
    o, _ = delta_rule(*delta_inputs(h, lp, beta_scale))
    gate = jnp.einsum("bsr,rhk->bshk", h @ lp["wg_a"].astype(F32), lp["wg_b"].astype(F32))
    return jnp.einsum("bshk,hkd->bsd", _norm(o, lp["o_norm"], eps) * jax.nn.sigmoid(gate), lp["wo"].astype(F32))


def routed_ffn(x, lp, model: dict, held=None, shared: bool = True):
    """x [B,S,D] (already normed) -> the routed layer's FFN output, the sum
    over the chosen experts among `held` (all in the tree when None), an
    expert at a time, plus the shared expert unless `shared` is False."""
    K = model["num_experts_per_tok"]
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) if shared else jnp.zeros_like(x)
    for j in range(count):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
    return out


def logits(params, tokens, model: dict, held=None, segment_ids=None):
    """tokens [B,S] -> logits [B,S,V], float32. `model`: the published keys
    (rms_norm_eps, num_hidden_layers, gqa_layers, kda_allow_neg_eigval,
    num_experts_per_tok, routed_scaling_factor); the widths and head counts
    are the tree's. A packed batch is refused, as the program refuses it."""
    if segment_ids is not None:
        raise NotImplementedError("the delta layers are written for one document a row")
    eps = float(model["rms_norm_eps"])
    B, S = tokens.shape
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    x = params["embed"].astype(F32)[tokens]
    later = {}
    for l in range(model["num_hidden_layers"]):
        kind = GQA if l in model["gqa_layers"] else KDA
        i = later.get(kind, 0)
        later[kind] = i + 1
        lp = {k: v[i] for k, v in params["kind_layers"][kind].items()}
        h = _norm(x, lp["attn_norm"], eps)
        x = x + (attention(h, lp, allowed) if kind == GQA
                 else delta_attention(h, lp, 2.0 if model["kda_allow_neg_eigval"] else 1.0, eps))
        x = x + routed_ffn(_norm(x, lp["ffn_norm"], eps), lp, model, held)
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32)
