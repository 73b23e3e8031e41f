"""The plain reference of the decoder with gated short-convolution layers beside
QK-normed, roped softmax GQA layers, leading dense layers and routed experts
chosen by a bias they are not weighed by, that models/transformer.py serves (a
``layer_pattern`` with a ``mixer="conv"`` kind, ``qk_norm``, ``router_bias``,
``n_dense_layers``, ``experts_held``, ``tie_embeddings``): the published layer
of LFM2-24B-A2B (``lfm2_moe``), written down once in float32 ``jax.numpy`` with
no kernel, cache or batching: a Python loop over layers and over experts, the
whole [S, S] score matrix masked, the convolution a sum of shifted copies. It
imports nothing of transformer.py nor of ops/ and reads that module's
parameter tree because the weights under test are the only ones there are:
``dense_layers`` (the leading layers, conv ones), ``kind_layers`` {"conv",
"attention": each kind's later layers stacked in order}, ``embed`` (the head
too) and ``final_norm``. tests/test_lfm2_serving.py holds the program to it.

    x = E[tokens]
    layer l (N an RMSNorm with a learned weight, eps norm_eps; no bias):
      x = x + Mixer(N_op(x)), then x = x + FFN(N_ffn(x))
    conv mixer (T taps): [B | C | u] = h W_in (D -> 3 D, thirds in that
      order); z = B * u; c_t = sum_j w_j z_(t-T+1+j) a channel (depthwise,
      causal, zeros before position 0, no bias, no activation); (C * c) W_out
    attention mixer: q = h Wq [H, d]; k = h Wk, v = h Wv [KV, d]; q = N_q(q),
      k = N_k(k) over a head's d columns (one weight of d for all heads), THEN
      rope over the whole head (rotate-half, theta rope_theta); a_h =
      softmax(q_h k_g^T / sqrt(d) + causal mask) v_g, g = h // (H / KV); a Wo
    FFN, layers below num_dense_layers: (silu(h Wg) * (h Wu)) Wd
    FFN, the others: s = sigmoid(h Wr) in float32 over every expert; the K
      experts with the largest s + b (b the router's bias, no part of the
      weight); w_e = scaling x s_e / (sum of the K chosen s + 1e-6);
      sum_e w_e E_e(h), every expert a SwiGLU; no shared expert
    logits = N_f(x) E^T

`model` holds the published keys that are numbers (`norm_eps`, `layer_types`,
`num_dense_layers`, `num_experts_per_tok`, `routed_scaling_factor`,
`rope_parameters`, `use_expert_bias`). Assumed, and listed in the benchmark's
configuration file: the 1e-6 and the choice by the bias (the published
module's `route_tokens_to_experts` as the catalog's keys describe it), the tied
head, the initial values. `held` = (first, count) restricts the sum over
chosen experts to ids first .. first + count - 1, as the chip that holds those
serves it, weights normalised over all K chosen; None sums every expert in the
tree. A tree without `q_norm` leaves (`qk_norm` off) has no head norm."""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
STACK = {CONV: "conv", ATTENTION: "attention"}  # a published layer type -> its stack in params["kind_layers"]


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def _rope(x, theta):
    """x [B, S, H, d] at positions 0 .. S - 1: rotate-half over the whole head."""
    S, half = x.shape[1], x.shape[-1] // 2
    angles = jnp.arange(S, dtype=F32)[:, None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [S, half]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def conv_inputs(h, lp):
    """-> (z = B u, the gate C), [B, S, D] each, of a conv layer's normed input."""
    D = h.shape[-1]
    bcu = h @ lp["w_in"].astype(F32)
    return bcu[..., :D] * bcu[..., 2 * D:], bcu[..., D:2 * D]


def short_conv(z, taps):
    """z [B, S, D] through the causal depthwise convolution: taps [T, D], the
    oldest input's first; zeros before position 0."""
    T, S = taps.shape[0], z.shape[1]
    padded = jnp.pad(z, ((0, 0), (T - 1, 0), (0, 0)))
    return sum(padded[:, j:j + S] * taps[j].astype(F32) for j in range(T))


def conv_mixer(h, lp):
    z, gate = conv_inputs(h, lp)
    return (gate * short_conv(z, lp["conv"])) @ lp["w_out"].astype(F32)


def attention(h, lp, theta, eps):
    """An attention layer's mixer. h [B, S, D] (already normed) -> [B, S, D]."""
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[name].astype(F32)) for name in ("wq", "wk", "wv"))
    if "q_norm" in lp:
        q, k = _norm(q, lp["q_norm"], eps), _norm(k, lp["k_norm"], eps)
    q, k = _rope(q, theta), _rope(k, theta)
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    return jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(F32))


def route(x, lp, model: dict):
    """-> (the K chosen experts [B, S, K], their weights [B, S, K]): chosen
    by score + bias, weighed by the score."""
    K = model["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest"))
    if model.get("use_expert_bias"):
        _, top_e = jax.lax.top_k(score + lp["router_bias"].astype(F32), K)
        top_s = jnp.take_along_axis(score, top_e, axis=-1)
        norm = jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6
    else:
        top_s, top_e = jax.lax.top_k(score, K)
        norm = jnp.sum(top_s, axis=-1, keepdims=True)
    return top_e, top_s / norm * float(model["routed_scaling_factor"])


def routed_ffn(x, lp, model: dict, held=None):
    """x [B, S, D] (already normed) -> the routed layer's FFN output, the sum
    over the chosen experts among `held` (all in the tree when None), an expert
    at a time."""
    top_e, weight = route(x, lp, model)
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = jnp.zeros_like(x)
    for j in range(count):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B, S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
    return out


def layers(params, model: dict):
    """Every layer's (published type, parameters) in the order a token passes
    them: the leading dense ones from ``dense_layers``, the others from their
    kind's stack."""
    n_dense, later = model["num_dense_layers"], {}
    for l, kind in enumerate(model["layer_types"]):
        if l < n_dense:
            stack, i = params["dense_layers"], l
        else:
            stack, i = params["kind_layers"][STACK[kind]], later.get(kind, 0)
            later[kind] = i + 1
        yield kind, {k: v[i] for k, v in stack.items()}


def logits(params, tokens, model: dict, held=None):
    """tokens [B, S] -> logits [B, S, V], float32: every layer in order."""
    eps, theta = float(model["norm_eps"]), float(model["rope_parameters"]["rope_theta"])
    with jax.default_matmul_precision("highest"):
        embed = params["embed"].astype(F32)
        x = embed[tokens]
        for kind, lp in layers(params, model):
            h = _norm(x, lp["attn_norm"], eps)
            x = x + (conv_mixer(h, lp) if kind == CONV else attention(h, lp, theta, eps))
            h = _norm(x, lp["ffn_norm"], eps)
            x = x + (routed_ffn(h, lp, model, held) if "router" in lp
                     else _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]))
        return _norm(x, params["final_norm"], eps) @ embed.T
