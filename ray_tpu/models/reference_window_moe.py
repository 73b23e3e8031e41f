"""The plain reference of the decoder with window and full attention layers,
a per-head output gate and routed experts that models/transformer.py serves
(``layer_pattern``, ``attn_gate``, ``head_dim``, ``n_dense_layers``,
``experts_held``): the published layer of Laguna-S-2.1, written down once in
float32 ``jax.numpy`` with no kernel, cache, ring or batching, a Python loop
over layers and over experts, the whole [S, S] score matrix masked. It imports
nothing of transformer.py and reads that module's parameter tree because the
weights under test are the only ones there are: ``dense_layers`` (the leading
layers, of layer 0's kind) and ``kind_layers`` {a kind's name as
``layer_types`` spells it: that kind's later layers, stacked in order}.
tests/test_window_moe.py holds the program to it.

The layer (x is [T, D]; N an RMSNorm with a learned weight before each
sublayer, none after; no bias). A layer's kind k = layer_types[l] gives its
query heads H (num_attention_heads_per_layer[l]), its rope
(rope_parameters[k]) and, for a sliding layer, the window W:

    h = N(x); q = h Wq as [H, d]; kk = h Wk, v = h Wv as [KV, d]
    rope (rotate-half) on the first r = d x partial_rotary_factor columns of
      q and kk, the rest pass. inv_freq_i = theta^(-2i/r), i < r/2; with
      rope_type yarn: c(t) = r ln(L / (2 pi t)) / (2 ln theta), low =
      floor(c(beta_fast)), high = ceil(c(beta_slow)), both inside 0 .. r - 1,
      m_i = 1 - clip((i - low) / (high - low), 0, 1), inv_freq_i =
      (e_i / factor)(1 - m_i) + e_i m_i, and cos, sin times attention_factor
    a_h = softmax(q_h kk_g^T / sqrt(d) + mask) v_g, g = h // (H / KV); mask
      causal, and in a sliding layer i sees j with i - W < j <= i
    gate: g = sigmoid(h Wg), one scalar a head; x = x + concat_h(g_h a_h) Wo
    FFN on N(x), leading layers: SwiGLU of the dense width
    FFN, the rest: s = sigmoid(h2 Wr) in float32 over every expert; the K
      largest; weights scaling x s_e / (sum of the K); shared(h2) +
      sum_e w_e E_e(h2)

Departures from the published layer, all of them:

- `held` = (first, count) restricts the sum over chosen experts to ids
  first .. first + count - 1, as the chip that holds those serves it: weights
  stay normalised over all K chosen. held=None sums every expert in the tree.
- The published keys say `gating: per-head` and no more: the gate's function
  (sigmoid), its input (the normed h) and its place (on the heads' outputs
  before Wo) are the head-wise form of gated attention, assumed.
- No norm on q or kk (the config has no key for one). The router's score is
  sigmoid (the convention of routers with norm_topk_prob and a scaling of
  2.5), no selection bias, no groups; the shared expert is not gated.
- The window's ends (its own position among the W), attention_factor on cos
  and sin, and rotate-half pairing (the released checkpoints may interleave:
  one model up to a permutation of roped columns, with random weights).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    """The rotation frequencies of one kind's rope_parameters, float64."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta = float(rope["rope_theta"])
    i = np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / r)
    if rope.get("rope_type", "default") != "yarn":
        return e
    L = rope["original_max_position_embeddings"]

    def column(turns):
        return r * math.log(L / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = min(max(math.floor(column(rope["beta_fast"])), 0), r - 1)
    high = min(max(math.ceil(column(rope["beta_slow"])), 0), r - 1)
    m = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return e / rope["factor"] * (1.0 - m) + e * m


def _rope(x, positions, rope: dict):
    """x [B,S,h,d]: the first r columns turned, column i with column i + r/2."""
    freq = inv_freq(rope, x.shape[-1])
    r = 2 * len(freq)
    ang = positions.astype(F32)[:, :, None, None] * jnp.asarray(freq, F32)
    factor = float(rope.get("attention_factor", 1.0)) if rope.get("rope_type") == "yarn" else 1.0
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def attention(h, lp, rope: dict, window: int, positions, allowed):
    """h [B,S,D] (already normed) -> [B,S,D]; allowed [B,S,S] bool, causal
    (and same-document); window 0: none."""
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[name].astype(F32)) for name in ("wq", "wk", "wv"))
    q, k = _rope(q, positions, rope), _rope(k, positions, rope)
    H, KV, S = q.shape[2], k.shape[2], q.shape[1]
    k, v = jnp.repeat(k, H // KV, axis=2), jnp.repeat(v, H // KV, axis=2)  # head h reads KV head h // (H / KV)
    if window:
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        allowed = allowed & (j > i - window)[None]
    s = jnp.einsum("bqhk,bthk->bhqt", q, k) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bhqt,bthk->bqhk", p, v)
    if "wg" in lp:  # a tree without the gate's weight is a model without the gate (reference_window_softmax_moe.py)
        a = a * jax.nn.sigmoid(h @ lp["wg"].astype(F32))[..., None]  # [B,S,H], one scalar a head
    return jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(F32))


def routed_ffn(x, lp, model: dict, held=None, shared: bool = True):
    """x [B,S,D] (already normed) -> the routed layer's FFN output, the sum
    over the chosen experts among `held` (all in the tree when None), an
    expert at a time, plus the shared expert unless `shared` is False."""
    K = model["num_experts_per_tok"]
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["moe_routed_scaling_factor"])
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"]) if shared else jnp.zeros_like(x)
    for j in range(count):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
    return out


def logits(params, tokens, model: dict, held=None, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. `model`: the published keys
    (rms_norm_eps, layer_types, sliding_window, rope_parameters,
    mlp_only_layers, num_experts_per_tok, moe_routed_scaling_factor); the
    depth is layer_types', the widths and head counts are the tree's."""
    eps = float(model["rms_norm_eps"])
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"].astype(F32)[tokens]
    n_dense, later = len(model["mlp_only_layers"]), {}
    for l, kind in enumerate(model["layer_types"]):
        if l < n_dense:
            stack, i = params["dense_layers"], l
        else:
            stack, i = params["kind_layers"][kind], later.get(kind, 0)
            later[kind] = i + 1
        lp = {k: v[i] for k, v in stack.items()}
        window = model["sliding_window"] if kind == "sliding_attention" else 0
        h = _norm(x, lp["attn_norm"], eps)
        x = x + attention(h, lp, model["rope_parameters"][kind], window, positions, allowed)
        h = _norm(x, lp["ffn_norm"], eps)
        x = x + (routed_ffn(h, lp, model, held) if "router" in lp else _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]))
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32)
