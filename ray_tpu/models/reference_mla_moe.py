"""The plain reference of the latent-attention, routed-expert decoder that
models/transformer.py serves (``attention_kind="latent"``, ``sandwich_norm``,
``n_dense_layers``, ``experts_held``): the published layer of
openPangu-Ultra-MoE / the DeepSeek-V3 family, written down once in float32
``jax.numpy`` with no kernel, cache, batching or absorbed projection, a Python
loop over layers and over experts. It imports nothing of transformer.py and
reads that module's parameter tree because the weights under test are the only
ones there are. tests/test_latent_moe.py holds the program to it.

The layer (x is [T, D], N an RMSNorm with a learned weight):

    h = x + N2(Attn(N1(x)))            y = h + N4(FFN(N3(h)))
    cq = Nq(x Wqa); q = cq Wqb, a head's [q_nope | q_rope], rope on q_rope
    [ckv | kr] = x Wkva; c = Nkv(ckv); k_rope = rope(kr), one for all heads
    k_nope_h = c Wkb_h; v_h = c Wvb_h
    scores (q_nope_h . k_nope_h + q_rope_h . k_rope) / sqrt(nope + rope), causal
    FFN, leading layers: SwiGLU of the dense width
    FFN, the rest: s = score(x Wr) in float32 over every expert; the K largest;
      weights s_e / (sum of the K) * scaling; shared(x) + sum_e w_e E_e(x)

Departures from the published layer, all of them:

- `held` = (first, count) restricts the sum over chosen experts to ids
  first .. first + count - 1, as the chip that holds those serves it: weights
  stay normalised over all K chosen. held=None sums every expert in the tree.
- `score` is not among the published keys: sigmoid (the family's convention),
  no expert groups, no selection bias.
- rope pairs a column with the one half the roped width away (rotate-half);
  the released checkpoints interleave pairs. With random weights the two are
  one model up to a permutation of Wqb's and Wkva's roped columns.
- The multi-token-prediction block takes no part in next-token logits and is
  not here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, positions, theta):
    """x [B,S,...,w]: column i turns with column i + w/2."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[..., None] * theta ** (-jnp.arange(half, dtype=F32) / half)  # [B,S,half]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


def attention(x, lp, model: dict, positions, allowed):
    """x [B,S,D] (already N1-normed) -> [B,S,D]; allowed [B,S,S] bool."""
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    R, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    cq = _norm(x @ lp["wq_a"].astype(F32), lp["q_norm"], eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, lp["wq_b"].astype(F32))
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, theta)
    ckr = x @ lp["wkv_a"].astype(F32)
    c, k_rope = _norm(ckr[..., :R], lp["kv_norm"], eps), _rope(ckr[..., R:], positions, theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("bsr,rhk->bshk", c, lp["wv_b"].astype(F32))
    s = jnp.einsum("bqhk,bthk->bhqt", q_nope, k_nope) + jnp.einsum("bqhk,btk->bhqt", q_rope, k_rope)
    s = s / jnp.sqrt(F32(q.shape[-1]))
    p = jax.nn.softmax(jnp.where(allowed[:, None], s, -jnp.inf), axis=-1)
    return jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhqt,bthk->bqhk", p, v), lp["wo"].astype(F32))


def routed_ffn(x, lp, model: dict, held=None, shared: bool = True):
    """x [B,S,D] (already N3-normed) -> the routed layer's FFN output, the
    sum over the chosen experts among `held` (all in the tree when None),
    an expert at a time, plus the shared expert unless `shared` is False."""
    K = model["num_experts_per_tok"]
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    score = jax.nn.sigmoid(logits) if model.get("score", "sigmoid") == "sigmoid" else jax.nn.softmax(logits, -1)
    top_s, top_e = jax.lax.top_k(score, K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = jnp.zeros_like(x)
    if shared and "ws_gate" in lp:
        out = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    for j in range(count):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
    return out


def logits(params, tokens, model: dict, held=None, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. `model`: the published keys
    (rms_norm_eps, rope_theta, kv_lora_rank, qk_nope_head_dim,
    num_experts_per_tok, routed_scaling_factor, sandwich_norm, optionally
    score); the depth and the widths are the tree's."""
    eps = float(model["rms_norm_eps"])
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"].astype(F32)[tokens]
    for name in ("dense_layers", "layers"):
        stack = params.get(name) or {}
        for i in range(stack["attn_norm"].shape[0] if stack else 0):
            lp = {k: v[i] for k, v in stack.items()}
            a = attention(_norm(x, lp["attn_norm"], eps), lp, model, positions, allowed)
            x = x + (_norm(a, lp["post_attn_norm"], eps) if model.get("sandwich_norm") else a)
            h = _norm(x, lp["ffn_norm"], eps)
            f = routed_ffn(h, lp, model, held) if "router" in lp else _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"])
            x = x + (_norm(f, lp["post_ffn_norm"], eps) if model.get("sandwich_norm") else f)
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32)
