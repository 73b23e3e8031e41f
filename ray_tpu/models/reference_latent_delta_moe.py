"""The plain reference of the decoder with ONE latent-attention layer in four
beside gated-delta-rule layers, a leading dense layer and routed experts, that
models/transformer.py serves (a ``layer_pattern`` with a ``mixer="latent"``
kind beside a ``mixer="delta"`` kind with fewer key heads than value heads and
a decay a head, ``attn_gate="elementwise"`` on the latent layer,
``sandwich_norm``, ``norm_gating``, ``swiglu_limit``, ``n_dense_layers``,
``experts_held``): the published layer of GigaChat3.5-432B-A28B as its keys
give it, written down once in float32 ``jax.numpy`` with no kernel, chunk,
cache, batching or absorbed projection: a Python loop over layers, the whole
[S, S] score matrix masked, the delta rule a ``lax.scan`` over positions, the
held experts one at a time in a rolled loop. It imports nothing of
transformer.py nor of ops/ and reads that module's parameter tree because the
weights under test are the only ones there are: ``dense_layers`` (the leading
layers, delta mixers with a dense FFN) and ``kind_layers`` {"latent": the
latent layers, "delta": the delta layers after the dense ones, each stacked
in order}. tests/test_gigachat_serving.py holds the program to it.

The layer (x [T, D]; no bias anywhere). N(x; w) = x / rms(x) * s(w), s(w) =
gating * sigmoid(w) where the model gates its norms (`layernorm_gating_weight`
2: a weight of zeros is a scale of one), else w:

    h = x + N2(Mixer(N1(x)))            x = h + N4(FFN(N3(h)))
    latent mixer (layer l in `full_attention_layers`), u = N1(x):
      cq = Nq(u Wqa); q = cq Wqb, a head's [q_nope | q_rope]; [ckv | kr] = u
      Wkva; c = Nkv(ckv); q_rope and kr roped by YaRN's frequencies (cos and
      sin times 1: mscale = mscale_all_dim), one kr for all heads;
      k_h = [c Wkb_h | kr], v_h = c Wvb_h; causal softmax of q k^T / sqrt(nope
      + rope) * m^2, m = 0.1 ln(factor) + 1 (`use_mla_scaling_factor`);
      o = o * sigmoid(u Wg) elementwise (`gated_attention`); Wo
    delta mixer (every other layer): q~, k~ = u Wq, u Wk [Hk, d], v~ = u Wv
      [H, d]; a causal depthwise convolution of T taps over the 2 Hk + H heads'
      channels (zeros before position 0), then SiLU; q = q' / |q'| / sqrt(d),
      k = k' / |k'| a key head; key head j serves value heads j H / Hk ..;
      beta = sigmoid(u Wb), g = -exp(a_log) softplus(u Wa + dt_bias) a value
      head; S <- e^g S; S <- S + beta k (v - S^T k)^T; o = S^T q, S [d, d]
      float32 a value head; o = o / rms(o) * w_o * scale sigmoid(u Wz); Wo
    FFN: SwiGLU(h) = W2(silu(min(W1 h, limit)) * clip(W3 h, -limit, limit))
      (`swiglu_limit`; 0: no clamp). Leading layers: one of the dense width.
      The rest: s = sigmoid(h Wr) in float32 over every expert, the K largest,
      weights s_e / (sum of the K) * scaling; shared(h) + sum_e w_e E_e(h)

Departures from the published keys, all of them assumed (the benchmark's
configuration file lists each with where it came from): the norm's function of
its weight, the latent layer's gate and its place, the softmax scale's m^2,
rotate-half pairing where the checkpoints interleave, the delta layer's gate
function and its plain head-norm weight, the clamp's form, the router's score.
`held` = (first, count) restricts the sum over chosen experts to ids first ..
first + count - 1, as the chip that holds those serves it, weights normalised
over all K chosen; None sums every expert in the tree.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LATENT, DELTA = "latent", "delta"
L2_EPS = 1e-6  # under the root of a delta layer's query and key norms


def _norm(x, w, eps, gating=0.0):
    w = w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (gating * jax.nn.sigmoid(w) if gating else w)


def _swiglu(x, gate, up, down, limit=0.0):
    a, b = x @ gate.astype(F32), x @ up.astype(F32)
    if limit:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return (jax.nn.silu(a) * b) @ down.astype(F32)


def yarn_inv_freq(width: int, theta: float, scaling: dict | None):
    """The width // 2 rotation frequencies of a roped part `width` wide. With
    `scaling` (factor, original_max_position_embeddings, beta_fast, beta_slow):
    a column that turns more than beta_fast times over the original length
    keeps its frequency, one that turns fewer than beta_slow times has it
    divided by the factor, a linear ramp over the columns between."""
    i = jnp.arange(width // 2, dtype=F32)
    base = theta ** (-2.0 * i / width)
    if not scaling:
        return base
    L = scaling["original_max_position_embeddings"]

    def column(turns):  # the column whose wavelength fits `turns` times into L
        return width * math.log(L / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(column(scaling["beta_fast"])), 0)
    high = min(math.ceil(column(scaling["beta_slow"])), width - 1)
    ramp = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)  # 0: kept, 1: divided
    return base * (1.0 - ramp) + base / scaling["factor"] * ramp


def softmax_scale(model: dict, width: int) -> float:
    """1 / sqrt(a head's query width), times m^2 under `use_mla_scaling_factor`."""
    scaling = model.get("rope_scaling")
    if not (scaling and model.get("use_mla_scaling_factor")):
        return 1.0 / math.sqrt(width)
    m = 0.1 * float(scaling.get("mscale_all_dim", 1)) * math.log(scaling["factor"]) + 1.0
    return m * m / math.sqrt(width)


def _rope(x, positions, inv_freq):
    """x [B,S,...,w]: column i turns with column i + w/2."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[..., None] * inv_freq  # [B,S,half]
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def latent_attention(u, lp, model: dict, positions, allowed):
    """A latent layer's mixer. u [B,S,D] (already N1-normed) -> [B,S,D];
    allowed [B,S,S] bool."""
    eps, gating = float(model["rms_norm_eps"]), float(model.get("layernorm_gating_weight") or 0)
    R, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    cq = _norm(u @ lp["wq_a"].astype(F32), lp["q_norm"], eps, gating)
    q = jnp.einsum("bsr,rhk->bshk", cq, lp["wq_b"].astype(F32))
    inv_freq = yarn_inv_freq(q.shape[-1] - nope, float(model["rope_theta"]), model.get("rope_scaling"))
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], positions, inv_freq)
    ckr = u @ lp["wkv_a"].astype(F32)
    c, k_rope = _norm(ckr[..., :R], lp["kv_norm"], eps, gating), _rope(ckr[..., R:], positions, inv_freq)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, lp["wk_b"].astype(F32))
    v = jnp.einsum("bsr,rhk->bshk", c, lp["wv_b"].astype(F32))
    s = jnp.einsum("bqhk,bthk->bhqt", q_nope, k_nope) + jnp.einsum("bqhk,btk->bhqt", q_rope, k_rope)
    p = jax.nn.softmax(jnp.where(allowed[:, None], s * softmax_scale(model, q.shape[-1]), -jnp.inf), axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", p, v)
    if model.get("gated_attention"):
        o = o * jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", u, lp["wg"].astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(F32))


def short_conv(u, taps, before=None):
    """u [B,S,C,d] through the causal depthwise convolution: taps [T,C,d], the
    oldest input's first; `before` [B,T-1,C,d] the inputs ahead of position 0
    (None: zeros)."""
    T, S = taps.shape[0], u.shape[1]
    if before is None:
        before = jnp.zeros((u.shape[0], T - 1, *u.shape[2:]), F32)
    padded = jnp.concatenate([before, u], axis=1)
    return sum(padded[:, j:j + S] * taps[j] for j in range(T))


def delta_rule(q, k, v, g, beta, state=None):
    """The rule a position at a time. q, k [B,S,H,K], v [B,S,H,V], g and beta
    [B,S,H] (a decay a HEAD), float32; state [B,H,K,V] or None (zeros) ->
    (o [B,S,H,V], the state after the last position)."""
    B, _, H, K = q.shape

    def one(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None, None]
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    s0 = jnp.zeros((B, H, K, v.shape[-1]), F32) if state is None else state
    s, o = jax.lax.scan(one, s0, tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), s


def delta_projections(u, lp):
    """What a delta layer's convolution runs over: [q~ | k~ | v~] along one
    axis of 2 Hk + H heads, [B,S,2 Hk + H,d]."""
    return jnp.concatenate([jnp.einsum("bsd,dhk->bshk", u, lp[w].astype(F32)) for w in ("wq", "wk", "wv")], axis=2)


def delta_inputs(u, lp, before=None):
    """(q, k, v, g, beta) of a delta layer from its normed input u [B,S,D],
    q and k already repeated over the value heads they serve."""
    H, Hk = lp["wv"].shape[1], lp["wq"].shape[1]
    taps = lp["conv"].astype(F32)
    y = jax.nn.silu(short_conv(delta_projections(u, lp), taps.reshape(taps.shape[0], -1, taps.shape[-1]), before))
    q, k, v = y[:, :, :Hk], y[:, :, Hk:2 * Hk], y[:, :, 2 * Hk:]
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(q.shape[-1])
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q, k = jnp.repeat(q, H // Hk, axis=2), jnp.repeat(k, H // Hk, axis=2)  # value head i reads key head i // (H / Hk)
    g = -jnp.exp(lp["a_log"].astype(F32)) * jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", u, lp["wa"].astype(F32)) + lp["dt_bias"].astype(F32))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", u, lp["wb"].astype(F32)))
    return q, k, v, g, beta


def delta_attention(u, lp, model: dict, state=None, before=None):
    """A delta layer's mixer. u [B,S,D] (already N1-normed) -> ([B,S,D], the
    state after the last position)."""
    o, s = delta_rule(*delta_inputs(u, lp, before), state)
    o = _norm(o, lp["o_norm"], float(model.get("linear_attn_o_norm_eps", model["rms_norm_eps"])))
    gate = float(model.get("linear_sigmoid_gate_scale", 1)) * jax.nn.sigmoid(
        jnp.einsum("bsd,dhk->bshk", u, lp["wz"].astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", o * gate, lp["wo"].astype(F32)), s


def routed_ffn(x, lp, model: dict, held=None, shared: bool = True):
    """x [B,S,D] (already N3-normed) -> the routed layer's FFN output, the sum
    over the chosen experts among `held` (all in the tree when None), an
    expert a turn of a rolled loop, plus the shared expert unless `shared` is
    False."""
    K, limit = model["num_experts_per_tok"], float(model.get("swiglu_limit") or 0)
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = _swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], limit) if shared else jnp.zeros_like(x)

    def one(j, out):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        return out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j], limit)

    return jax.lax.fori_loop(0, count, one, out)


def layer_kinds(model: dict) -> list:
    return [LATENT if l in model["full_attention_layers"] else DELTA for l in range(model["num_hidden_layers"])]


def layers(params, model: dict):
    """(kind, the layer's parameters, routed?) of every layer in the order a
    token passes them."""
    n_dense, later = model["first_k_dense_replace"], {}
    for l, kind in enumerate(layer_kinds(model)):
        if l < n_dense:
            yield kind, {k: v[l] for k, v in params["dense_layers"].items()}, False
        else:
            i = later.get(kind, 0)
            later[kind] = i + 1
            yield kind, {k: v[i] for k, v in params["kind_layers"][kind].items()}, True


def logits(params, tokens, model: dict, held=None, segment_ids=None):
    """tokens [B,S] -> logits [B,S,V], float32. `model`: the published keys
    (rms_norm_eps, num_hidden_layers, first_k_dense_replace,
    full_attention_layers, kv_lora_rank, qk_nope_head_dim, rope_theta,
    rope_scaling, use_mla_scaling_factor, gated_attention,
    layernorm_gating_weight, swiglu_limit, linear_sigmoid_gate_scale,
    linear_attn_o_norm_eps, num_experts_per_tok, routed_scaling_factor); the
    widths and head counts are the tree's. A packed batch is refused, as the
    program refuses it."""
    if segment_ids is not None:
        raise NotImplementedError("the delta layers are written for one document a row")
    eps, gating = float(model["rms_norm_eps"]), float(model.get("layernorm_gating_weight") or 0)
    limit = float(model.get("swiglu_limit") or 0)
    B, S = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    norm = lambda x, w: _norm(x, w, eps, gating)
    x = params["embed"].astype(F32)[tokens]
    for kind, lp, routed in layers(params, model):
        u = norm(x, lp["attn_norm"])
        a = latent_attention(u, lp, model, positions, allowed) if kind == LATENT else delta_attention(u, lp, model)[0]
        x = x + norm(a, lp["post_attn_norm"])
        h = norm(x, lp["ffn_norm"])
        f = routed_ffn(h, lp, model, held) if routed else _swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], limit)
        x = x + norm(f, lp["post_ffn_norm"])
    return norm(x, params["final_norm"]) @ params["lm_head"].astype(F32)
