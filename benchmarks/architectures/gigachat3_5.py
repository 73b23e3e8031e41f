"""GigaChat3.5-432B-A28B (ai-sage, `gigachat3_5`) as an architecture of the
benchmark, as ONE chip of an expert-parallel deployment serves it: layers of a
gated delta rule (`GigaChat35GatedDeltaNet`: `linear_num_key_heads` key heads
serving `linear_num_value_heads` value heads, a decay a head, a short
convolution) with every fourth layer (`full_attention_layers`) latent attention
(MLA: `q_lora_rank`, `kv_lora_rank`, YaRN on the roped columns, an output gate),
norms before and after every sublayer whose scale is a gated weight, a clamp
inside SwiGLU, `first_k_dense_replace` leading layers with a dense FFN and
behind them a shared expert beside `n_routed_experts` routed experts HELD HERE
out of the `router_experts` the router scores. benchmarks/README.md, "An
architecture", says what each function is for.

The reference: the benchmark's own copy of the layer in float32 jax.numpy: no
kernel, chunk, cache, batching or absorbed projection, a Python loop over
layers, the whole score matrix masked, the delta rule a SEQUENTIAL scan over
positions with a scalar decay a head (`jax.lax.scan`; the program's chunked
form, its kernels and the decay a channel they are handed share nothing with
it), the held experts ONE AT A TIME in a `lax.fori_loop`. It reads the
program's parameter tree (`dense_layers`: the leading layers, delta mixers;
`kind_layers` {"latent", "delta": each kind's later layers, stacked in order})
a layer at a time and an expert at a time, each with ONE index into the
stacked weight (`v[i]`, `v[i, e]`), so that `refcheck.read_coarsely` rounds
slices and the check never holds a layer's 16 experts in float32 at once.

    N(x; w) = x / rms(x) * 2 sigmoid(w)   (`norm_type` ZeroCenteredGatedNorm,
      `layernorm_gating_weight` 2: a weight of zeros is a scale of one)
    layer: h = x + N2(Mixer(N1(x))); x = h + N4(FFN(N3(h)))   (`pre_post`)
    latent mixer, u = N1(x): cq = N(u Wqa); q = cq Wqb, a head's [q_nope |
      q_rope]; [ckv | kr] = u Wkva; c = N(ckv); q_rope, kr roped by YaRN's
      frequencies, cos and sin times mscale / mscale_all_dim = 1; k_h = [c
      Wkb_h | kr], v_h = c Wvb_h; causal softmax of q k^T / sqrt(192) * m^2, m
      = 0.1 ln(factor) + 1 (`use_mla_scaling_factor`); o * sigmoid(u Wg)
      (`gated_attention`); Wo
    delta mixer: q~, k~ = u Wq, u Wk [Hk, d], v~ = u Wv [H, d]; a causal
      depthwise convolution of T taps (zeros before position 0), then SiLU; q
      = q' / |q'| / sqrt(d), k = k' / |k'|; key head j serves value heads 2j,
      2j + 1; beta = sigmoid(u Wb), g = -exp(A_log) softplus(u Wa + dt_bias) a
      value head; S <- e^g S; S <- S + beta k (v - S^T k)^T; o = S^T q; o /
      rms(o) * w_o * 2 sigmoid(u Wz) (`linear_sigmoid_gate_scale`); Wo
    FFN: W2(silu(min(W1 h, limit)) * clip(W3 h, -limit, limit)) (`swiglu_limit`);
      routed layers: s = sigmoid(h Wr) over all scored experts in float32, the
      K largest, weights scaling x s_e / (sum of the K), shared(h) + the sum
      over the chosen experts HELD HERE of w_e E_e(h)

What the absent experts would have added is left out, here as in the program
(the configuration's `deployment` says which chip this is). Every reading of a
key that names a mechanism and gives no code is listed in the configuration
file under `assumed`.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
LATENT, DELTA = "latent", "delta"
L2_EPS = 1e-6


def _norm(x, w, eps, gating=0.0):
    w = w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (gating * jax.nn.sigmoid(w) if gating else w)


def _swiglu(x, gate, up, down, limit):
    a, b = x @ gate.astype(F32), x @ up.astype(F32)
    if limit:
        a, b = jnp.minimum(a, limit), jnp.clip(b, -limit, limit)
    return (jax.nn.silu(a) * b) @ down.astype(F32)


class _Layer:
    """Layer i of a stack of layers, read lazily: `layer("wq")` is that
    weight's slice for this layer and `layer("w_gate", e)` expert e's, one
    index into the stacked array each."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __call__(self, name, *index):
        return self.stack[name][(self.i, *index)]


def _yarn_frequencies(width: int, model: dict):
    """The width // 2 frequencies of the roped columns: theta^(-2i/width), kept
    where a column turns more than beta_fast times over the original length,
    divided by the factor where it turns fewer than beta_slow times, a linear
    ramp over the columns between."""
    theta, scaling = float(model["rope_theta"]), model.get("rope_scaling")
    i = jnp.arange(width // 2, dtype=F32)
    base = theta ** (-2.0 * i / width)
    if not scaling:
        return base
    turns_at = lambda turns: width * math.log(
        scaling["original_max_position_embeddings"] / (turns * 2 * math.pi)) / (2 * math.log(theta))
    low = max(math.floor(turns_at(scaling["beta_fast"])), 0)
    high = min(math.ceil(turns_at(scaling["beta_slow"])), width - 1)
    divided = jnp.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return base * (1.0 - divided) + base / float(scaling["factor"]) * divided


def _softmax_factor(model: dict) -> float:
    """m^2 under `use_mla_scaling_factor`, m = 0.1 mscale_all_dim ln(factor) + 1; else 1."""
    scaling = model.get("rope_scaling")
    if not (scaling and model.get("use_mla_scaling_factor")):
        return 1.0
    m = 0.1 * float(scaling.get("mscale_all_dim", 1)) * math.log(float(scaling["factor"])) + 1.0
    return m * m


def _rotary(x, positions, frequencies):
    """x [B,S,...,w]: column i turns with column i + w/2 (rotate-half)."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[..., None] * frequencies
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _latent_mixer(u, lp, model, positions, allowed, norm):
    R, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    frequencies = _yarn_frequencies(model["qk_rope_head_dim"], model)
    cq = norm(u @ lp("wq_a").astype(F32), lp("q_norm"))
    q = jnp.einsum("bsr,rhk->bshk", cq, lp("wq_b").astype(F32))
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], positions, frequencies)
    ckr = u @ lp("wkv_a").astype(F32)
    c, k_rope = norm(ckr[..., :R], lp("kv_norm")), _rotary(ckr[..., R:], positions, frequencies)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, lp("wk_b").astype(F32))
    v = jnp.einsum("bsr,rhk->bshk", c, lp("wv_b").astype(F32))
    s = jnp.einsum("bqhk,bthk->bhqt", q_nope, k_nope) + jnp.einsum("bqhk,btk->bhqt", q_rope, k_rope)
    scale = _softmax_factor(model) / math.sqrt(q.shape[-1])
    p = jax.nn.softmax(jnp.where(allowed[:, None], s * scale, -jnp.inf), axis=-1)
    o = jnp.einsum("bhqt,bthk->bqhk", p, v)
    if model.get("gated_attention"):
        o = o * jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", u, lp("wg").astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", o, lp("wo").astype(F32))


def _delta_rule(q, k, v, g, beta):
    """One position a turn from S = 0: q, k [B,S,H,K], v [B,S,H,V], g and beta
    [B,S,H] (the decay a head, a scalar) -> o [B,S,H,V]."""
    B, _, H, K = q.shape

    def position(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None, None] * s
        read = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - read)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    over_time = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(position, jnp.zeros((B, H, K, v.shape[-1]), F32), over_time)[1], 0, 1)


def _delta_mixer(u, lp, model):
    H, Hk = model["linear_num_value_heads"], model["linear_num_key_heads"]
    taps = lp("conv").astype(F32)
    taps = taps.reshape(taps.shape[0], -1, taps.shape[-1])  # [T, 2 Hk + H, d], the oldest input's first
    T, S = taps.shape[0], u.shape[1]
    mixed, at = [], 0
    for name in ("wq", "wk", "wv"):
        w = jnp.pad(jnp.einsum("bsd,dhk->bshk", u, lp(name).astype(F32)), ((0, 0), (T - 1, 0), (0, 0), (0, 0)))
        n = w.shape[2]
        mixed.append(jax.nn.silu(sum(w[:, j:j + S] * taps[j, at:at + n] for j in range(T))))
        at += n
    q, k, v = mixed
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(q.shape[-1])
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    q, k = jnp.repeat(q, H // Hk, axis=2), jnp.repeat(k, H // Hk, axis=2)  # value head i reads key head i // (H / Hk)
    g = -jnp.exp(lp("a_log").astype(F32)) * jax.nn.softplus(
        jnp.einsum("bsd,dh->bsh", u, lp("wa").astype(F32)) + lp("dt_bias").astype(F32))
    beta = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", u, lp("wb").astype(F32)))
    o = _norm(_delta_rule(q, k, v, g, beta), lp("o_norm"), float(model["linear_attn_o_norm_eps"]))
    gate = float(model["linear_sigmoid_gate_scale"]) * jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", u, lp("wz").astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", o * gate, lp("wo").astype(F32))


def _dense_ffn(x, lp, width, limit):
    """The leading layers' SwiGLU, `width` columns of its hidden state at a
    time: the same sum (the clamp is a column's own), and no more of its
    weights in float32 at once."""
    every = slice(None)
    out = jnp.zeros_like(x)
    for a in range(0, lp.stack["w_gate"].shape[2], width):
        cols = slice(a, a + width)
        out = out + _swiglu(x, lp("w_gate", every, cols), lp("w_up", every, cols), lp("w_down", cols), limit)
    return out


def _routed_ffn(x, lp, model, limit):
    K, first = model["num_experts_per_tok"], model.get("first_expert", 0)
    logits = jnp.einsum("bsd,de->bse", x, lp("router").astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])

    def add_expert(j, out):  # the experts held here, one a turn
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        return out + mine[..., None] * _swiglu(x, lp("w_gate", j), lp("w_up", j), lp("w_down", j), limit)

    shared = _swiglu(x, lp("ws_gate"), lp("ws_up"), lp("ws_down"), limit)
    return jax.lax.fori_loop(0, model["n_routed_experts"], add_expert, shared)


def _kinds(model: dict) -> list:
    return [LATENT if l in model["full_attention_layers"] else DELTA for l in range(model["num_hidden_layers"])]


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. A packed batch is refused, as
    the program refuses it (the state and the convolution would cross
    documents)."""
    if segment_ids is not None:
        raise SystemExit("benchmark: gigachat3_5's delta layers are written for one document a row")
    eps, gating = float(model["rms_norm_eps"]), float(model.get("layernorm_gating_weight") or 0)
    limit, B, S = float(model.get("swiglu_limit") or 0), *tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    norm = lambda x, w: _norm(x, w, eps, gating)
    x = params["embed"][tokens].astype(F32)  # the rows read, not the table, in float32
    n_dense, later = model["first_k_dense_replace"], {}
    for l, kind in enumerate(_kinds(model)):
        if l < n_dense:
            lp = _Layer(params["dense_layers"], l)
        else:
            lp = _Layer(params["kind_layers"][kind], later.get(kind, 0))
            later[kind] = lp.i + 1
        u = norm(x, lp("attn_norm"))
        a = _latent_mixer(u, lp, model, positions, allowed, norm) if kind == LATENT else _delta_mixer(u, lp, model)
        x = x + norm(a, lp("post_attn_norm"))
        h = norm(x, lp("ffn_norm"))
        f = _dense_ffn(h, lp, model["moe_intermediate_size"], limit) if l < n_dense else _routed_ffn(h, lp, model, limit)
        x = x + norm(f, lp("post_ffn_norm"))
    return norm(x, params["final_norm"]) @ params["lm_head"].astype(F32)


def packed_loss(params, batch: dict, model: dict):
    """Mean next-token cross entropy of a packed batch: refused with the
    batch's segments (no train cell runs this architecture; the seam asks for
    the function)."""
    tok = batch["tokens"]
    lg = logits(params, tok[:, :-1], model, batch.get("segment_ids"))
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), tok[:, 1:, None], axis=-1)[..., 0]
    w = (batch["mask"][:, 1:] > 0).astype(F32)
    return jnp.sum(nll * w) / jnp.sum(w)


# ---------------------------------------------------------------------------
# What the harness asks of an architecture besides its reference
# ---------------------------------------------------------------------------

def _period(model: dict) -> list:
    """The kinds of one period: the shortest prefix of the layers' kinds that, repeated, gives them."""
    kinds = _kinds(model)
    return next(kinds[:p] for p in range(1, len(kinds) + 1) if all(kinds[l] == kinds[l % p] for l in range(len(kinds))))


def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The router
    stays `router_experts` wide; `n_routed_experts` of them are held here, from
    `first_expert` on. The two kinds of layer are LayerKinds "latent" and
    "delta"; the leading dense layers are of the kind `full_attention_layers`
    leaves them."""
    scaling = model.get("rope_scaling") or {}
    if (model.get("norm_type") != "ZeroCenteredGatedNorm" or model.get("layernorm_type") != "pre_post"
            or model.get("linear_attention_type") != "GigaChat35GatedDeltaNet" or not model.get("norm_topk_prob")
            or model.get("use_shared_expert_sigmoid") or model.get("n_group", 1) != 1
            or model["linear_key_head_dim"] != model["linear_value_head_dim"]
            or scaling.get("type", "yarn") != "yarn" or scaling.get("mscale", 1) != scaling.get("mscale_all_dim", 1)
            or model["qk_head_dim"] != model["qk_nope_head_dim"] + model["qk_rope_head_dim"]):
        raise SystemExit("benchmark: gigachat3_5 is written for gated norms before and after every sublayer, the "
                         "GigaChat35GatedDeltaNet mixer with key and value heads of one width, norm_topk_prob, an "
                         "ungated shared expert, one expert group, and YaRN whose mscale equals its mscale_all_dim")
    # Refused here, in the cell's driver and before a replica is started: a
    # program whose layer kinds cannot be latent, or whose delta kind has as
    # many key heads as value heads and a decay a channel (the parent of the
    # PR that brought this architecture), would fail in the replica's
    # constructor instead.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend

    kind = getattr(transformer, "LayerKind", None)
    missing = sorted({"n_key_heads", "gate_scale", "softmax_factor"} - {f.name for f in dataclasses.fields(kind)}
                     if kind else ["LayerKind"])
    missing += sorted({"norm_gating", "swiglu_limit"} - {f.name for f in dataclasses.fields(transformer.TransformerConfig)})
    if missing:
        raise SystemExit(
            "benchmark: this program cannot hold a gigachat3_5 configuration (a latent-attention layer beside "
            f"gated-delta-rule layers with grouped key heads, gated norms, a clamped SwiGLU): it has no {missing}")
    yarn = dict(yarn_factor=float(scaling["factor"]), yarn_original_len=scaling["original_max_position_embeddings"],
                yarn_beta_fast=float(scaling["beta_fast"]), yarn_beta_slow=float(scaling["beta_slow"]),
                softmax_factor=_softmax_factor(model)) if scaling else {}
    kinds = {
        LATENT: transformer.LayerKind(name=LATENT, n_heads=model["num_attention_heads"], mixer="latent",
                                      rope_theta=float(model["rope_theta"]), **yarn),
        DELTA: transformer.LayerKind(name=DELTA, n_heads=model["linear_num_value_heads"], mixer="delta",
                                     conv_size=model["linear_conv_kernel_dim"], n_key_heads=model["linear_num_key_heads"],
                                     gate_scale=float(model["linear_sigmoid_gate_scale"])),
    }
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], head_dim=model["linear_key_head_dim"], d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], norm_eps=float(model["rms_norm_eps"]), attention_impl="auto",
        layer_pattern=tuple(kinds[k] for k in _period(model)), n_dense_layers=model["first_k_dense_replace"],
        attn_gate="elementwise" if model.get("gated_attention") else "", sandwich_norm=True,
        norm_gating=float(model["layernorm_gating_weight"]), swiglu_limit=float(model.get("swiglu_limit") or 0),
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"],
        n_experts=model["router_experts"], expert_top_k=model["num_experts_per_tok"],
        experts_held=model["n_routed_experts"], first_expert=model.get("first_expert", 0),
        expert_d_ff=model["moe_intermediate_size"], n_shared_experts=model["n_shared_experts"],
        routed_scaling=float(model["routed_scaling_factor"]), router_score="sigmoid",
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts
    too; the depth, the dense count and `full_attention_layers` stay the
    file's (one leading dense layer and one period), key heads half the value
    heads as published."""
    model.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=32,
                 qk_nope_head_dim=32, qk_rope_head_dim=16, qk_head_dim=48, v_head_dim=32,
                 linear_key_head_dim=32, linear_value_head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
                 intermediate_size=256, moe_intermediate_size=64, router_experts=16, n_routed_experts=4,
                 num_experts_per_tok=4, vocab_size=512, max_position_embeddings=512)
    model["rope_scaling"] = dict(model["rope_scaling"], original_max_position_embeddings=64)


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of the expanded form a prompt runs in
    the latent layers: every head has keys of its own, nope + rope wide
    (harness/flops.py's attention-only counts read one kind of layer and are
    not reported in this architecture's cell)."""
    H = model["num_attention_heads"]
    return (_kinds(model).count(LATENT), H, H, model["qk_head_dim"])


def _parts(model: dict) -> dict:
    d, H, F = model["hidden_size"], model["num_attention_heads"], model["moe_intermediate_size"]
    Rq, R = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    Hv, Hk, dl, T = (model[k] for k in ("linear_num_value_heads", "linear_num_key_heads", "linear_key_head_dim",
                                        "linear_conv_kernel_dim"))
    gate = d * H * vd if model.get("gated_attention") else 0
    return {
        LATENT: d * Rq + Rq * H * (nope + rope) + d * (R + rope) + R * H * (nope + vd) + H * vd * d + gate,
        DELTA: 2 * d * Hk * dl + 3 * d * Hv * dl + 2 * d * Hv,  # wq, wk; wv, the gate, wo; beta and the decay
        "latent_small": Rq + R,  # the two inner norms
        "delta_small": T * (2 * Hk + Hv) * dl + 2 * Hv + dl,  # taps, a_log and dt_bias, the head norm: no matrix's
        "dense_ffn": 3 * d * model["intermediate_size"],
        "shared": 3 * d * F * model["n_shared_experts"], "router": d * model["router_experts"],
        "expert": 3 * d * F, "norms": 4 * d,
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies; of its K chosen experts the share
    held here, K x held / scored of one expert's parameters a routed layer,
    beside the shared one. `resident_matmul`: what lies on this chip (every
    held expert whole). `per_layer_matmul`: a routed layer's, at the mean of
    the kinds' mixers."""
    p, d, V, L = _parts(model), model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
    kinds, n_dense = _kinds(model), model["first_k_dense_replace"]
    n_routed = L - n_dense
    held, scored, K = model["n_routed_experts"], model["router_experts"], model["num_experts_per_tok"]
    mixers = sum(p[k] for k in kinds)
    common = mixers + n_dense * p["dense_ffn"] + n_routed * (p["shared"] + p["router"])
    head = 0 if model.get("tie_word_embeddings") else d * V
    a_token = K * held * p["expert"] // scored
    small = sum(p[k + "_small"] for k in kinds) + L * p["norms"] + d
    return {
        "embedding": V * d, "lm_head": head,
        "per_layer_matmul": mixers // L + p["shared"] + p["router"] + a_token,
        "matmul": common + n_routed * a_token + d * V,
        "resident_matmul": common + n_routed * held * p["expert"] + d * V,
        "total": V * d + head + common + n_routed * held * p["expert"] + small,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a routed layer (cellspec.routing)."""
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


GMM_CALLS_A_LAYER = 3  # gate, up and down, each one grouped matmul


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: `kda_step` once a delta layer (the
    one decode steps are counted from: the leading dense layers are of that
    kind), `latent_attn` once a latent layer, the grouped matmul three times a
    routed layer."""
    kinds = _kinds(model)
    return {"kda_step": kinds.count(DELTA), "latent_attn": kinds.count(LATENT),
            "expert_gmm": GMM_CALLS_A_LAYER * routing(model)}


def kda_step_needs(model: dict, rows: float) -> dict:
    """One delta layer's one-token rule, summed over calls: `rows` (slot,
    step) pairs. The work and not the implementation: a row's state, H x d x d
    float32, read and written once; q and k of its Hk key heads and v of its H
    value heads in float32, the decay and beta a scalar a head each (the
    kernel is handed the decay repeated over a head's d key channels: d times
    the bytes, counted by nobody here), o written; a head decays its state (1
    operation a value), reads it with k and with q and adds the rank-one
    update (2 each)."""
    H, Hk, d = model["linear_num_value_heads"], model["linear_num_key_heads"], model["linear_key_head_dim"]
    return {"flops": rows * H * 7.0 * d * d,
            "bytes": float(rows * (H * 2 * d * d * 4 + (2 * Hk + 2 * H) * d * 4 + 2 * H * 4))}


def kda_chunk_needs(model: dict, padded_tokens: float, chunk: int = 64, dtype_bytes: int = 2) -> dict:
    """One delta layer's rule over `padded_tokens` positions of prompts in
    chunks of `chunk`: the chunked form's matrix products (2 operations a
    multiply-add) a value head, as Solar's file counts them: the two [chunk,
    chunk] tables (2 x chunk^2 x d), the state read by keys and by queries and
    its update (3 x chunk x d^2), the triangular solve and the table's product
    with its result (2 x chunk^2 x d). Bytes: q and k of the Hk key heads and
    v of the H value heads read and o written in the activations' dtype, the
    decay and beta a float32 scalar a head."""
    H, Hk, d = model["linear_num_value_heads"], model["linear_num_key_heads"], model["linear_key_head_dim"]
    macs_a_token = 4 * chunk * d + 3 * d * d
    return {"flops": 2.0 * macs_a_token * H * padded_tokens,
            "bytes": float(padded_tokens * ((2 * Hk + 2 * H) * d * dtype_bytes + 2 * H * 4))}


def latent_decode_needs(model: dict, context_tokens: float, rows: float, dtype_bytes: int = 2) -> dict:
    """ONE latent layer's decode attention, summed over calls: `rows` (slot,
    step) pairs attending to `context_tokens` cached positions in all. A
    position's row ([c | k_rope], R + rope values: 1,152 bytes at the
    published widths, whatever the pool pads it to) is read once, for all
    heads and for scores and values alike; a row's absorbed queries are read
    and its H contexts of R values written once. Operations: a head scores
    R + rope columns and sums R, 2 each."""
    H, R, rope = model["num_attention_heads"], model["kv_lora_rank"], model["qk_rope_head_dim"]
    return {"flops": 2.0 * H * (R + rope + R) * context_tokens,
            "bytes": float((R + rope) * dtype_bytes * context_tokens + rows * H * (R + rope + R) * dtype_bytes)}


def expert_gmm_needs(model: dict, pairs: float, tiles: float, dtype_bytes: int = 2) -> dict:
    """One routed layer's three grouped matmuls, summed over steps: `pairs`
    (token, expert) pairs on held experts and `tiles` live tiles of their
    rows, both as the program counts them. A tile streams its expert's three
    matrices once; a pair multiplies them once (2 operations a parameter),
    reads its row twice (gate, up), writes and reads its hidden state and
    writes its result."""
    d, F = model["hidden_size"], model["moe_intermediate_size"]
    return {"flops": 2.0 * 3 * d * F * pairs,
            "bytes": float(3 * d * F * dtype_bytes * tiles + pairs * (3 * d + 3 * F) * dtype_bytes)}
