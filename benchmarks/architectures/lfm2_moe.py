"""LFM2-24B-A2B (LiquidAI, `lfm2_moe`) as an architecture of the benchmark, ONE
chip that shares no layer: `layer_types` of gated short-convolution layers
("conv") and softmax GQA layers with a head norm on queries and keys before the
rope ("full_attention"), `num_dense_layers` leading layers with a dense SwiGLU
and behind them layers whose `num_experts` experts are ALL here, chosen by a
bias the weights leave out, and a tied head. benchmarks/README.md, "An
architecture", says what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy: no kernel, cache or batching, a Python loop over layers, the whole
score matrix masked, the convolution a sum of shifted copies, the experts ONE
AT A TIME in a `lax.fori_loop` (the program sorts pairs by expert and runs a
grouped matmul; this shares nothing with it). It reads the program's parameter
tree (`dense_layers`: the leading layers; `kind_layers` {"conv", "attention":
each kind's later layers, stacked in order}; `embed` is the head too) a layer
at a time and an expert at a time, each with ONE index into the stacked weight
(`v[i]`, `v[i, e]`), so that `refcheck.read_coarsely` rounds slices and the
check never holds a layer's 64 experts in float32 at once (2.4 GB). The loop
over experts is rolled, not unrolled: 8 layers x 64 experts written out are 512
copies of an expert in one program, which the TPU compiler takes minutes and
tens of GB of host memory for (PERF.md section 6, PR 38's 288 took 105 s).

    x = E[tokens]
    layer: x = x + Mixer(N_op(x)); x = x + FFN(N_ffn(x)); N an RMS norm with a
      weight, eps norm_eps; no bias anywhere
    conv mixer (T = conv_L_cache taps): [B | C | u] = h W_in (thirds in that
      order); z = B * u; c_t = sum_j w_j z_(t-T+1+j) a channel, zeros before
      position 0, no bias, no activation; (C * c) W_out
    attention mixer: q = h Wq [H, d]; k = h Wk, v = h Wv [KV, d]; q = N_q(q),
      k = N_k(k) over a head's d columns, one weight of d for all heads; THEN
      rope over the whole head (rotate-half, theta rope_parameters.rope_theta);
      a_h = softmax(q_h k_g^T / sqrt(d) + causal mask) v_g; a Wo
    FFN below num_dense_layers: (silu(h Wg) * (h Wu)) Wd, width intermediate_size
    FFN elsewhere: s = sigmoid(h Wr) in float32; the K experts with the largest
      s + b (use_expert_bias; b float32, no part of the weight); w_e =
      routed_scaling_factor s_e / (sum of the K chosen s + 1e-6)
      (norm_topk_prob); sum_e w_e E_e(h), each a SwiGLU of moe_intermediate_size
    logits = N_f(x) E^T

Assumed, and listed in the configuration file: the choice by the bias and the
1e-6 (the published module's routing as the catalog's keys describe it), the
tied head, the initial values of the taps and the bias.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
STACK = {CONV: "conv", ATTENTION: "attention"}  # a published layer type -> the LayerKind's name and stack


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


class _Layer:
    """Layer i of a stack of layers, read lazily: `layer("wq")` is that
    weight's slice for this layer and `layer("w_gate", e)` expert e's, one
    index into the stacked array each (e may be a loop's counter)."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __call__(self, name, *index):
        return self.stack[name][(self.i, *index)]

    def __contains__(self, name):
        return name in self.stack


def _rotary(x, positions, theta):
    """x [B, S, H, d] at `positions` [B, S]: rotate-half over the whole head."""
    half = x.shape[-1] // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=F32) / half)
    angles = positions.astype(F32)[:, :, None, None] * inv_freq  # [B, S, 1, half]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * jnp.cos(angles) - x2 * jnp.sin(angles),
                            x2 * jnp.cos(angles) + x1 * jnp.sin(angles)], axis=-1)


def _conv_mixer(h, lp):
    D, S = h.shape[-1], h.shape[1]
    bcu = h @ lp("w_in").astype(F32)
    gate_in, gate_out, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    taps = lp("conv").astype(F32)  # [T, D], the oldest input's first
    T = taps.shape[0]
    z = jnp.pad(gate_in * u, ((0, 0), (T - 1, 0), (0, 0)))  # zeros before position 0
    c = sum(z[:, j:j + S] * taps[j] for j in range(T))
    return (gate_out * c) @ lp("w_out").astype(F32)


def _attention_mixer(h, lp, positions, allowed, theta, eps):
    q = jnp.einsum("bsd,dhk->bshk", h, lp("wq").astype(F32))
    k = jnp.einsum("bsd,dhk->bshk", h, lp("wk").astype(F32))
    v = jnp.einsum("bsd,dhk->bshk", h, lp("wv").astype(F32))
    q, k = _norm(q, lp("q_norm"), eps), _norm(k, lp("k_norm"), eps)
    q, k = _rotary(q, positions, theta), _rotary(k, positions, theta)
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    return jnp.einsum("bshk,hkd->bsd", a, lp("wo").astype(F32))


def _routed_ffn(x, lp, model):
    K = model["num_experts_per_tok"]
    score = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp("router").astype(F32), precision="highest"))
    _, top_e = jax.lax.top_k(score + lp("router_bias").astype(F32), K)  # chosen with the bias
    top_s = jnp.take_along_axis(score, top_e, axis=-1)  # weighed without it
    weight = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6) * float(model["routed_scaling_factor"])

    def add_expert(e, out):
        mine = jnp.sum(jnp.where(top_e == e, weight, 0.0), axis=-1)  # [B, S]: 0 unless chosen
        return out + mine[..., None] * _swiglu(x, lp("w_gate", e), lp("w_up", e), lp("w_down", e))

    return jax.lax.fori_loop(0, model["num_experts"], add_expert, jnp.zeros_like(x))


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. A packed batch is refused, as
    the program refuses it (the convolution would cross documents)."""
    if segment_ids is not None:
        raise SystemExit("benchmark: lfm2_moe's convolution layers are written for one document a row")
    eps, theta = float(model["norm_eps"]), float(model["rope_parameters"]["rope_theta"])
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    x = params["embed"][tokens].astype(F32)  # the rows read, not the table, in float32
    later = {}
    for l, kind in enumerate(model["layer_types"]):
        if l < model["num_dense_layers"]:
            lp = _Layer(params["dense_layers"], l)
        else:
            lp = _Layer(params["kind_layers"][STACK[kind]], later.get(kind, 0))
            later[kind] = lp.i + 1
        h = _norm(x, lp("attn_norm"), eps)
        x = x + (_conv_mixer(h, lp) if kind == CONV else _attention_mixer(h, lp, positions, allowed, theta, eps))
        h = _norm(x, lp("ffn_norm"), eps)
        x = x + (_routed_ffn(h, lp, model) if "router" in lp
                 else _swiglu(h, lp("w_gate"), lp("w_up"), lp("w_down")))
    return _norm(x, params["final_norm"], eps) @ params["embed"].astype(F32).T


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

def _head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def _kinds(model: dict) -> list:
    kinds = list(model["layer_types"])
    if len(kinds) != model["num_hidden_layers"] or set(kinds) - {CONV, ATTENTION}:
        raise SystemExit("benchmark: lfm2_moe: layer_types names num_hidden_layers layers, each conv or full_attention")
    return kinds


def _period(model: dict) -> list:
    """The kinds of one period: the shortest prefix of `layer_types` that, repeated, gives them."""
    kinds = _kinds(model)
    return next(kinds[:p] for p in range(1, len(kinds) + 1) if all(kinds[l] == kinds[l % p] for l in range(len(kinds))))


def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The two kinds
    of layer are LayerKinds "conv" and "attention"; every one of `num_experts`
    is held here (`experts_held` = `n_experts`, `first_expert` 0)."""
    if (model.get("conv_bias") or not model.get("norm_topk_prob") or not model.get("use_expert_bias")
            or not model.get("tie_word_embeddings") or model["rope_parameters"].get("rope_type", "default") != "default"
            or len({k for k in _kinds(model)[:model["num_dense_layers"]]}) > 1):
        raise SystemExit("benchmark: lfm2_moe is written for a convolution without a bias, norm_topk_prob, the "
                         "router's selection bias, a tied head, plain rope and leading dense layers of one kind")
    # Refused here, in the cell's driver and before a replica is started: a
    # program without the convolution kind, the head norm or the router's bias
    # (the parent of the PR that brought this architecture) would fail in the
    # replica's constructor instead.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend

    missing = sorted({"qk_norm", "router_bias", "tie_embeddings", "experts_held", "layer_pattern"}
                     - {f.name for f in dataclasses.fields(transformer.TransformerConfig)})
    kind = getattr(transformer, "LayerKind", None)
    if kind is None or "mixer" not in {f.name for f in dataclasses.fields(kind)} or not kind("conv", 0, mixer="conv").recurrent:
        missing.append("LayerKind(mixer='conv')")
    if missing:
        raise SystemExit(
            "benchmark: this program's TransformerConfig cannot hold an lfm2_moe configuration (gated "
            "short-convolution layers whose tail is kept by slot, a head norm on q and k, a router that chooses by "
            f"a bias, every expert held and a tied head): it has no {missing}")
    kinds = {
        CONV: transformer.LayerKind(name=STACK[CONV], n_heads=0, mixer="conv", conv_size=model["conv_L_cache"]),
        ATTENTION: transformer.LayerKind(name=STACK[ATTENTION], n_heads=model["num_attention_heads"],
                                         rope_theta=float(model["rope_parameters"]["rope_theta"])),
    }
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], head_dim=_head_dim(model),
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["norm_eps"]), attention_impl="auto",
        layer_pattern=tuple(kinds[k] for k in _period(model)), n_dense_layers=model["num_dense_layers"],
        n_experts=model["num_experts"], expert_top_k=model["num_experts_per_tok"], experts_held=model["num_experts"],
        first_expert=0, expert_d_ff=model["moe_intermediate_size"],
        routed_scaling=float(model["routed_scaling_factor"]), router_score="sigmoid", router_bias=True,
        qk_norm=True, tie_embeddings=True,
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts too;
    `layer_types`, `num_dense_layers` and the depth stay as they are, and the
    two derived keys follow the counts they repeat."""
    model.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2, intermediate_size=256,
                 moe_intermediate_size=64, num_experts=8, num_experts_per_tok=2, vocab_size=512,
                 max_position_embeddings=512)
    model.update(n_routed_experts=model["num_experts"], first_k_dense_replace=model["num_dense_layers"])


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of the softmax layers: the
    "full_attention" ones of `layer_types` alone."""
    return (_kinds(model).count(ATTENTION), model["num_attention_heads"], model["num_key_value_heads"], _head_dim(model))


def _parts(model: dict) -> dict:
    d, hd, H, KV = model["hidden_size"], _head_dim(model), model["num_attention_heads"], model["num_key_value_heads"]
    return {
        CONV: d * 3 * d + d * d,  # the input projection [B | C | u]; the output's
        ATTENTION: 2 * d * H * hd + 2 * d * KV * hd,  # wq, wo; wk, wv
        CONV + "_small": model["conv_L_cache"] * d, ATTENTION + "_small": 2 * hd,  # taps; the two head norms
        "dense_ffn": 3 * d * model["intermediate_size"], "expert": 3 * d * model["moe_intermediate_size"],
        "router": d * model["num_experts"], "router_bias": model["num_experts"], "norms": 2 * d,
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies: the mixers, the leading layers'
    dense FFN, a routed layer's router and K of its experts, the tied head
    once more (the embedding's rows are read, its transpose multiplied).
    `resident_matmul`: what lies on this chip and a decode step reads: every
    expert of every routed layer. `per_layer_matmul`: a layer's, at the mean
    of the kinds' mixers and FFNs."""
    p, d, V, L = _parts(model), model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
    kinds, dense = _kinds(model), model["num_dense_layers"]
    E, K, routed = model["num_experts"], model["num_experts_per_tok"], L - dense
    mixers = sum(p[k] for k in kinds)
    common = mixers + dense * p["dense_ffn"] + routed * p["router"]
    small = sum(p[k + "_small"] for k in kinds) + routed * p["router_bias"] + L * p["norms"] + d
    return {
        "embedding": V * d, "lm_head": 0,  # tied: one array
        "per_layer_matmul": (common + routed * K * p["expert"]) // L,
        "matmul": common + routed * K * p["expert"] + d * V,
        "resident_matmul": common + routed * E * p["expert"] + d * V,
        "total": V * d + common + routed * E * p["expert"] + small,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a routed layer (cellspec.routing)."""
    return model["num_hidden_layers"] - model["num_dense_layers"]


GMM_CALLS_A_LAYER = 3  # gate, up and down, each one grouped matmul


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: the paged kernel once a softmax
    layer (`paged_attn`, the one decode steps are counted from), the grouped
    matmul three times a routed layer. A conv layer calls no kernel."""
    return {"paged_attn": _kinds(model).count(ATTENTION), "expert_gmm": GMM_CALLS_A_LAYER * routing(model)}


def full_decode_needs(model: dict, context_tokens: float, rows: float, dtype_bytes: int = 2) -> dict:
    """One softmax layer's decode attention, summed over calls: `rows` (slot,
    step) pairs attending to `context_tokens` cached positions in all. The
    work and not the implementation: a position's K and V are 2 x 8 x 64
    values (2,048 bytes), read once for all heads, whatever the pool's rows
    hold beside them (a lane tile of 128 columns a head: the kernel moves
    twice these bytes, so the share cannot pass a half); a head scores 64
    columns and sums 64 (2 operations each); a row's 32 queries are read and
    its 32 outputs written once."""
    H, KV, hd = model["num_attention_heads"], model["num_key_value_heads"], _head_dim(model)
    return {"flops": 4.0 * H * hd * context_tokens,
            "bytes": float(2 * KV * hd * dtype_bytes * context_tokens + 2 * rows * H * hd * dtype_bytes)}


def expert_gmm_needs(model: dict, pairs: float, tiles: float, dtype_bytes: int = 2) -> dict:
    """One routed layer's three grouped matmuls, summed over steps: `pairs`
    (token, expert) pairs (every one lands on an expert held here) and `tiles`
    live tiles of their rows, both as the program counts them. A tile streams
    its expert's three matrices once (3 x 2048 x 1536 x 2 = 18.9 MB); a pair
    multiplies them once (2 operations a parameter), reads its row twice
    (gate, up), writes and reads its hidden state and writes its result."""
    d, F = model["hidden_size"], model["moe_intermediate_size"]
    return {"flops": 2.0 * 3 * d * F * pairs,
            "bytes": float(3 * d * F * dtype_bytes * tiles + pairs * (3 * d + 3 * F) * dtype_bytes)}
