"""Solar-Open2-250B (upstage) as an architecture of the benchmark, as ONE chip
of an expert-parallel deployment serves it: periods of one softmax GQA layer
without positions (`gqa_layers`, `use_rope` false, `use_gqa_gate`) and three
layers of Kimi delta attention (`linear_attn_config`, `kda_*`), each followed
by a shared expert beside `n_routed_experts` routed experts HELD HERE out of
the `router_experts` the router scores. benchmarks/README.md, "An
architecture", says what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy: no kernel, chunk, cache or batching, a Python loop over layers and
over experts, the whole score matrix masked, and the delta rule as a
SEQUENTIAL scan over positions (`jax.lax.scan`, one position a turn: the
program's chunked form and its kernels share nothing with it). It reads the
program's parameter tree (`kind_layers` {"gqa": the softmax layers, "kda":
the delta layers, stacked in order}) a layer at a time and an expert at a
time, each with ONE index into the stacked weight (`v[i]`, `v[i, e]`), so
that `refcheck.read_coarsely` rounds slices and the check holds no layer's 40
experts in float32 at once (2.5 GB).

    softmax layer: h = N(x); q = h Wq [H, d]; kk = h Wk, v = h Wv [KV, d];
      no rope; a_h = softmax(q_h kk_g^T / sqrt(d) + causal mask) v_g;
      x = x + (a * sigmoid(h Wg)) Wo, Wg [D, H, d]
    delta layer: h = N(x); q~, k~, v~ = h Wq, h Wk, h Wv [Hl, dl], each
      through a causal depthwise convolution of T taps (zeros before position
      0) and SiLU; q = q' / |q'| / sqrt(dl), k = k' / |k'|;
      g_t = -exp(a_log) softplus((h Wf_a) Wf_b + dt_bias) in R^(Hl x dl),
      beta_t = 2 sigmoid(h Wb) in R^Hl (2: kda_allow_neg_eigval);
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_(t-1) + beta_t k_t v_t^T,
      o_t = S_t^T q_t; x = x + (N_head(o) * sigmoid((h Wg_a) Wg_b)) Wo
    FFN on N(x): s = sigmoid(h2 Wr) over all scored experts in float32, the K
      largest, weights scaling x s_e / (sum of the K), shared(h2) + sum over
      the chosen experts HELD HERE of w_e E_e(h2)

What the absent experts would have added is left out, here as in the program
(the configuration's `deployment` says which chip this is). Assumed, and
listed in the configuration file: the low-rank form and rank of the decay and
gate projections, the float32 state, the elementwise gate and no q/k norm in
a softmax layer, the router's score, 1e-6 under the root of |q'| and |k'|.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
GQA, KDA = "gqa", "kda"
L2_EPS = 1e-6


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


class _Layer:
    """Layer i of a stack of layers, read lazily: `layer("wq")` is that
    weight's slice for this layer and `layer("w_gate", e)` expert e's, one
    index into the stacked array each."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __call__(self, name, *index):
        return self.stack[name][(self.i, *index)]


def _softmax_mixer(h, lp, allowed):
    q = jnp.einsum("bsd,dhk->bshk", h, lp("wq").astype(F32))
    k = jnp.einsum("bsd,dhk->bshk", h, lp("wk").astype(F32))
    v = jnp.einsum("bsd,dhk->bshk", h, lp("wv").astype(F32))
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", h, lp("wg").astype(F32)))
    return jnp.einsum("bshk,hkd->bsd", a * gate, lp("wo").astype(F32))


def _delta_rule(q, k, v, g, beta):
    """One position a turn from S = 0: q, k, g [B,S,H,K], v [B,S,H,V], beta
    [B,S,H] -> o [B,S,H,V]."""
    B, _, H, K = q.shape

    def position(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = jnp.exp(g_t)[..., None] * s
        read = jnp.einsum("bhkv,bhk->bhv", s, k_t)
        s = s + (b_t[..., None] * k_t)[..., None] * (v_t - read)[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    over_time = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v, g, beta))
    return jnp.moveaxis(jax.lax.scan(position, jnp.zeros((B, H, K, v.shape[-1]), F32), over_time)[1], 0, 1)


def _delta_mixer(h, lp, model, eps):
    taps = lp("conv").astype(F32)  # [T, 3, Hl, dl], the oldest input's first
    T, S = taps.shape[0], h.shape[1]
    mixed = []
    for i, name in enumerate(("wq", "wk", "wv")):
        u = jnp.pad(jnp.einsum("bsd,dhk->bshk", h, lp(name).astype(F32)), ((0, 0), (T - 1, 0), (0, 0), (0, 0)))
        mixed.append(jax.nn.silu(sum(u[:, j:j + S] * taps[j, i] for j in range(T))))
    q, k, v = mixed
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + L2_EPS) / math.sqrt(q.shape[-1])
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + L2_EPS)
    f = jnp.einsum("bsr,rhk->bshk", h @ lp("wf_a").astype(F32), lp("wf_b").astype(F32))
    g = -jnp.exp(lp("a_log").astype(F32))[:, None] * jax.nn.softplus(f + lp("dt_bias").astype(F32))
    scale = 2.0 if model["kda_allow_neg_eigval"] else 1.0
    beta = scale * jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, lp("wb").astype(F32)))
    o = _norm(_delta_rule(q, k, v, g, beta), lp("o_norm"), eps)
    gate = jnp.einsum("bsr,rhk->bshk", h @ lp("wg_a").astype(F32), lp("wg_b").astype(F32))
    return jnp.einsum("bshk,hkd->bsd", o * jax.nn.sigmoid(gate), lp("wo").astype(F32))


def _routed_ffn(x, lp, model):
    K, first = model["num_experts_per_tok"], model.get("first_expert", 0)
    logits = jnp.einsum("bsd,de->bse", x, lp("router").astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])
    out = _swiglu(x, lp("ws_gate"), lp("ws_up"), lp("ws_down"))
    for j in range(model["n_routed_experts"]):  # the experts held here, one at a time
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp("w_gate", j), lp("w_up", j), lp("w_down", j))
    return out


def _kinds(model: dict) -> list:
    return [GQA if l in model["gqa_layers"] else KDA for l in range(model["num_hidden_layers"])]


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. No layer reads `positions`
    (no rope); a packed batch is refused, as the program refuses it."""
    if segment_ids is not None:
        raise SystemExit("benchmark: solar_open2's delta layers are written for one document a row")
    eps, B, S = float(model["rms_norm_eps"]), *tokens.shape
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    x = params["embed"][tokens].astype(F32)  # the rows read, not the table, in float32
    later = {}
    for kind in _kinds(model):
        lp = _Layer(params["kind_layers"][kind], later.get(kind, 0))
        later[kind] = lp.i + 1
        h = _norm(x, lp("attn_norm"), eps)
        x = x + (_softmax_mixer(h, lp, allowed) if kind == GQA else _delta_mixer(h, lp, model, eps))
        x = x + _routed_ffn(_norm(x, lp("ffn_norm"), eps), lp, model)
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32)


def packed_loss(params, batch: dict, model: dict):
    """Mean next-token cross entropy of a packed batch: refused with the
    batch's segments (no train cell runs this architecture; the seam asks
    for the function)."""
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
    stays `router_experts` wide; `n_routed_experts` of them are held here,
    from `first_expert` on. The two kinds of layer are LayerKinds "gqa" and
    "kda"."""
    lin = model["linear_attn_config"]
    if (model.get("use_rope") or not model.get("use_gqa_gate") or model.get("kda_use_full_proj")
            or not model.get("norm_topk_prob") or model.get("first_k_dense_replace")
            or lin.get("num_kv_heads") not in (None, lin["num_heads"]) or lin["head_dim"] != model["head_dim"]):
        raise SystemExit("benchmark: solar_open2 is written for softmax layers without rope and with a gate, the "
                         "low-rank decay projection, delta heads of the softmax heads' width with as many key "
                         "heads, norm_topk_prob and no leading dense layer")
    # Refused here, in the cell's driver and before a replica is started: a
    # program whose layer kinds are all softmax attention (the parent of the
    # PR that brought this architecture) would fail in the replica's
    # constructor instead.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend

    kind = getattr(transformer, "LayerKind", None)
    missing = sorted({"mixer", "conv_size", "low_rank", "beta_scale"} - {f.name for f in dataclasses.fields(kind)}
                     if kind else ["LayerKind"])
    if missing:
        raise SystemExit(
            "benchmark: this program's LayerKind cannot hold a solar_open2 configuration (delta-rule "
            f"linear-attention layers whose state is kept by slot): it has no {missing}")
    kinds = {
        GQA: transformer.LayerKind(name=GQA, n_heads=model["num_attention_heads"], rope_share=0.0),
        KDA: transformer.LayerKind(name=KDA, n_heads=lin["num_heads"], mixer="delta",
                                   conv_size=lin["short_conv_kernel_size"], low_rank=lin["head_dim"],
                                   beta_scale=2.0 if model["kda_allow_neg_eigval"] else 1.0),
    }
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]), attention_impl="auto", attn_gate="elementwise",
        layer_pattern=tuple(kinds[k] for k in _period(model)),
        n_experts=model["router_experts"], expert_top_k=model["num_experts_per_tok"],
        experts_held=model["n_routed_experts"], first_expert=model.get("first_expert", 0),
        expert_d_ff=model["moe_intermediate_size"], n_shared_experts=model["n_shared_experts"],
        routed_scaling=float(model["routed_scaling_factor"]), router_score="sigmoid",
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts too."""
    model.update(hidden_size=128, head_dim=32, num_attention_heads=4, num_key_value_heads=2, intermediate_size=256,
                 moe_intermediate_size=64, router_experts=16, n_routed_experts=4, num_experts_per_tok=4,
                 vocab_size=512, max_position_embeddings=512)
    model["linear_attn_config"] = dict(model["linear_attn_config"], head_dim=32, num_heads=4)


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of a softmax layer (harness/flops.py's
    attention-only counts read one kind of layer; of this architecture's
    `num_hidden_layers` only the `gqa_layers` are such)."""
    return (model["num_hidden_layers"], model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"])


def _parts(model: dict) -> dict:
    d, hd, H, KV = model["hidden_size"], model["head_dim"], model["num_attention_heads"], model["num_key_value_heads"]
    lin, F = model["linear_attn_config"], model["moe_intermediate_size"]
    Hl, dl, T = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    low_rank = d * dl + dl * Hl * dl  # through `dl` columns: the decay's, and the gate's the same
    return {
        GQA: 2 * d * H * hd + 2 * d * KV * hd + d * H * hd,  # wq, wo; wk, wv; the gate
        KDA: 4 * d * Hl * dl + 2 * low_rank + d * Hl,  # wq, wk, wv, wo; decay and gate; beta
        "kda_small": T * 3 * Hl * dl + Hl * dl + Hl + dl,  # taps, dt_bias, a_log, the head norm: multiplied by no matrix
        "shared": 3 * d * F * model["n_shared_experts"], "router": d * model["router_experts"],
        "expert": 3 * d * F, "norms": 2 * d,
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies; of its K chosen experts the share
    held here, K x held / scored of one expert's parameters a layer.
    `resident_matmul`: what lies on this chip (every held expert whole).
    `per_layer_matmul`: a layer's, at the mean of the kinds' mixers."""
    p, d, V, L = _parts(model), model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
    kinds = _kinds(model)
    held, scored, K = model["n_routed_experts"], model["router_experts"], model["num_experts_per_tok"]
    mixers = sum(p[k] for k in kinds)
    common = mixers + L * (p["shared"] + p["router"])
    head = 0 if model.get("tie_word_embeddings") else d * V
    a_token = K * held * p["expert"] // scored
    return {
        "embedding": V * d, "lm_head": head,
        "per_layer_matmul": mixers // L + p["shared"] + p["router"] + a_token,
        "matmul": common + L * a_token + d * V,
        "resident_matmul": common + L * held * p["expert"] + d * V,
        "total": V * d + head + common + L * held * p["expert"] + kinds.count(KDA) * p["kda_small"] + L * p["norms"] + d,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a layer (cellspec.routing)."""
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


GMM_CALLS_A_LAYER = 3  # gate, up and down, each one grouped matmul


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: the paged kernel once a softmax
    layer (`paged_attn`, the one decode steps are counted from), `kda_step`
    once a delta layer, the grouped matmul three times a layer."""
    kinds = _kinds(model)
    return {"paged_attn": kinds.count(GQA), "kda_step": kinds.count(KDA), "expert_gmm": GMM_CALLS_A_LAYER * routing(model)}


def kda_step_needs(model: dict, rows: float) -> dict:
    """One delta layer's one-token rule, summed over calls: `rows` (slot,
    step) pairs. The work and not the implementation: a row's state, Hl x dl
    x dl float32, read and written once; its q, k, v, g in float32 and beta;
    a head decays its state (1 operation a value), reads it with k and with q
    and adds the rank-one update (2 each)."""
    lin = model["linear_attn_config"]
    Hl, dl = lin["num_heads"], lin["head_dim"]
    return {"flops": rows * Hl * 7.0 * dl * dl,
            "bytes": float(rows * Hl * (2 * dl * dl * 4 + 4 * dl * 4 + 4 + dl * 4))}  # the last: o


def kda_chunk_needs(model: dict, padded_tokens: float, chunk: int = 64, dtype_bytes: int = 2) -> dict:
    """One delta layer's rule over `padded_tokens` positions of prompts in
    chunks of `chunk`: the chunked form's matrix products (2 operations a
    multiply-add), whatever an implementation adds to solve the chunk's
    triangular system. A chunk of a head: the two [chunk, chunk] tables from
    keys and queries (2 x chunk^2 x dl), the state read by keys and by
    queries and its update (3 x chunk x dl^2), the triangular solve and the
    table's product with its result (2 x chunk^2 x dl). Bytes: q, k, v read
    and o written in the activations' dtype, g in float32, beta."""
    lin = model["linear_attn_config"]
    Hl, dl = lin["num_heads"], lin["head_dim"]
    macs_a_token = 4 * chunk * dl + 3 * dl * dl
    return {"flops": 2.0 * macs_a_token * Hl * padded_tokens,
            "bytes": float(padded_tokens * Hl * (4 * dl * dtype_bytes + dl * 4 + 4))}


def expert_gmm_needs(model: dict, pairs: float, tiles: float, dtype_bytes: int = 2) -> dict:
    """One layer's three grouped matmuls, summed over steps: `pairs` (token,
    expert) pairs on held experts and `tiles` live tiles of their rows, both
    as the program counts them. A tile streams its expert's three matrices
    once; a pair multiplies them once (2 operations a parameter), reads its
    row twice (gate, up), writes and reads its hidden state and writes its
    result."""
    d, F = model["hidden_size"], model["moe_intermediate_size"]
    return {"flops": 2.0 * 3 * d * F * pairs,
            "bytes": float(3 * d * F * dtype_bytes * tiles + pairs * (3 * d + 3 * F) * dtype_bytes)}
