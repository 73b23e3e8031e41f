"""Laguna-S-2.1 (poolside) as an architecture of the benchmark, as ONE chip of
an expert-parallel deployment serves it: layers of two kinds in a fixed
pattern (`layer_types`: one full-attention layer, then three sliding ones with
a window of `sliding_window` positions), the kinds differing in query heads
(`num_attention_heads_per_layer`) and rope (`rope_parameters`: YaRN on half a
head for full layers, plain rope on the whole head for sliding ones), a
per-head sigmoid gate on the attention's output, a leading dense layer
(`mlp_only_layers`) before routed ones, a shared expert beside `num_experts`
routed experts HELD HERE out of the `router_experts` the router scores.
benchmarks/README.md, "An architecture", says what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy, no kernel, cache, ring or batching, a Python loop over layers and
over experts, the whole score matrix masked. It reads the program's parameter
tree (`dense_layers`, the leading layers; `kind_layers` {a kind's name as
`layer_types` spells it: that kind's later layers stacked in order}; wq
[L,D,H,d], wk / wv [L,D,KV,d], wg [L,D,H], wo [L,H,d,D], attn_norm, ffn_norm,
router [L,D,E_all], w_gate / w_up [L,E,D,F], w_down [L,E,F,D], ws_gate /
ws_up / ws_down) a layer at a time, an expert at a time and the dense FFN a
slice of `moe_intermediate_size` columns at a time, each with ONE index into
the stacked weight (`v[i, e]`, `v[i, :, a:b]`), so that
`refcheck.read_coarsely` rounds slices and the check holds no layer's 32
experts in float32 at once (1.2 GB).

    h = N(x); q = h Wq [H, d]; kk = h Wk, v = h Wv [KV, d]; N before each
    sublayer and none after; rope (rotate-half) on the first d x
    partial_rotary_factor columns, YaRN's frequencies and attention_factor on
    cos / sin where rope_type says yarn
    a_h = softmax(q_h kk_g^T / sqrt(d) + mask) v_g, g = h // (H / KV), mask
    causal and, in a sliding layer, i - W < j <= i
    x = x + concat_h(sigmoid(h Wg)_h a_h) Wo
    FFN: SwiGLU (leading layers), or s = sigmoid(h2 Wr) over all experts in
    float32, the K largest, weights scaling x s_e / (sum of the K),
    shared(h2) + sum over the chosen experts HELD HERE of w_e E_e(h2)

What the absent experts would have added is left out, here as in the program
(the configuration's `deployment` says which chip this is); the weights stay
normalised over all K chosen. Assumed, and listed in the configuration file:
the gate's function, input and place, no norm on q or kk, the router's score,
the window's ends, attention_factor on cos and sin, rotate-half pairing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
SLIDING = "sliding_attention"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _inv_freq(rope: dict, head_dim: int) -> np.ndarray:
    """A kind's rotation frequencies from its rope_parameters, float64."""
    r = int(head_dim * rope.get("partial_rotary_factor", 1))
    theta, i = float(rope["rope_theta"]), np.arange(r // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / r)
    if rope.get("rope_type", "default") != "yarn":
        return e
    turns_at = lambda t: r * math.log(rope["original_max_position_embeddings"] / (2 * math.pi * t)) / (2 * math.log(theta))
    low = min(max(math.floor(turns_at(rope["beta_fast"])), 0), r - 1)
    high = min(max(math.ceil(turns_at(rope["beta_slow"])), 0), r - 1)
    keep = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return e / rope["factor"] * (1.0 - keep) + e * keep


def _rotary(x, positions, rope: dict):
    """x [B,S,h,d]: rotate_half over the first r columns, the rest pass."""
    freq = _inv_freq(rope, x.shape[-1])
    r = 2 * len(freq)
    ang = positions.astype(F32)[:, :, None, None] * jnp.asarray(freq, F32)
    factor = float(rope.get("attention_factor", 1.0)) if rope.get("rope_type") == "yarn" else 1.0
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :r // 2], x[..., r // 2:r]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, x[..., r:]], axis=-1)


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


def _attention(h, lp, rope, window, positions, allowed):
    q = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp("wq").astype(F32)), positions, rope)
    k = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp("wk").astype(F32)), positions, rope)
    v = jnp.einsum("bsd,dhk->bshk", h, lp("wv").astype(F32))
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    if window:
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        allowed = allowed & (j > i - window)[None]
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    gate = jax.nn.sigmoid(h @ lp("wg").astype(F32))
    return jnp.einsum("bshk,hkd->bsd", a * gate[..., None], lp("wo").astype(F32))


def _dense_ffn(x, lp, width):
    """The leading layers' SwiGLU, `width` columns of its hidden state at a
    time: the same sum, and no more of its weights in float32 at once."""
    every = slice(None)
    out = jnp.zeros_like(x)
    for a in range(0, lp.stack["w_gate"].shape[2], width):
        cols = slice(a, a + width)
        out = out + _swiglu(x, lp("w_gate", every, cols), lp("w_up", every, cols), lp("w_down", cols))
    return out


def _routed_ffn(x, lp, model):
    K, first = model["num_experts_per_tok"], model.get("first_expert", 0)
    logits = jnp.einsum("bsd,de->bse", x, lp("router").astype(F32), precision="highest")
    top_s, top_e = jax.lax.top_k(jax.nn.sigmoid(logits), K)
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["moe_routed_scaling_factor"])
    out = _swiglu(x, lp("ws_gate"), lp("ws_up"), lp("ws_down"))
    for j in range(model["num_experts"]):  # the experts held here, one at a time
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp("w_gate", j), lp("w_up", j), lp("w_down", j))
    return out


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32."""
    eps, B, S = float(model["rms_norm_eps"]), *tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"][tokens].astype(F32)  # the rows read, not the table, in float32
    n_dense, later = len(model["mlp_only_layers"]), {}
    for l, kind in enumerate(model["layer_types"][:model["num_hidden_layers"]]):
        if l < n_dense:
            lp = _Layer(params["dense_layers"], l)
        else:
            lp = _Layer(params["kind_layers"][kind], later.get(kind, 0))
            later[kind] = lp.i + 1
        window = model["sliding_window"] if kind == SLIDING else 0
        h = _norm(x, lp("attn_norm"), eps)
        x = x + _attention(h, lp, model["rope_parameters"][kind], window, positions, allowed)
        h = _norm(x, lp("ffn_norm"), eps)
        x = x + (_dense_ffn(h, lp, model["moe_intermediate_size"]) if l < n_dense else _routed_ffn(h, lp, model))
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32)


def packed_loss(params, batch: dict, model: dict):
    """Mean next-token cross entropy over the targets inside documents of a
    packed batch (no train cell runs this architecture; the seam asks for it)."""
    tok, seg = batch["tokens"], batch["segment_ids"]
    lg = logits(params, tok[:, :-1], model, seg[:, :-1], batch["positions"][:, :-1])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), tok[:, 1:, None], axis=-1)[..., 0]
    w = ((seg[:, 1:] == seg[:, :-1]) & (batch["mask"][:, 1:] > 0)).astype(F32)
    return jnp.sum(nll * w) / jnp.sum(w)


# ---------------------------------------------------------------------------
# What the harness asks of an architecture besides its reference
# ---------------------------------------------------------------------------

def _pattern(model: dict) -> list:
    """[(kind's name, its query heads)] of one period: the shortest prefix of
    the per-layer lists that, repeated, gives them."""
    layers = list(zip(model["layer_types"], model["num_attention_heads_per_layer"]))[:model["num_hidden_layers"]]
    if len(layers) != model["num_hidden_layers"]:
        raise SystemExit("benchmark: laguna: the per-layer lists are shorter than num_hidden_layers")
    return next(layers[:p] for p in range(1, len(layers) + 1)
                if all(layers[l] == layers[l % p] for l in range(len(layers))))


def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The router
    stays `router_experts` wide; `num_experts` of them are held here, from
    `first_expert` on. A kind of layer is a LayerKind named as `layer_types`
    names it."""
    if (not model.get("norm_topk_prob") or model.get("moe_router_logit_softcapping")
            or model.get("moe_apply_router_weight_on_input") or model.get("gating") != "per-head"):
        raise SystemExit("benchmark: laguna is written for norm_topk_prob, no router softcapping, the "
                         "router's weight on the output and gating per-head")
    n_dense = len(model["mlp_only_layers"])
    if model["mlp_only_layers"] != list(range(n_dense)):
        raise SystemExit("benchmark: laguna is written for dense layers that lead")
    # Refused here, in the cell's driver and before a replica is started: a
    # program without layer kinds (the parent of the PR that brought this
    # architecture) would fail in the replica's constructor instead.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend

    fields = {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    missing = sorted({"head_dim", "layer_pattern", "attn_gate"} - fields)
    if missing or not hasattr(transformer, "LayerKind"):
        raise SystemExit(
            "benchmark: this program's TransformerConfig cannot hold a laguna configuration (window and full "
            f"attention layers of different head counts, a per-head gate, rope by layer kind): it has no "
            f"{missing or ['LayerKind']}")

    def kind(name, heads):
        rope = model["rope_parameters"][name]
        yarn = rope.get("rope_type") == "yarn"
        return transformer.LayerKind(
            name=name, n_heads=heads, window=model["sliding_window"] if name == SLIDING else 0,
            rope_theta=float(rope["rope_theta"]), rope_share=float(rope.get("partial_rotary_factor", 1)),
            yarn_factor=float(rope["factor"]) if yarn else 0.0,
            yarn_original_len=rope["original_max_position_embeddings"] if yarn else 0,
            yarn_beta_fast=float(rope.get("beta_fast", 32)), yarn_beta_slow=float(rope.get("beta_slow", 1)),
            attention_factor=float(rope.get("attention_factor", 1.0)) if yarn else 1.0)

    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]), attention_impl="auto", attn_gate="per_head",
        layer_pattern=tuple(kind(*k) for k in _pattern(model)), n_dense_layers=n_dense,
        n_experts=model["router_experts"], expert_top_k=model["num_experts_per_tok"],
        experts_held=model["num_experts"], first_expert=model.get("first_expert", 0),
        expert_d_ff=model["moe_intermediate_size"],
        n_shared_experts=model["shared_expert_intermediate_size"] // model["moe_intermediate_size"],
        routed_scaling=float(model["moe_routed_scaling_factor"]), router_score="sigmoid",
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts
    too; two kinds of head count (4 and 6 over 2 KV heads) and a window of two
    toy pages, which a rehearsal's contexts pass."""
    n = model["num_hidden_layers"]
    heads = {48: 4, 72: 6}
    model.update(hidden_size=128, head_dim=32, num_attention_heads=4, num_key_value_heads=2,
                 num_attention_heads_per_layer=[heads[h] for h in model["num_attention_heads_per_layer"][:n]],
                 intermediate_size=256, moe_intermediate_size=64, shared_expert_intermediate_size=64,
                 router_experts=16, num_experts=4, n_routed_experts=4, num_experts_per_tok=4, vocab_size=512,
                 max_position_embeddings=512, sliding_window=64)
    model["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 64


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of a full-attention layer; a
    sliding layer has more heads (harness/flops.py's attention-only counts
    read one head count and are not reported in this architecture's cells:
    `full_decode_needs` and `window_decode_needs` below take their place)."""
    return (model["num_hidden_layers"], model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"])


def _parts(model: dict) -> dict:
    d, hd, KV, F = model["hidden_size"], model["head_dim"], model["num_key_value_heads"], model["moe_intermediate_size"]
    attn = lambda H: 2 * d * H * hd + 2 * d * KV * hd + d * H  # wq, wo; wk, wv; the gate
    return {
        "attn": [attn(H) for H in model["num_attention_heads_per_layer"][:model["num_hidden_layers"]]],
        "dense_ffn": 3 * d * model["intermediate_size"],
        "shared": 3 * d * model["shared_expert_intermediate_size"], "router": d * model["router_experts"],
        "expert": 3 * d * F, "norms": 2 * d,
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies; of its K chosen experts the share
    held here, K x held / scored of one expert's parameters a routed layer.
    `resident_matmul`: what lies on this chip (every held expert whole).
    `per_layer_matmul`: a routed layer's, at the mean of its kinds' attention."""
    p, d, V = _parts(model), model["hidden_size"], model["vocab_size"]
    n_dense, L = len(model["mlp_only_layers"]), model["num_hidden_layers"]
    n_routed = L - n_dense
    held, scored, K = model["num_experts"], model["router_experts"], model["num_experts_per_tok"]
    attn = sum(p["attn"])
    common = attn + n_dense * p["dense_ffn"] + n_routed * (p["shared"] + p["router"])
    head = 0 if model.get("tie_word_embeddings") else d * V
    a_token = K * held * p["expert"] // scored
    return {
        "embedding": V * d, "lm_head": head,
        "per_layer_matmul": sum(p["attn"][n_dense:]) // n_routed + p["shared"] + p["router"] + a_token,
        "matmul": common + n_routed * a_token + d * V,
        "resident_matmul": common + n_routed * held * p["expert"] + d * V,
        "total": V * d + head + common + n_routed * held * p["expert"] + L * p["norms"] + d,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a routed layer (cellspec.routing)."""
    return model["num_hidden_layers"] - len(model["mlp_only_layers"])


GMM_CALLS_A_LAYER = 3  # gate, up and down, each one grouped matmul


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: the paged kernel once a full
    layer (`paged_attn`, the one decode steps are counted from), its windowed
    form once a sliding layer (`window_attn`), the grouped matmul three times
    a routed layer."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    sliding = kinds.count(SLIDING)
    return {"paged_attn": len(kinds) - sliding, "window_attn": sliding,
            "expert_gmm": GMM_CALLS_A_LAYER * routing(model)}


def _decode_needs(model: dict, heads: int, context_tokens: float, rows: float, dtype_bytes: int) -> dict:
    KV, hd = model["num_key_value_heads"], model["head_dim"]
    return {"flops": 4.0 * heads * hd * context_tokens,
            "bytes": float(2 * KV * hd * dtype_bytes * context_tokens + 2 * rows * heads * hd * dtype_bytes)}


def full_decode_needs(model: dict, context_tokens: float, rows: float, dtype_bytes: int = 2) -> dict:
    """One full-attention layer's decode attention, summed over calls: `rows`
    (slot, step) pairs attending to `context_tokens` cached positions in all.
    A position's K and V (2 x 8 x 128 values: 4,096 bytes) are read once for
    all heads, a head scores 128 columns and sums 128 (2 operations each), and
    a row's 48 queries are read and its 48 outputs written once."""
    return _decode_needs(model, model["num_attention_heads"], context_tokens, rows, dtype_bytes)


def window_decode_needs(model: dict, window_tokens: float, rows: float, dtype_bytes: int = 2) -> dict:
    """One sliding layer's, of `window_tokens` positions attended in all (at
    most the window a row, as the program counts them): the same bytes a
    position, 72 heads' operations. What the mathematics reads, whatever
    pages the kernel walks to read it."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    return _decode_needs(model, model["num_attention_heads_per_layer"][kinds.index(SLIDING)],
                         window_tokens, rows, dtype_bytes)


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
