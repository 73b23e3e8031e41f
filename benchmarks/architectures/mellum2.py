"""Mellum2-12B-A2.5B (JetBrains) as an architecture of the benchmark, as ONE
chip of an expert-parallel deployment TRAINS it: layers of two kinds in a
fixed pattern (`layer_types`: three sliding layers with a window of
`sliding_window` positions, then one full-attention layer), the kinds
differing in rope (`rope_parameters`: plain for sliding layers, YaRN's
frequencies and `attention_factor` for full ones), every layer's FFN
`num_experts` routed experts HELD HERE out of the `router_experts` a softmax
router scores, no shared expert. benchmarks/README.md, "An architecture",
says what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy, no kernel, remat or passes, the whole score matrix masked, a Python
loop over layers; the loop over the held experts is rolled (`lax.fori_loop`,
one SwiGLU in the program whatever the count). It reads the program's
parameter tree (`kind_layers` {a kind's name as `layer_types` spells it: that
kind's layers stacked in order}; wq [L,D,H,d], wk / wv [L,D,KV,d], wo
[L,H,d,D], attn_norm, ffn_norm, router [L,D,E_all], w_gate / w_up [L,E,D,F],
w_down [L,E,F,D]).

    h = N(x); q = h Wq [H, d]; kk = h Wk, v = h Wv [KV, d]; N before each
    sublayer; rotate-half rope on the whole head, YaRN's frequencies and
    attention_factor on cos / sin where rope_type says yarn
    a_h = softmax(q_h kk_g^T / sqrt(d) + mask) v_g, g = h // (H / KV), mask
    causal inside a document and, in a sliding layer, i - W < j <= i
    x = x + concat_h(a_h) Wo
    h2 = N(x); p = softmax(h2 Wr) over all experts, float32; the K largest;
    w_e = p_e / (sum of the K); x = x + sum over the chosen experts HELD
    HERE of w_e E_e(h2)
    loss = mean next-token NLL inside documents + alpha x sum over layers of
    E_all x sum_i f_i P_i: f_i the share of the (token, choice) pairs on
    expert i (all K choices), P_i the mean of p_i, over ALL experts

What the absent experts would have added is left out, here as in the program
(the configuration's `deployment` says which chip this is). Assumed, and listed
in the configuration file: no norm on q or kk, softmax before the top-k, alpha,
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
    theta, i = float(rope["rope_theta"]), np.arange(head_dim // 2, dtype=np.float64)
    e = theta ** (-2.0 * i / head_dim)
    if rope.get("rope_type", "default") != "yarn":
        return e
    column = lambda turns: head_dim * math.log(  # noqa: E731
        rope["original_max_position_embeddings"] / (2 * math.pi * turns)) / (2 * math.log(theta))
    low = min(max(math.floor(column(rope["beta_fast"])), 0), head_dim - 1)
    high = min(max(math.ceil(column(rope["beta_slow"])), 0), head_dim - 1)
    keep = 1.0 - np.clip((i - low) / max(high - low, 0.001), 0.0, 1.0)
    return e / rope["factor"] * (1.0 - keep) + e * keep


def _rotary(x, positions, rope: dict):
    """x [B,S,h,d]: rotate_half over the whole head."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[:, :, None, None] * jnp.asarray(_inv_freq(rope, x.shape[-1]), F32)
    factor = float(rope.get("attention_factor", 1.0)) if rope.get("rope_type") == "yarn" else 1.0
    cos, sin = jnp.cos(ang) * factor, jnp.sin(ang) * factor
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _attention(h, lp, rope, window, positions, allowed):
    q = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(F32)), positions, rope)
    k = _rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(F32)), positions, rope)
    v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(F32))
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    if window:
        i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
        allowed = allowed & (j > i - window)[None]
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) / math.sqrt(d)
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    return jnp.einsum("bshk,hkd->bsd", a, lp["wo"].astype(F32))


def _routed_ffn(x, lp, model):
    """-> (the held experts' part of the layer's FFN, the layer's balance term over all experts)."""
    K, first = model["num_experts_per_tok"], model.get("first_expert", 0)
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, K)
    weight = top_p / jnp.sum(top_p, axis=-1, keepdims=True)

    def add_expert(j, out):  # the experts held here, one at a time
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        hidden = jax.nn.silu(x @ lp["w_gate"][j].astype(F32)) * (x @ lp["w_up"][j].astype(F32))
        return out + mine[..., None] * (hidden @ lp["w_down"][j].astype(F32))

    out = jax.lax.fori_loop(0, model["num_experts"], add_expert, jnp.zeros_like(x))
    E = p.shape[-1]
    f = jnp.mean(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1, 2))  # pairs on i / (tokens x K)
    return out, E * jnp.sum(f * jnp.mean(p, axis=(0, 1)))


def _logits_and_balance(params, tokens, model: dict, segment_ids=None, positions=None):
    eps, B, S = float(model["rms_norm_eps"]), *tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"][tokens].astype(F32)  # the rows read, not the table, in float32
    seen, balance = {}, jnp.zeros((), F32)
    for kind in model["layer_types"][:model["num_hidden_layers"]]:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        lp = {name: v[i] for name, v in params["kind_layers"][kind].items()}
        window = model["sliding_window"] if kind == SLIDING else 0
        h = _norm(x, lp["attn_norm"], eps)
        x = x + _attention(h, lp, model["rope_parameters"][kind], window, positions, allowed)
        out, term = _routed_ffn(_norm(x, lp["ffn_norm"], eps), lp, model)
        x, balance = x + out, balance + term
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32), balance


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32."""
    return _logits_and_balance(params, tokens, model, segment_ids, positions)[0]


def packed_loss(params, batch: dict, model: dict):
    """The training loss of a packed batch: the mean next-token cross entropy
    over the targets inside documents + alpha x the layers' balance terms."""
    tok, seg = batch["tokens"], batch["segment_ids"]
    lg, balance = _logits_and_balance(params, tok[:, :-1], model, seg[:, :-1], batch["positions"][:, :-1])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), tok[:, 1:, None], axis=-1)[..., 0]
    w = ((seg[:, 1:] == seg[:, :-1]) & (batch["mask"][:, 1:] > 0)).astype(F32)
    return jnp.sum(nll * w) / jnp.sum(w) + float(model["router_aux_loss_coef"]) * balance


# ---------------------------------------------------------------------------
# What the harness asks of an architecture besides its reference
# ---------------------------------------------------------------------------

def _period(model: dict) -> list:
    """The kinds of one period: the shortest prefix of layer_types that, repeated, gives it."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    if len(kinds) != model["num_hidden_layers"] or set(model["mlp_layer_types"][:len(kinds)]) != {"sparse"}:
        raise SystemExit("benchmark: mellum2 is written for num_hidden_layers entries of layer_types and every "
                         "layer's FFN sparse")
    return next(kinds[:p] for p in range(1, len(kinds) + 1) if all(kinds[l] == kinds[l % p] for l in range(len(kinds))))


def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The router
    stays `router_experts` wide; `num_experts` of them are held here, from
    `first_expert` on. A kind of layer is a LayerKind named as `layer_types`
    names it."""
    if not model.get("norm_topk_prob") or model.get("attention_bias") or model.get("tie_word_embeddings"):
        raise SystemExit("benchmark: mellum2 is written for norm_topk_prob, no attention bias and an untied head")
    # Refused here, in the cell's driver and before a worker holds the chip: a
    # program whose grouped matmul has no backward pass (the parent of the PR
    # that brought this architecture) would fail minutes later, inside
    # jax.grad of a pallas_call, or train through another path than the one
    # this cell measures.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend
    from ray_tpu.ops import grouped_matmul

    fields = {f.name for f in dataclasses.fields(transformer.TransformerConfig)}
    if "router_aux_coef" not in fields or not hasattr(grouped_matmul, "expert_tgmm"):
        raise SystemExit(
            "benchmark: this program cannot train a mellum2 configuration: its grouped matmul has no backward pass "
            "(ops/grouped_matmul.py has no expert_tgmm) and a layer of held experts hands no balance term to the "
            "loss (TransformerConfig has no router_aux_coef)")

    def kind(name):
        rope = model["rope_parameters"][name]
        yarn = rope.get("rope_type") == "yarn"
        return transformer.LayerKind(
            name=name, n_heads=model["num_attention_heads"], window=model["sliding_window"] if name == SLIDING else 0,
            rope_theta=float(rope["rope_theta"]),
            yarn_factor=float(rope["factor"]) if yarn else 0.0,
            yarn_original_len=rope["original_max_position_embeddings"] if yarn else 0,
            yarn_beta_fast=float(rope.get("beta_fast", 32)), yarn_beta_slow=float(rope.get("beta_slow", 1)),
            attention_factor=float(rope.get("attention_factor", 1.0)) if yarn else 1.0)

    kinds = {name: kind(name) for name in dict.fromkeys(_period(model))}
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]), attention_impl="auto",
        layer_pattern=tuple(kinds[name] for name in _period(model)),
        n_experts=model["router_experts"], expert_top_k=model["num_experts_per_tok"],
        experts_held=model["num_experts"], first_expert=model.get("first_expert", 0),
        expert_d_ff=model["moe_intermediate_size"], router_score="softmax",
        router_aux_coef=float(model["router_aux_loss_coef"]),
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts
    too; a window of 32 positions, which a rehearsal's documents (up to 128
    tokens) pass."""
    model.update(hidden_size=128, head_dim=32, num_attention_heads=4, num_key_value_heads=2,
                 intermediate_size=256, moe_intermediate_size=64, router_experts=16, num_experts=4,
                 n_routed_experts=4, num_experts_per_tok=4, vocab_size=512, max_position_embeddings=512,
                 sliding_window=32)
    model["rope_parameters"]["full_attention"]["original_max_position_embeddings"] = 64


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim); the kinds share them and differ in window and rope."""
    return (model["num_hidden_layers"], model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"])


def _parts(model: dict) -> dict:
    d, hd, H, KV = model["hidden_size"], model["head_dim"], model["num_attention_heads"], model["num_key_value_heads"]
    return {"attn": 2 * d * H * hd + 2 * d * KV * hd, "router": d * model["router_experts"],
            "expert": 3 * d * model["moe_intermediate_size"], "norms": 2 * d}


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies; of its K chosen experts the share
    held here under uniform routing, K x held / scored of one expert's
    parameters a layer. `resident_matmul`: what lies on this chip (every held
    expert whole). `total`: every parameter of the tree."""
    p, d, V, L = _parts(model), model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
    held, scored, K = model["num_experts"], model["router_experts"], model["num_experts_per_tok"]
    a_token = K * held * p["expert"] // scored
    common = L * (p["attn"] + p["router"])
    return {
        "embedding": V * d, "lm_head": d * V,
        "per_layer_matmul": p["attn"] + p["router"] + a_token,
        "matmul": common + L * a_token + d * V,
        "resident_matmul": common + L * held * p["expert"] + d * V,
        "total": 2 * V * d + common + L * held * p["expert"] + L * p["norms"] + d,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a layer (cellspec.routing)."""
    return model["num_hidden_layers"]


def live_pairs(doc_lens, window: int = 0) -> int:
    """(query, key) pairs of causal attention inside documents; with a window
    a query sees itself and the window - 1 positions before it: a band."""
    if not window:
        return sum(l * (l + 1) // 2 for l in doc_lens)
    return sum(l * (l + 1) // 2 if l <= window else window * (window + 1) // 2 + (l - window) * window
               for l in doc_lens)


def flash_train_needs(model: dict, doc_lens, kind: str, dtype_bytes: int = 2) -> dict:
    """ONE layer of `kind`'s flash forward + backward over documents of these
    lengths: two matmuls forward and four backward over the live pairs (the
    band's in a sliding layer), 12 H hd a pair; bytes: forward reads q, k, v
    and writes o, backward reads q, k, v, o, do and writes dq, dk, dv (row
    statistics ignored). Nothing recomputed counts."""
    _, H, KV, hd = attention_dims(model)
    n = sum(doc_lens)
    q, kv = n * H * hd * dtype_bytes, n * KV * hd * dtype_bytes
    pairs = live_pairs(doc_lens, model["sliding_window"] if kind == SLIDING else 0)
    return {"flops": 12.0 * H * hd * pairs, "bytes": float((2 * q + 2 * kv) + (4 * q + 4 * kv))}


def expert_train_needs(model: dict, tokens: float, dtype_bytes: int = 2) -> dict:
    """ONE layer's grouped matmuls forward and backward over `tokens`
    positions: nine products (gate, up, down; each forward, dx and dW) over
    the pairs uniform routing sends to the experts held here, tokens x K x
    held / scored (EXPECTED, not counted: a train step's counters do not
    reach a reader, so a share made of this is right only while the routing
    is even), 2 operations a parameter a pair each. Bytes: every held
    expert's three matrices read by the forward, read by dx and written by dW
    once, and a pair's rows in and out of each product (3 d + 3 F values a
    product triple). Nothing recomputed counts."""
    d, F = model["hidden_size"], model["moe_intermediate_size"]
    held = model["num_experts"]
    pairs = tokens * model["num_experts_per_tok"] * held / model["router_experts"]
    return {"flops": 3 * 2.0 * 3 * d * F * pairs,
            "bytes": float(3 * (3 * d * F * held + pairs * (3 * d + 3 * F)) * dtype_bytes)}


def train_needs(model: dict, doc_lens) -> dict:
    """The whole step over documents of these lengths: 6 operations a matmul
    parameter a token multiplies (`param_counts`' `matmul`: 8 x 16 / 64
    experts a token a layer, as uniform routing expects) plus attention over
    the causal pairs in the full layers and the banded pairs in the sliding
    ones. Nothing recomputed counts."""
    kinds = model["layer_types"][:model["num_hidden_layers"]]
    attention = sum(flash_train_needs(model, doc_lens, kind)["flops"] for kind in kinds)
    return {"flops": 6.0 * param_counts(model)["matmul"] * sum(doc_lens) + attention}
