"""openPangu-Ultra-MoE (the DeepSeek-V3 family's layer with sandwich norms) as
an architecture of the benchmark, as ONE chip of an expert-parallel deployment
serves it: latent attention (low-rank query and key/value projections, a roped
key part all heads share), a norm after each sublayer as well as before it,
leading dense layers before routed ones, a shared expert beside `n_routed_experts`
routed experts HELD HERE out of the `router_experts` the router scores.
benchmarks/README.md, "An architecture", says what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy, no kernel, cache, batching or absorbed projection, a Python loop
over layers and over experts. It reads the program's parameter tree
(`dense_layers` then `layers`; wq_a [L,D,Rq], q_norm, wq_b [L,Rq,H,nope+rope],
wkv_a [L,D,R+rope], kv_norm, wk_b [L,R,H,nope], wv_b [L,R,H,v], wo [L,H,v,D],
the four sandwich norms, router [L,D,E_all], w_gate / w_up [L,E,D,F], w_down
[L,E,F,D], ws_gate / ws_up / ws_down) a layer at a time, an expert at a time
and the dense FFN a slice of `moe_intermediate_size` columns at a time, each
with ONE index into the stacked weight (`v[i, e]`, `v[i, :, a:b]`: a layer's
slice taken first, `v[i][e]`, is a copy of the layer's 16 experts, 0.5 GB a
matrix, that the compiler keeps), so that `refcheck.read_coarsely` rounds
slices and the check's temporaries stay under a gigabyte: a whole routed
layer in float32 is 4.0 GB and does not fit beside 12.8 GB.

    h = x + N2(Attn(N1(x)))            y = h + N4(FFN(N3(h)))
    cq = Nq(x Wqa); q = cq Wqb, a head's [q_nope | q_rope], rope on q_rope
    [ckv | kr] = x Wkva; c = Nkv(ckv); k_rope = rope(kr), one for all heads
    scores (q_nope_h . (c Wkb_h) + q_rope_h . k_rope) / sqrt(nope + rope)
    FFN: SwiGLU (leading layers), or s = sigmoid(x Wr) over all experts in
    float32, the K largest, weights s_e / (sum of the K) * scaling,
    shared(x) + sum over the chosen experts HELD HERE of w_e E_e(x)

What the absent experts would have added is left out, here as in the program
(the configuration's `deployment` says which chip this is); the weights stay
normalised over all K chosen. Assumed, and listed in the configuration file:
the score function, rotate-half rope pairing, no multi-token-prediction block.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rotary(x, positions, theta):
    """x [B,S,...,w]: rotate_half convention (first half with second half)."""
    half = x.shape[-1] // 2
    ang = positions.astype(F32)[..., None] * theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = ang.reshape(ang.shape[:2] + (1,) * (x.ndim - 3) + (half,))
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * jnp.cos(ang) - b * jnp.sin(ang), b * jnp.cos(ang) + a * jnp.sin(ang)], axis=-1)


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate.astype(F32)) * (x @ up.astype(F32))) @ down.astype(F32)


class _Layer:
    """Layer i of a stack of layers, read lazily: `layer("wq_a")` is that
    weight's slice for this layer and `layer("w_gate", e)` expert e's, one
    index into the stacked array each."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __call__(self, name, *index):
        return self.stack[name][(self.i, *index)]


def _attention(x, lp, model, positions, allowed):
    eps, theta = float(model["rms_norm_eps"]), float(model["rope_theta"])
    R, nope = model["kv_lora_rank"], model["qk_nope_head_dim"]
    cq = _norm(x @ lp("wq_a").astype(F32), lp("q_norm"), eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, lp("wq_b").astype(F32))
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], positions, theta)
    ckr = x @ lp("wkv_a").astype(F32)
    c, k_rope = _norm(ckr[..., :R], lp("kv_norm"), eps), _rotary(ckr[..., R:], positions, theta)
    k_nope = jnp.einsum("bsr,rhk->bshk", c, lp("wk_b").astype(F32))
    v = jnp.einsum("bsr,rhk->bshk", c, lp("wv_b").astype(F32))
    s = jnp.einsum("bqhk,bthk->bhqt", q_nope, k_nope) + jnp.einsum("bqhk,btk->bhqt", q_rope, k_rope)
    p = jax.nn.softmax(jnp.where(allowed[:, None], s / jnp.sqrt(F32(q.shape[-1])), -jnp.inf), axis=-1)
    return jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhqt,bthk->bqhk", p, v), lp("wo").astype(F32))


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
    weight = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * float(model["routed_scaling_factor"])
    out = _swiglu(x, lp("ws_gate"), lp("ws_up"), lp("ws_down"))
    for j in range(model["n_routed_experts"]):  # the experts held here, one at a time
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
    n_dense = model["first_k_dense_replace"]
    for name, n in (("dense_layers", n_dense), ("layers", model["num_hidden_layers"] - n_dense)):
        for i in range(n):
            lp = _Layer(params[name], i)
            x = x + _norm(_attention(_norm(x, lp("attn_norm"), eps), lp, model, positions, allowed),
                          lp("post_attn_norm"), eps)
            h = _norm(x, lp("ffn_norm"), eps)
            f = _routed_ffn(h, lp, model) if name == "layers" else _dense_ffn(h, lp, model["moe_intermediate_size"])
            x = x + _norm(f, lp("post_ffn_norm"), eps)
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

def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The router
    stays `router_experts` wide; `n_routed_experts` of them are held here,
    from `first_expert` on."""
    if not (model.get("sandwich_norm") and model.get("norm_topk_prob")):
        raise SystemExit("benchmark: pangu_ultra_moe is written for sandwich_norm and norm_topk_prob")
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], d_ff=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], rope_theta=float(model["rope_theta"]),
        norm_eps=float(model["rms_norm_eps"]), attention_impl="auto", attention_kind="latent",
        q_lora_rank=model["q_lora_rank"], kv_lora_rank=model["kv_lora_rank"],
        qk_nope_head_dim=model["qk_nope_head_dim"], qk_rope_head_dim=model["qk_rope_head_dim"],
        v_head_dim=model["v_head_dim"], sandwich_norm=True, n_dense_layers=model["first_k_dense_replace"],
        n_experts=model["router_experts"], expert_top_k=model["num_experts_per_tok"],
        experts_held=model["n_routed_experts"], first_expert=model.get("first_expert", 0),
        expert_d_ff=model["moe_intermediate_size"], n_shared_experts=model["n_shared_experts"],
        routed_scaling=float(model["routed_scaling_factor"]), router_score="sigmoid",
        **(model.get("transformer") or {}),
    )
    # Refused here, in the cell's driver and before a replica is started: a
    # program without these fields (the parent of the PR that brought this
    # architecture) would fail in the replica's constructor instead.
    import dataclasses

    from ray_tpu.models.transformer import TransformerConfig  # imports jax, touches no backend

    missing = sorted(set(kwargs) - {f.name for f in dataclasses.fields(TransformerConfig)})
    if missing:
        raise SystemExit(f"benchmark: this program's TransformerConfig cannot hold a pangu_ultra_moe "
                         f"configuration (latent attention, sandwich norms, held experts): it has no {missing}")
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count, experts too."""
    model.update(hidden_size=128, num_hidden_layers=3, first_k_dense_replace=1, num_attention_heads=4,
                 num_key_value_heads=4, q_lora_rank=64, kv_lora_rank=32, qk_nope_head_dim=32,
                 qk_rope_head_dim=16, v_head_dim=32, intermediate_size=256, moe_intermediate_size=64,
                 router_experts=16, n_routed_experts=4, num_experts_per_tok=4, vocab_size=512,
                 max_position_embeddings=512)


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of the expanded form a prompt
    runs: every head has keys of its own, nope + rope wide (values are
    v_head_dim wide; harness/flops.py's attention-only counts read one width
    and are not reported in this architecture's cells)."""
    H = model["num_attention_heads"]
    return (model["num_hidden_layers"], H, H, model["qk_nope_head_dim"] + model["qk_rope_head_dim"])


def _parts(model: dict) -> dict:
    d, H, F = model["hidden_size"], model["num_attention_heads"], model["moe_intermediate_size"]
    Rq, R = model["q_lora_rank"], model["kv_lora_rank"]
    nope, rope, vd = model["qk_nope_head_dim"], model["qk_rope_head_dim"], model["v_head_dim"]
    return {
        "attn": d * Rq + Rq * H * (nope + rope) + d * (R + rope) + R * H * (nope + vd) + H * vd * d,
        "dense_ffn": 3 * d * model["intermediate_size"],
        "shared": 3 * d * F * model["n_shared_experts"], "router": d * model["router_experts"],
        "expert": 3 * d * F,
        "norms": 4 * d + Rq + R,  # the sandwich's four, q_norm, kv_norm
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies; of its K chosen experts the share
    held here, K x held / scored of one expert's parameters a routed layer.
    `resident_matmul`: what lies on this chip (every held expert whole)."""
    p, d, V = _parts(model), model["hidden_size"], model["vocab_size"]
    n_dense = model["first_k_dense_replace"]
    n_routed = model["num_hidden_layers"] - n_dense
    held, scored, K = model["n_routed_experts"], model["router_experts"], model["num_experts_per_tok"]
    dense_layer = p["attn"] + p["dense_ffn"]
    routed_common = p["attn"] + p["shared"] + p["router"]
    head = 0 if model.get("tie_word_embeddings") else d * V
    return {
        "embedding": V * d, "lm_head": head,
        "per_layer_matmul": routed_common + K * held * p["expert"] // scored,  # a routed layer's
        "matmul": n_dense * dense_layer + n_routed * routed_common
        + n_routed * K * held * p["expert"] // scored + d * V,
        "resident_matmul": n_dense * dense_layer + n_routed * (routed_common + held * p["expert"]) + d * V,
        "total": V * d + head + n_dense * dense_layer + n_routed * (routed_common + held * p["expert"])
        + model["num_hidden_layers"] * p["norms"] + d,
    }


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a routed layer (cellspec.routing)."""
    return model["num_hidden_layers"] - model["first_k_dense_replace"]


GMM_CALLS_A_LAYER = 3  # gate, up and down, each one grouped matmul


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: the latent kernel once a layer
    (the one decode steps are counted from), the grouped matmul three times a
    routed layer."""
    return {"latent_attn": model["num_hidden_layers"], "expert_gmm": GMM_CALLS_A_LAYER * routing(model)}


def latent_decode_needs(model: dict, context_tokens: float, rows: float, dtype_bytes: int = 2) -> dict:
    """One layer's latent decode attention, summed over calls: `rows` (slot,
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
    rows, both as the program counts them. The kernel's grid is (live tiles,
    N blocks, K blocks): a tile streams its expert's three matrices once, so
    an expert whose pairs fill two tiles is read twice and one with no pair
    not at all. A pair multiplies the matrices once (2 operations a
    parameter), reads its row twice (gate, up), writes and reads its hidden
    state and writes its result."""
    d, F = model["hidden_size"], model["moe_intermediate_size"]
    return {"flops": 2.0 * 3 * d * F * pairs,
            "bytes": float(3 * d * F * dtype_bytes * tiles + pairs * (3 * d + 3 * F) * dtype_bytes)}
