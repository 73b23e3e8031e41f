"""granite-4.0-h-micro (ibm-granite, `granitemoehybrid` with no experts) as an
architecture of the benchmark, whole on ONE chip: periods of nine Mamba-2
layers around one softmax GQA layer without positions (`layer_types`,
`position_embedding_type` "nope"), a dense SwiGLU of `shared_intermediate_size`
in every layer (`num_local_experts` 0: no router, no experts), four scalar
multipliers and a tied head. benchmarks/README.md, "An architecture", says
what each function is for.

The reference: the benchmark's own copy of the published layer in float32
jax.numpy: no kernel, chunk, cache or batching, a Python loop over layers, the
whole score matrix masked, and the state-space recurrence as a SEQUENTIAL scan
over positions (`jax.lax.scan`, one position a turn: the program's chunked
form and its kernels share nothing with it). It reads the program's parameter
tree (`kind_layers` {"attention": the softmax layers, "mamba": the state-space
layers, stacked in order}; `embed` is the head too) a layer at a time, each
with ONE index into the stacked weight (`v[i]`), so that
`refcheck.read_coarsely` rounds slices.

    x = embedding_multiplier E[tokens]
    layer: x = x + residual_multiplier Mixer(N(x)); x = x + residual_multiplier
      (silu(h Wg) * (h Wu)) Wd, h = N'(x)
    attention mixer: q = h Wq [H, d]; k = h Wk, v = h Wv [KV, d]; no rope;
      a_h = softmax(attention_multiplier q_h k_g^T + causal mask) v_g; a Wo
    mamba mixer: [z | u | dt] = h W_in^T (W_in stored [outputs, D]);
      u = silu(conv(u) + b), causal and depthwise over time, T taps, zeros
      before position 0; [x | B | C] = u, x [Hm, P], B and C [G, N];
      dt = softplus(dt + dt_bias), a = exp(-exp(A_log) dt) a head;
      S_t = a_t S_(t-1) + dt_t x_t B_t^T, y_t = S_t C_t + D x_t, float32;
      y = N_g(y * silu(z)) over all Hm P columns (the gate first); y W_out
    logits = N_f(x) E^T / logits_scaling

Assumed, and listed in the configuration file: the state and the decays in
float32, no `time_step_limit`, the gated norm over one group of all columns,
the SwiGLU's two input matrices kept apart (the published layer fuses them:
the same function), the initial values of A_log, dt_bias, D, the taps and
their bias.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
ATTENTION, MAMBA = "attention", "mamba"


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


class _Layer:
    """Layer i of a stack of layers, read lazily: `layer("wq")` is that
    weight's slice for this layer, one index into the stacked array."""

    def __init__(self, stack: dict, i: int):
        self.stack, self.i = stack, i

    def __call__(self, name):
        return self.stack[name][self.i]


def _softmax_mixer(h, lp, allowed, scale):
    q = jnp.einsum("bsd,dhk->bshk", h, lp("wq").astype(F32))
    k = jnp.einsum("bsd,dhk->bshk", h, lp("wk").astype(F32))
    v = jnp.einsum("bsd,dhk->bshk", h, lp("wv").astype(F32))
    B, S, H, d = q.shape
    KV = k.shape[2]
    qg = q.reshape(B, S, KV, H // KV, d)  # head h reads KV head h // (H / KV)
    s = jnp.einsum("bqkgd,btkd->bkgqt", qg, k) * scale
    p = jax.nn.softmax(jnp.where(allowed[:, None, None], s, -jnp.inf), axis=-1)
    a = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, H, d)
    return jnp.einsum("bshk,hkd->bsd", a, lp("wo").astype(F32))


def _selective_scan(x, Bm, Cm, dt, a):
    """One position a turn from S = 0: x [B,S,H,P], Bm, Cm [B,S,G,N], dt, a
    [B,S,H] -> y [B,S,H,P]."""
    B, _, H, P = x.shape
    heads = lambda m: jnp.repeat(m, H // m.shape[2], axis=2)

    def position(s, at):
        x_t, b_t, c_t, dt_t, a_t = at
        s = a_t[..., None, None] * s + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :]
        return s, jnp.einsum("bhpn,bhn->bhp", s, c_t)

    over_time = tuple(jnp.moveaxis(m, 1, 0) for m in (x, heads(Bm), heads(Cm), dt, a))
    return jnp.moveaxis(jax.lax.scan(position, jnp.zeros((B, H, P, Bm.shape[-1]), F32), over_time)[1], 0, 1)


def _mamba_mixer(h, lp, model, eps):
    H, P, G, N = model["mamba_n_heads"], model["mamba_d_head"], model["mamba_n_groups"], model["mamba_d_state"]
    I, S = H * P, h.shape[1]
    zxd = h @ lp("w_in").astype(F32).T
    z, u, dt = zxd[..., :I], zxd[..., I:-H], zxd[..., -H:]
    taps = lp("conv").astype(F32)  # [T, channels], the oldest input's first
    T = taps.shape[0]
    padded = jnp.pad(u, ((0, 0), (T - 1, 0), (0, 0)))
    u = jax.nn.silu(sum(padded[:, j:j + S] * taps[j] for j in range(T)) + lp("conv_bias").astype(F32))
    x = u[..., :I].reshape(*u.shape[:2], H, P)
    Bm, Cm = (u[..., I + i * G * N:I + (i + 1) * G * N].reshape(*u.shape[:2], G, N) for i in range(2))
    dt = jax.nn.softplus(dt + lp("dt_bias").astype(F32))
    y = _selective_scan(x, Bm, Cm, dt, jnp.exp(-jnp.exp(lp("a_log").astype(F32)) * dt))
    y = y + lp("d_skip").astype(F32)[:, None] * x
    y = _norm(y.reshape(z.shape) * jax.nn.silu(z), lp("o_norm"), eps)
    return jnp.einsum("bshk,hkd->bsd", y.reshape(x.shape), lp("wo").astype(F32))


def _swiglu(x, lp):
    return (jax.nn.silu(x @ lp("w_gate").astype(F32)) * (x @ lp("w_up").astype(F32))) @ lp("w_down").astype(F32)


def logits(params, tokens, model: dict, segment_ids=None, positions=None):
    """tokens [B,S] -> logits [B,S,V], float32. No layer reads `positions`
    (no rope); a packed batch is refused, as the program refuses it."""
    if segment_ids is not None:
        raise SystemExit("benchmark: granite_hybrid's state-space layers are written for one document a row")
    eps, r, (B, S) = float(model["rms_norm_eps"]), float(model["residual_multiplier"]), tokens.shape
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    x = float(model["embedding_multiplier"]) * params["embed"][tokens].astype(F32)  # the rows read, not the table
    later = {}
    for kind in model["layer_types"]:
        lp = _Layer(params["kind_layers"][kind], later.get(kind, 0))
        later[kind] = lp.i + 1
        h = _norm(x, lp("attn_norm"), eps)
        x = x + r * (_softmax_mixer(h, lp, allowed, float(model["attention_multiplier"])) if kind == ATTENTION
                     else _mamba_mixer(h, lp, model, eps))
        x = x + r * _swiglu(_norm(x, lp("ffn_norm"), eps), lp)
    return _norm(x, params["final_norm"], eps) @ params["embed"].astype(F32).T / float(model["logits_scaling"])


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
    """The kinds of one period: the shortest prefix of `layer_types` that, repeated, gives them."""
    kinds = list(model["layer_types"])
    return next(kinds[:p] for p in range(1, len(kinds) + 1) if all(kinds[l] == kinds[l % p] for l in range(len(kinds))))


def _head_dim(model: dict) -> int:
    return model.get("head_dim") or model["hidden_size"] // model["num_attention_heads"]


def transformer_kwargs(model: dict) -> dict:
    """The published keys -> ray_tpu.models.TransformerConfig's. The two kinds
    of layer are LayerKinds "attention" and "mamba"."""
    if (model.get("position_embedding_type") != "nope" or model.get("num_local_experts") or model.get("attention_bias")
            or model.get("mamba_proj_bias") or not model.get("mamba_conv_bias") or not model.get("tie_word_embeddings")
            or model["mamba_expand"] * model["hidden_size"] != model["mamba_n_heads"] * model["mamba_d_head"]
            or model["shared_intermediate_size"] != model["intermediate_size"]
            or len(model["layer_types"]) != model["num_hidden_layers"]):
        raise SystemExit("benchmark: granite_hybrid is written for softmax layers without positions, no experts, no "
                         "bias but the convolution's, a tied head, mamba_expand x hidden_size columns of heads and "
                         "one dense FFN width")
    # Refused here, in the cell's driver and before a replica is started: a
    # program without the state-space kind (the parent of the PR that brought
    # this architecture) would fail in the replica's constructor instead.
    import dataclasses

    from ray_tpu.models import transformer  # imports jax, touches no backend

    kind = getattr(transformer, "LayerKind", None)
    missing = sorted(({"mixer", "conv_size", "head_width", "state_size", "n_groups"}
                      - {f.name for f in dataclasses.fields(kind)}) if kind else ["LayerKind"])
    missing += sorted({"embed_multiplier", "residual_multiplier", "attention_multiplier", "logits_divisor",
                       "tie_embeddings"} - {f.name for f in dataclasses.fields(transformer.TransformerConfig)})
    if missing:
        raise SystemExit(
            "benchmark: this program's TransformerConfig cannot hold a granite_hybrid configuration (selective "
            f"state-space layers whose state is kept by slot, four multipliers and a tied head): it has no {missing}")
    kinds = {
        ATTENTION: transformer.LayerKind(name=ATTENTION, n_heads=model["num_attention_heads"], rope_share=0.0),
        MAMBA: transformer.LayerKind(name=MAMBA, n_heads=model["mamba_n_heads"], mixer="ssd",
                                     conv_size=model["mamba_d_conv"], head_width=model["mamba_d_head"],
                                     state_size=model["mamba_d_state"], n_groups=model["mamba_n_groups"]),
    }
    kwargs = dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"], head_dim=_head_dim(model),
        d_ff=model["shared_intermediate_size"], max_seq_len=model["max_position_embeddings"],
        norm_eps=float(model["rms_norm_eps"]), attention_impl="auto",
        layer_pattern=tuple(kinds[k] for k in _period(model)),
        embed_multiplier=float(model["embedding_multiplier"]), residual_multiplier=float(model["residual_multiplier"]),
        attention_multiplier=float(model["attention_multiplier"]), logits_divisor=float(model["logits_scaling"]),
        tie_embeddings=True,
    )
    kwargs.update(model.get("transformer") or {})
    return kwargs


def shrink(model: dict) -> None:
    """Toy widths for --rehearse, in place: every width and count."""
    model.update(hidden_size=128, num_attention_heads=4, num_key_value_heads=2, intermediate_size=256,
                 shared_intermediate_size=256, vocab_size=512, max_position_embeddings=512,
                 mamba_n_heads=8, mamba_d_head=32, mamba_d_state=32)


def attention_dims(model: dict) -> tuple:
    """(layers, heads, KV heads, head_dim) of a softmax layer (harness/flops.py's
    attention-only counts read one kind of layer; of this architecture's
    `num_hidden_layers` only the "attention" ones of `layer_types` are such)."""
    return (model["num_hidden_layers"], model["num_attention_heads"], model["num_key_value_heads"], _head_dim(model))


def _parts(model: dict) -> dict:
    d, hd, H, KV = model["hidden_size"], _head_dim(model), model["num_attention_heads"], model["num_key_value_heads"]
    Hm, P, G, N, T = (model[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state", "mamba_d_conv"))
    inner, channels = Hm * P, Hm * P + 2 * G * N
    return {
        ATTENTION: 2 * d * H * hd + 2 * d * KV * hd,  # wq, wo; wk, wv
        MAMBA: d * (inner + channels + Hm) + inner * d,  # the input projection [z | x B C | dt]; the output's
        "mamba_small": T * channels + channels + 3 * Hm + inner,  # taps, their bias, dt_bias, A_log, D, the gated norm
        "ffn": 3 * d * model["shared_intermediate_size"], "norms": 2 * d,
    }


def param_counts(model: dict) -> dict:
    """`matmul`: what one token multiplies (the tied head once more: the
    embedding's rows are read, its transpose multiplied). Nothing is routed,
    so `resident_matmul` is the same. `per_layer_matmul`: a layer's, at the
    mean of the kinds' mixers."""
    p, d, V, L = _parts(model), model["hidden_size"], model["vocab_size"], model["num_hidden_layers"]
    kinds = list(model["layer_types"])
    mixers = sum(p[k] for k in kinds)
    matmul = mixers + L * p["ffn"] + d * V
    return {
        "embedding": V * d, "lm_head": 0,  # tied: one array
        "per_layer_matmul": mixers // L + p["ffn"],
        "matmul": matmul, "resident_matmul": matmul,
        "total": V * d + mixers + L * p["ffn"] + kinds.count(MAMBA) * p["mamba_small"] + L * p["norms"] + d,
    }


def decode_kernels(model: dict) -> dict:
    """The Mosaic calls of one decode step: the paged kernel once a softmax
    layer (`paged_attn`, the one decode steps are counted from), `ssd_step`
    once a state-space layer."""
    kinds = list(model["layer_types"])
    return {"paged_attn": kinds.count(ATTENTION), "ssd_step": kinds.count(MAMBA)}


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


def ssd_step_needs(model: dict, rows: float) -> dict:
    """One state-space layer's one-token rule, summed over calls: `rows`
    (slot, step) pairs. The work and not the implementation: a row's state,
    Hm x P x N float32, read and written once; its x and y [Hm, P], B and C
    [G, N], dt and the decay [Hm] in float32; a state's value is decayed (1
    operation), takes dt x B (2) and is read with C (2)."""
    Hm, P, G, N = (model[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state"))
    return {"flops": rows * 5.0 * Hm * P * N,
            "bytes": float(rows * 4 * (2 * Hm * P * N + 2 * Hm * P + 2 * G * N + 2 * Hm))}


def ssd_chunk_needs(model: dict, padded_tokens: float, chunk: int = 128, dtype_bytes: int = 2) -> dict:
    """One state-space layer's rule over `padded_tokens` positions of prompts
    in chunks of `chunk`: the chunked form's matrix products (2 operations a
    multiply-add), whatever an implementation adds to them. A chunk: the
    [chunk, chunk] table C B^T a group (chunk^2 x N), its product with x a
    head (chunk^2 x P), the state read with C and its update a head (2 x
    chunk x N x P). Bytes: x read and y written in the activations' dtype, B
    and C, dt and the decay in float32."""
    Hm, P, G, N = (model[k] for k in ("mamba_n_heads", "mamba_d_head", "mamba_n_groups", "mamba_d_state"))
    macs_a_token = G * chunk * N + Hm * (chunk * P + 2 * N * P)
    return {"flops": 2.0 * macs_a_token * padded_tokens,
            "bytes": float(padded_tokens * (2 * Hm * P * dtype_bytes + 2 * G * N * dtype_bytes + 2 * Hm * 4))}
