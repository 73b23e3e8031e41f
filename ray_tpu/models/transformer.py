"""Decoder-only transformer LM, written TPU-first.

Design choices for the MXU/HBM (see /opt/skills/guides/pallas_guide.md):
- bfloat16 activations, fp32 params/optimizer (casted per-matmul) so every
  matmul tiles onto the 128x128 MXU at full rate.
- Layers are *stacked* and iterated with ``lax.scan`` — one compiled layer
  body regardless of depth, static shapes throughout.
- Every weight and activation carries logical axes; the active
  ``ShardingStrategy`` (ray_tpu.parallel) decides the mesh mapping, so this
  one implementation serves DP, FSDP, Megatron-TP, sequence/context parallel
  and expert parallel without modification.
- Optional ``remat`` wraps the layer body in ``jax.checkpoint`` to trade
  FLOPs for HBM.

The reference has no model zoo of its own (it orchestrates torch/vLLM — see
SURVEY.md §2.4); this model is the framework's flagship train/serve workload,
playing the role MaxText plays for the reference's JaxTrainer
(/root/reference/python/ray/train/v2/jax/jax_trainer.py:19).
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.parallel.sharding import with_logical_constraint as wlc


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32_000
    d_model: int = 512
    n_layers: int = 8
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # GQA; None -> n_heads
    d_ff: int = 2048
    max_seq_len: int = 2048
    rope_theta: float = 10_000.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # MoE: n_experts=0 -> dense FFN; else top-k routed experts (expert axis).
    n_experts: int = 0
    expert_top_k: int = 2
    remat: bool = False
    # Rematerialization policy when remat=True: "full" recomputes the whole
    # layer in bwd; "dots" (jax dots_with_no_batch_dims_saveable) lets XLA
    # keep cheap-to-store dot results — measured +1pt MFU on v5e at the
    # flagship size (PROFILES.md round 4).
    remat_policy: str = "full"
    attention_impl: str = "auto"  # auto | flash | reference | ring | ulysses
    # Flash-kernel tile sizes (0 = ops/attention.py defaults). v5e at
    # S=2048/hd=64 measures fastest at 1024x1024 (PROFILES.md round 4).
    attention_block_q: int = 0
    attention_block_k: int = 0
    # Training-loss chunking: compute CE over sequence chunks of this size
    # so the full [B, S, V] logits never materialize (0 = off). Requires
    # chunk | (S-1 of the train batch); big win at large vocab (PROFILES.md).
    ce_chunk: int = 0
    norm_eps: float = 1e-6
    # Attention kind. "gqa": a head's K and V projected from the hidden state
    # and cached. "latent": low-rank query and key/value projections with
    # their inner norms; a head's query and key are qk_nope_head_dim wide plus
    # a roped part of qk_rope_head_dim whose key all heads share, its value
    # v_head_dim wide; what a token caches is the kv_lora_rank latent and that
    # roped key, one row for all heads.
    attention_kind: str = "gqa"
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # A norm after each sublayer as well as before it:
    # h = x + N2(Attn(N1(x))), y = h + N4(FFN(N3(h))).
    sandwich_norm: bool = False
    # Leading layers whose FFN is dense (width d_ff) before the routed ones:
    # params["dense_layers"], scanned before params["layers"].
    n_dense_layers: int = 0
    # A routed layer as one chip of an expert-parallel deployment serves it:
    # the router is n_experts wide and every token takes its expert_top_k
    # best, the chip holds experts first_expert .. first_expert +
    # experts_held - 1 (each a SwiGLU of width expert_d_ff) and computes the
    # part of the result those give, beside n_shared_experts every token
    # passes. experts_held = 0: the training form (_moe_ffn), every expert here.
    experts_held: int = 0
    first_expert: int = 0
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    router_score: str = "softmax"  # softmax | sigmoid, over the router's logits in float32

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def head_dim(self) -> int:
        return self.d_model // self.n_heads

    @property
    def latent(self) -> bool:
        return self.attention_kind == "latent"

    def __post_init__(self):
        assert self.d_model % self.n_heads == 0
        assert self.n_heads % self.kv_heads == 0
        assert self.attention_kind in ("gqa", "latent"), self.attention_kind
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.router_score in ("softmax", "sigmoid"), self.router_score
        if self.experts_held:
            assert self.first_expert + self.experts_held <= self.n_experts
            assert self.expert_d_ff > 0


# ---------------------------------------------------------------------------
# Parameter init + logical axes
# ---------------------------------------------------------------------------

def _dense_init(key, shape, dtype, in_axis=0):
    """in_axis: int or tuple of axes whose product is the contraction fan-in."""
    axes = (in_axis,) if isinstance(in_axis, int) else tuple(in_axis)
    fan_in = 1
    for a in axes:
        fan_in *= shape[a]
    scale = 1.0 / (fan_in ** 0.5)
    return jax.random.normal(key, shape, dtype) * scale


def _init_stack(key: jax.Array, cfg: TransformerConfig, L: int, routed: bool) -> tuple:
    """One stack of L identical layers (leading 'layers' dim on every leaf);
    `routed`: the FFN is experts behind a router, else dense of width d_ff.
    Returns (the stack, the iterator over the keys it left)."""
    pd = cfg.param_dtype
    k = iter(jax.random.split(key, 16))
    D, F, H = cfg.d_model, cfg.d_ff, cfg.n_heads
    if cfg.latent:
        R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        layer = {
            "attn_norm": jnp.ones((L, D), pd),
            "wq_a": _dense_init(next(k), (L, D, Rq), pd, in_axis=1),
            "q_norm": jnp.ones((L, Rq), pd),
            "wq_b": _dense_init(next(k), (L, Rq, H, nope + rope), pd, in_axis=1),
            "wkv_a": _dense_init(next(k), (L, D, R + rope), pd, in_axis=1),
            "kv_norm": jnp.ones((L, R), pd),
            "wk_b": _dense_init(next(k), (L, R, H, nope), pd, in_axis=1),
            "wv_b": _dense_init(next(k), (L, R, H, vd), pd, in_axis=1),
            "wo": _dense_init(next(k), (L, H, vd, D), pd, in_axis=(1, 2)),
            "ffn_norm": jnp.ones((L, D), pd),
        }
    else:
        KV, Hd = cfg.kv_heads, cfg.head_dim
        layer = {
            "attn_norm": jnp.ones((L, D), pd),
            "wq": _dense_init(next(k), (L, D, H, Hd), pd, in_axis=1),
            "wk": _dense_init(next(k), (L, D, KV, Hd), pd, in_axis=1),
            "wv": _dense_init(next(k), (L, D, KV, Hd), pd, in_axis=1),
            "wo": _dense_init(next(k), (L, H, Hd, D), pd, in_axis=(1, 2)),
            "ffn_norm": jnp.ones((L, D), pd),
        }
    if cfg.sandwich_norm:
        layer.update({"post_attn_norm": jnp.ones((L, D), pd), "post_ffn_norm": jnp.ones((L, D), pd)})
    if routed:
        E = cfg.experts_held or cfg.n_experts
        EF = cfg.expert_d_ff or F
        layer.update(
            {
                "router": _dense_init(next(k), (L, D, cfg.n_experts), pd, in_axis=1),
                "w_gate": _dense_init(next(k), (L, E, D, EF), pd, in_axis=2),
                "w_up": _dense_init(next(k), (L, E, D, EF), pd, in_axis=2),
                "w_down": _dense_init(next(k), (L, E, EF, D), pd, in_axis=2),
            }
        )
        if cfg.n_shared_experts:
            SF = cfg.n_shared_experts * EF
            layer.update(
                {
                    "ws_gate": _dense_init(next(k), (L, D, SF), pd, in_axis=1),
                    "ws_up": _dense_init(next(k), (L, D, SF), pd, in_axis=1),
                    "ws_down": _dense_init(next(k), (L, SF, D), pd, in_axis=1),
                }
            )
    else:
        layer.update(
            {
                "w_gate": _dense_init(next(k), (L, D, F), pd, in_axis=1),
                "w_up": _dense_init(next(k), (L, D, F), pd, in_axis=1),
                "w_down": _dense_init(next(k), (L, F, D), pd, in_axis=1),
            }
        )
    return layer, k


def init_params(key: jax.Array, cfg: TransformerConfig) -> dict:
    """Stacked-layer parameter pytree: ``layers`` (leading 'layers' dim on
    every leaf) and, for a model with leading dense layers, ``dense_layers``
    before it; a model with none has no such key (an empty first stack)."""
    pd = cfg.param_dtype
    D = cfg.d_model
    layer, k = _init_stack(key, cfg, cfg.n_layers - cfg.n_dense_layers, routed=bool(cfg.n_experts))
    params = {
        "embed": _dense_init(next(k), (cfg.vocab_size, D), pd) * (D ** 0.5),
        "layers": layer,
        "final_norm": jnp.ones((D,), pd),
        "lm_head": _dense_init(next(k), (D, cfg.vocab_size), pd, in_axis=0),
    }
    if cfg.n_dense_layers:
        params["dense_layers"], _ = _init_stack(
            jax.random.fold_in(key, 1), cfg, cfg.n_dense_layers, routed=False)
    return params


def layer_stacks(params: dict) -> list:
    """The model's stacks of identical layers, in the order a token passes
    them: the leading dense layers where it has any, then ``layers``."""
    return [params[name] for name in ("dense_layers", "layers") if name in params]


HELD_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def scan_stack(body, carry, stack: dict, cfg: TransformerConfig, *xs):
    """``lax.scan`` of ``body(carry, lp, *xs_i)`` over a stack of layers, lp
    one layer's parameters. The matrices of held experts stay out of the
    scanned operands: lp holds them whole, [L, E, ...], with the layer's
    index in the stack under "expert_layer", and the grouped matmul is told
    the layer by an operand. A layer's slice of them handed to a Mosaic call
    is a copy of it (1.5 GB a layer at the published widths of the serve
    cell's model, as compiled for a v5e), as a layer's slice of a KV pool was
    (llm/engine.py, the rule where the pools are made)."""
    if not (cfg.experts_held and "router" in stack):
        return lax.scan(lambda c, s: body(c, *s), carry, (stack, *xs))
    whole = {k: stack[k] for k in HELD_EXPERT_WEIGHTS}
    scanned = {k: v for k, v in stack.items() if k not in whole}
    index = jnp.arange(stack["router"].shape[0], dtype=jnp.int32)
    return lax.scan(lambda c, s: body(c, {**s[0], **whole, "expert_layer": s[1]}, *s[2:]),
                    carry, (scanned, index, *xs))


def _stack_logical_axes(cfg: TransformerConfig, routed: bool) -> dict:
    if cfg.latent:
        layer = {
            "attn_norm": ("layers", "embed"),
            "wq_a": ("layers", "embed", None),
            "q_norm": ("layers", None),
            "wq_b": ("layers", None, "heads", "head_dim"),
            "wkv_a": ("layers", "embed", None),
            "kv_norm": ("layers", None),
            "wk_b": ("layers", None, "heads", "head_dim"),
            "wv_b": ("layers", None, "heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "ffn_norm": ("layers", "embed"),
        }
    else:
        layer = {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "ffn_norm": ("layers", "embed"),
        }
    if cfg.sandwich_norm:
        layer.update({"post_attn_norm": ("layers", "embed"), "post_ffn_norm": ("layers", "embed")})
    if routed:
        layer.update(
            {
                "router": ("layers", "embed", None),
                "w_gate": ("layers", "experts", "embed", "expert_mlp"),
                "w_up": ("layers", "experts", "embed", "expert_mlp"),
                "w_down": ("layers", "experts", "expert_mlp", "embed"),
            }
        )
        if cfg.n_shared_experts:
            layer.update(
                {
                    "ws_gate": ("layers", "embed", "mlp"),
                    "ws_up": ("layers", "embed", "mlp"),
                    "ws_down": ("layers", "mlp", "embed"),
                }
            )
    else:
        layer.update(
            {
                "w_gate": ("layers", "embed", "mlp"),
                "w_up": ("layers", "embed", "mlp"),
                "w_down": ("layers", "mlp", "embed"),
            }
        )
    return layer


def param_logical_axes(cfg: TransformerConfig) -> dict:
    """Same-structure pytree of logical-axis tuples (see LOGICAL_AXES)."""
    axes = {
        "embed": ("vocab", "embed"),
        "layers": _stack_logical_axes(cfg, routed=bool(cfg.n_experts)),
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }
    if cfg.n_dense_layers:
        axes["dense_layers"] = _stack_logical_axes(cfg, routed=False)
    return axes


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def _rope(x, positions, theta):
    """x: [B, S, H, Hd]; rotate pairs (even, odd) halves."""
    half = x.shape[-1] // 2
    freqs = jnp.exp(
        -jnp.log(theta) * jnp.arange(0, half, dtype=jnp.float32) / half
    )
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = jnp.cos(angles)[:, :, None, :].astype(x.dtype)
    sin = jnp.sin(angles)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _flash(q, k, v, cfg: TransformerConfig, segment_ids, scale=None):
    """The flash kernel on this device's shard. Under a multi-device mesh the
    call is shard_map'd over the batch and head axes the active strategy
    shards: GSPMD cannot partition a Mosaic kernel, and jax refuses to lower
    a bare pallas_call there ("Mosaic kernels cannot be automatically
    partitioned"). The sequence stays whole per shard (ring/ulysses are the
    sequence-parallel impls)."""
    from ray_tpu.ops.attention import DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q, flash_attention
    from ray_tpu.parallel.sharding import _ambient_mesh, _current_strategy

    def local(q, k, v, seg):
        return flash_attention(
            q, k, v, causal=True, segment_ids=seg, scale=scale,
            block_q=cfg.attention_block_q or DEFAULT_BLOCK_Q,
            block_k=cfg.attention_block_k or DEFAULT_BLOCK_K,
        )

    mesh, strategy = _ambient_mesh(), _current_strategy()
    if mesh is None or strategy is None or mesh.size == 1:
        return local(q, k, v, segment_ids)
    q_spec = strategy.spec(("batch", None, "heads", None))
    kv_spec = strategy.spec(("batch", None, "kv_heads", None))
    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: local(q, k, v, None), mesh=mesh,
            in_specs=(q_spec, kv_spec, kv_spec), out_specs=q_spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        local, mesh=mesh,
        in_specs=(q_spec, kv_spec, kv_spec, strategy.spec(("batch", None))),
        out_specs=q_spec, check_vma=False,
    )(q, k, v, segment_ids)


def _attention(q, k, v, cfg: TransformerConfig, positions=None, segment_ids=None, scale=None):
    """Dispatch to the configured attention implementation.

    q: [B,S,H,D]; k,v: [B,S,KV,D] — flash and reference handle grouped KV
    natively (no repeat: the KV HBM-footprint saving is the point of GQA);
    ring still expects full heads, so its K/V are expanded at the call site.
    """
    impl = cfg.attention_impl
    if impl == "auto":
        from ray_tpu.ops.attention import flash_supported

        impl = "flash" if flash_supported(q.shape[1]) else "reference"
    if impl == "flash":
        if scale is None:
            return _flash(q, k, v, cfg, segment_ids)
        # A latent layer: keys wider than values; the kernel takes one width.
        return _flash(*lane_padded(q, k, v), cfg, segment_ids, scale)[..., :v.shape[-1]]
    if scale is not None and impl != "reference":
        raise NotImplementedError(f"attention_impl={impl!r} is not written for latent attention")
    if impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        if segment_ids is not None:
            raise NotImplementedError(
                "ring attention does not support segment_ids yet; use "
                "attention_impl='flash' (or 'reference') for packed sequences"
            )
        if k.shape[2] != q.shape[2]:
            rep = q.shape[2] // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        return ring_attention(q, k, v, axis_name="seq", causal=True)
    if impl == "ulysses":
        from ray_tpu.ops.ulysses import ulysses_attention

        return ulysses_attention(
            q, k, v, axis_name="seq", causal=True, segment_ids=segment_ids
        )
    from ray_tpu.ops.attention import mha_reference

    return mha_reference(q, k, v, causal=True, segment_ids=segment_ids, scale=scale)


def _dense_ffn(x, p):
    gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(gate) * up
    h = wlc(h, ("batch", "seq", "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"].astype(x.dtype))


def _moe_ffn(x, p, cfg: TransformerConfig):
    """Top-k routed MoE. Experts carry the 'experts' logical axis; under the
    EP strategy the einsum over the expert dim induces an all_to_all.

    Dense-dispatch formulation (every token weighted to every expert with a
    sparse weight matrix) — compiler-friendly: static shapes, no gather along
    the token axis, and XLA shards the expert dim cleanly.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.expert_top_k
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(x.dtype))
    weights = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    top_w, top_idx = lax.top_k(weights, K)  # [B,S,K]
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)
    # combine [B,S,E] sparse routing matrix
    route = jnp.sum(
        jax.nn.one_hot(top_idx, E, dtype=jnp.float32) * top_w[..., None], axis=2
    )
    route = route.astype(x.dtype)
    # expert compute: xe [E, B, S, D] weighted inputs would be huge; instead
    # compute all experts on all tokens is O(E*tokens) — fine for small E on
    # bench; for large E the EP strategy shards the E dim across chips.
    gate = jnp.einsum("bsd,edf->ebsf", x, p["w_gate"].astype(x.dtype))
    up = jnp.einsum("bsd,edf->ebsf", x, p["w_up"].astype(x.dtype))
    h = jax.nn.silu(gate) * up
    h = wlc(h, ("experts", "batch", "seq", "expert_mlp"))
    out = jnp.einsum("ebsf,efd->ebsd", h, p["w_down"].astype(x.dtype))
    out = jnp.einsum("ebsd,bse->bsd", out, route)
    aux = _load_balance_loss(weights, top_idx, E)
    return out, aux


def _load_balance_loss(weights, top_idx, n_experts):
    """Switch-transformer aux loss: mean_prob * mean_assignment per expert."""
    me = jnp.mean(weights, axis=(0, 1))  # [E]
    ce = jnp.mean(
        jax.nn.one_hot(top_idx[..., 0], n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    return n_experts * jnp.sum(me * ce)


def latent_scale(cfg: TransformerConfig) -> float:
    """Softmax scale of a latent layer: over the whole query/key width."""
    return 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


def latent_expand(lp, c, k_rope, dt):
    """A latent layer's keys and values from what it caches. c: [B, S, R];
    k_rope: [B, S, rope] -> k [B, S, H, nope + rope], v [B, S, H, v]: the
    path over a prompt (decode absorbs the two projections instead)."""
    k_nope = jnp.einsum("bsr,rhk->bshk", c, lp["wk_b"].astype(dt))
    v = jnp.einsum("bsr,rhk->bshk", c, lp["wv_b"].astype(dt))
    k_rope = jnp.broadcast_to(k_rope[:, :, None, :], (*k_nope.shape[:3], k_rope.shape[-1]))
    return jnp.concatenate([k_nope, k_rope], axis=-1), v


def latent_absorb(lp, q_nope, dt):
    """Decode's query with the key up-projection absorbed: q_nope [B, H, nope]
    -> [B, H, R], to be scored against the cached latents as they lie."""
    return jnp.einsum("bhk,rhk->bhr", q_nope, lp["wk_b"].astype(dt))


def latent_values(lp, ctx, dt):
    """The value up-projection applied after attention: ctx [B, H, R], a
    head's weighted sum of cached latents -> its output [B, H, v]."""
    return jnp.einsum("bhr,rhk->bhk", ctx, lp["wv_b"].astype(dt))


def pad_last(a, width: int):
    """a with its last axis zero-padded up to `width`."""
    return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, width - a.shape[-1]),))


def lane_padded(q, k, v):
    """q, k (one width) and v (another) zero-padded to one lane multiple,
    for a flash kernel that takes one head width: zero columns change neither
    a score nor, once the output is cut back to v's width, a value."""
    wide = -(-max(q.shape[-1], v.shape[-1]) // 128) * 128
    return pad_last(q, wide), pad_last(k, wide), pad_last(v, wide)


def _latent_qkv(h, lp, cfg: TransformerConfig, positions):
    """-> (q_nope [B,S,H,nope], q_rope [B,S,H,rope]) roped, c [B,S,R] normed,
    k_rope [B,S,rope] roped."""
    dt, eps, R = h.dtype, cfg.norm_eps, cfg.kv_lora_rank
    with jax.named_scope("mla_q"):
        cq = _rms_norm(jnp.einsum("bsd,dr->bsr", h, lp["wq_a"].astype(dt)), lp["q_norm"], eps)
        q = jnp.einsum("bsr,rhk->bshk", cq, lp["wq_b"].astype(dt))
        q = wlc(q, ("batch", "seq", "heads", "head_dim"))
        q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        q_rope = _rope(q_rope, positions, cfg.rope_theta)
    with jax.named_scope("mla_kv"):
        ckr = jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"].astype(dt))
        c = _rms_norm(ckr[..., :R], lp["kv_norm"], eps)
        k_rope = _rope(ckr[:, :, None, R:], positions, cfg.rope_theta)[:, :, 0]
    return (q_nope, q_rope), c, k_rope


def _expert_tile(tokens: int, cfg: TransformerConfig) -> int:
    """Rows of a grouped-matmul tile: twice the pairs an expert expects, as a
    power of two in 16 .. 256, so that most experts fill one tile and an
    expert's weights are read once."""
    expect = 2 * tokens * cfg.expert_top_k // cfg.n_experts
    return min(256, max(16, 1 << max(expect - 1, 0).bit_length()))


def _held_experts_ffn(x, p, cfg: TransformerConfig):
    """A routed FFN as the chip that holds experts first_expert ..
    first_expert + experts_held - 1 serves it. Every token is scored over all
    n_experts (router logits in float32) and takes its expert_top_k best,
    weights normalised over all of them and scaled; the pairs that landed on
    experts held here are sorted by expert and multiplied by a grouped matmul
    (ops/grouped_matmul.py), none dropped whatever the imbalance; what the
    absent experts would have added is left out. Beside it the shared expert,
    which every token passes.
    Returns (out [B,S,D], int32 [2]: the pairs on held experts, and the live
    tiles of the grouped matmul, each of which reads its expert's matrices)."""
    from ray_tpu.ops.grouped_matmul import expert_matmul, group_rows

    B, S, D = x.shape
    dt = x.dtype
    xt = x.reshape(B * S, D)
    with jax.named_scope("experts/route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        score = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        top_s, top_e = lax.top_k(score, cfg.expert_top_k)  # [T, K]
        top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * cfg.routed_scaling
        tm = _expert_tile(B * S, cfg)
        plan = group_rows(top_e, cfg.first_expert, cfg.experts_held, tm)
    with jax.named_scope("experts/gmm"):
        # One layer's matrices [E, ...] (layer 0 of a stack of one: a free
        # reshape), or the whole stack with this layer's index (scan_stack).
        stacked = p["w_gate"].ndim == 4
        gmm = functools.partial(
            expert_matmul(),
            layer=p["expert_layer"] if stacked else 0,
            tile_expert=plan.tile_expert, n_tiles=plan.n_tiles, tm=tm)
        w_gate, w_up, w_down = (p[k] if stacked else p[k][None] for k in HELD_EXPERT_WEIGHTS)
        xs = xt[plan.token_of_row]  # [M, D]
        h = jax.nn.silu(gmm(xs, w_gate)) * gmm(xs, w_up)
        y = gmm(h, w_down)  # [M, D]; rows past the live tiles hold nothing
        pair = y[plan.row_of_pair].astype(jnp.float32) * top_w[..., None]  # [T, K, D]
        routed = jnp.sum(jnp.where(plan.held[..., None], pair, 0.0), axis=1)
        routed = routed.astype(dt).reshape(B, S, D)
    with jax.named_scope("experts/shared"):
        if cfg.n_shared_experts:
            shared = {"w_gate": p["ws_gate"], "w_up": p["ws_up"], "w_down": p["ws_down"]}
            routed = routed + _dense_ffn(x, shared)
    return routed, jnp.stack([jnp.sum(plan.sizes), plan.n_tiles[0]]).astype(jnp.int32)


def decoder_block(x, lp, cfg: TransformerConfig, positions, attend):
    """The one decoder block that training, prefill and decode all run: what
    the model is (norms, projections, rope, the attention's and the FFN's
    kind) lives here, what a program does with what a layer caches is its
    ``attend``.

    x: [B, S, D] in cfg.dtype; lp: one layer's parameters (its FFN is routed
    if they hold a router, dense otherwise); positions: [B, S].
    attend(q, k, v) -> (o [B,S,H,v width], kept). "gqa": q [B,S,H,Hd],
    k and v [B,S,KV,Hd], q and k roped, grouped K/V as they are (native GQA).
    "latent": q = (q_nope [B,S,H,nope], q_rope [B,S,H,rope]), k = the normed
    latent c [B,S,R], v = the roped shared key k_rope [B,S,rope], which is
    what such a layer caches; the attention side expands them over a prompt
    (``latent_expand``) or absorbs the projections in decode. ``kept`` is
    whatever the attention side wants handed out of the layer (a prompt's
    rows, the carried pools, None). Returns (x, aux, kept): aux is the MoE
    balance term of a training layer, a zero for a dense one, and the
    [pairs, live tiles] counts of a layer that serves held experts."""
    eps = cfg.norm_eps
    dt = x.dtype
    if cfg.latent:
        q, k, v = _latent_qkv(_rms_norm(x, lp["attn_norm"], eps), lp, cfg, positions)
    else:
        with jax.named_scope("qkv"):
            h = _rms_norm(x, lp["attn_norm"], eps)
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(dt))
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(dt))
            q = wlc(q, ("batch", "seq", "heads", "head_dim"))
            k = wlc(k, ("batch", "seq", "kv_heads", "head_dim"))
            q = _rope(q, positions, cfg.rope_theta)
            k = _rope(k, positions, cfg.rope_theta)
    o, kept = attend(q, k, v)
    with jax.named_scope("attn_out"):
        o = wlc(o, ("batch", "seq", "heads", "head_dim"))
        a = jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(dt))
        if cfg.sandwich_norm:
            a = _rms_norm(a, lp["post_attn_norm"], eps)
        x = x + a
    with jax.named_scope("ffn"):
        h = _rms_norm(x, lp["ffn_norm"], eps)
        if "router" not in lp:
            ffn_out, aux = _dense_ffn(h, lp), jnp.zeros((), jnp.float32)
        elif cfg.experts_held:
            ffn_out, aux = _held_experts_ffn(h, lp, cfg)
        else:
            ffn_out, aux = _moe_ffn(h, lp, cfg)
        if cfg.sandwich_norm:
            ffn_out = _rms_norm(ffn_out, lp["post_ffn_norm"], eps)
        x = x + ffn_out
    x = wlc(x, ("batch", "seq", "embed"))
    return x, aux, kept


def _layer(x, lp, cfg: TransformerConfig, positions, segment_ids=None):
    """The block as training runs it: attention over the layer's own K/V by
    the configured implementation, nothing kept. x: [B, S, D] in cfg.dtype."""
    def attend(q, k, v):
        if not cfg.latent:
            return _attention(q, k, v, cfg, positions, segment_ids), None
        k, v = latent_expand(lp, k, v, x.dtype)
        q = jnp.concatenate(q, axis=-1)
        return _attention(q, k, v, cfg, positions, segment_ids, scale=latent_scale(cfg)), None

    x, aux, _ = decoder_block(x, lp, cfg, positions, attend)
    # A layer that serves held experts hands out counts, not a loss term.
    return x, (jnp.zeros((), jnp.float32) if cfg.experts_held else aux)


def forward_hidden(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                   segment_ids=None, positions=None):
    """tokens [B, S] int32 -> (final-norm hidden states [B, S, D], moe_aux).
    The shared trunk of forward() and the chunked-CE training loss."""
    B, S = tokens.shape
    x = params["embed"].astype(cfg.dtype)[tokens]
    x = wlc(x, ("batch", "seq", "embed"))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    body = functools.partial(_layer, cfg=cfg, positions=positions, segment_ids=segment_ids)
    if cfg.remat:
        if cfg.remat_policy == "dots":
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable
            )
        elif cfg.remat_policy == "full":
            body = jax.checkpoint(body)
        else:
            raise ValueError(
                f"unknown remat_policy {cfg.remat_policy!r} (full|dots)"
            )

    aux = None
    for stack in layer_stacks(params):
        x, auxes = scan_stack(body, x, stack, cfg)
        aux = jnp.sum(auxes) if aux is None else aux + jnp.sum(auxes)
    return _rms_norm(x, params["final_norm"], cfg.norm_eps), aux


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            segment_ids=None, positions=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab].

    Packed sequences: pass ``segment_ids`` [B, S] (attention masked within
    segments) and per-segment-restarting ``positions`` [B, S] for RoPE.
    """
    x, aux = forward_hidden(params, tokens, cfg, segment_ids, positions)
    logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"].astype(cfg.dtype))
    logits = wlc(logits, ("batch", "seq", "vocab"))
    # Keep logits in activation dtype: at vocab=32k the fp32 copy alone is
    # O(GBs) of HBM; the loss upcasts per-reduction instead.
    return logits, aux


def _ce_from_logits(logits, targets, mask=None):
    """logsumexp-form CE: avoids materializing a full [B,S,V] log_softmax."""
    lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = lse - picked.astype(jnp.float32)
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def _ce_chunked(x, lm_head, targets, mask, chunk: int):
    """Fused-style CE: the [B, S, V] logits are never materialized — a
    rematted scan computes each sequence chunk's logits [B, c, V], reduces
    to (sum nll, count), and the bwd recomputes them per chunk. At vocab
    32k / B16 / S2048 this removes a 2+ GB bf16 logits tensor (plus its bwd
    twin) from HBM, which is what lets batch 24 fit on one v5e and shaves
    the fwd/bwd logits traffic (PROFILES.md round 4)."""
    B, S, D = x.shape
    n = S // chunk
    if mask is None:
        mask = jnp.ones((B, S), jnp.float32)
    mask = mask.astype(jnp.float32)

    @jax.checkpoint
    def body(xc, tc, mc):
        logits = jnp.einsum("bcd,dv->bcv", xc, lm_head)
        lse = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1)
        picked = jnp.take_along_axis(logits, tc[..., None], axis=-1)[..., 0]
        nll = lse - picked.astype(jnp.float32)
        return jnp.sum(nll * mc), jnp.sum(mc)

    # Unrolled chunk loop (n is small): a lax.scan here measured 6x SLOWER
    # on v5e (the scanned body pessimizes the [D, V] matmul layout). The
    # optimization_barrier chains each chunk's input on the previous chunk's
    # sum — without it XLA overlaps all n matmul islands and every chunk's
    # logits are live at once (OOM, the exact thing chunking exists to fix).
    tot = jnp.float32(0.0)
    cnt = jnp.float32(0.0)
    for i in range(n):
        sl = slice(i * chunk, (i + 1) * chunk)
        x_i = x[:, sl]
        if i:
            x_i, tot = lax.optimization_barrier((x_i, tot))
        s_i, c_i = body(x_i, targets[:, sl], mask[:, sl])
        tot += s_i
        cnt += c_i
    return tot / jnp.maximum(cnt, 1.0)


def cross_entropy_loss(params, batch, cfg: TransformerConfig):
    """batch: {"tokens": [B, S+1] int32, optional "mask"/"segment_ids"/
    "positions"} -> scalar mean NLL (+ MoE aux). segment_ids enable packed-
    sequence training (attention + loss respect example boundaries)."""
    tokens = batch["tokens"]
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    segs = batch.get("segment_ids")
    pos = batch.get("positions")
    mask = None if batch.get("mask") is None else batch["mask"][:, 1:].astype(jnp.float32)
    if segs is not None:
        # Don't train the position that predicts across a segment boundary;
        # composes with any provided padding mask.
        boundary = (segs[:, 1:] == segs[:, :-1]).astype(jnp.float32)
        mask = boundary if mask is None else mask * boundary
    if cfg.ce_chunk and inputs.shape[1] % cfg.ce_chunk:
        import warnings

        warnings.warn(
            f"ce_chunk={cfg.ce_chunk} does not divide the train seq length "
            f"{inputs.shape[1]}; falling back to MATERIALIZED logits "
            f"([B,S,V] in HBM) — a run sized around chunked CE may OOM here",
            stacklevel=2,
        )
    if cfg.ce_chunk and inputs.shape[1] % cfg.ce_chunk == 0:
        x, aux = forward_hidden(
            params, inputs, cfg,
            segment_ids=None if segs is None else segs[:, :-1],
            positions=None if pos is None else pos[:, :-1],
        )
        loss = _ce_chunked(
            x, params["lm_head"].astype(cfg.dtype), targets, mask, cfg.ce_chunk
        )
    else:
        logits, aux = forward(
            params, inputs, cfg,
            segment_ids=None if segs is None else segs[:, :-1],
            positions=None if pos is None else pos[:, :-1],
        )
        loss = _ce_from_logits(logits, targets, mask)
    return loss + 0.01 * aux


# ---------------------------------------------------------------------------
# Train step factory
# ---------------------------------------------------------------------------

def make_train_step(cfg: TransformerConfig, optimizer=None):
    """Returns (init_state, train_step, state_logical_axes).

    train_step(state, batch) -> (state, metrics); pure + jittable, composes
    with any mesh/strategy via ray_tpu.parallel.shard_pytree on the state.
    """
    import optax

    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)

    def init_state(key):
        params = init_params(key, cfg)
        return {"params": params, "opt": optimizer.init(params), "step": jnp.zeros((), jnp.int32)}

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(cross_entropy_loss)(
            state["params"], batch, cfg
        )
        updates, opt = optimizer.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": loss, "grad_norm": gnorm, "step": state["step"] + 1},
        )

    def state_logical_axes(state):
        p_axes = param_logical_axes(cfg)
        return {
            "params": p_axes,
            "opt": _opt_axes_like(state["opt"], p_axes),
            "step": (),
        }

    return init_state, train_step, state_logical_axes


def make_pipeline_train_step(cfg: TransformerConfig, mesh, n_micro: int, optimizer=None, axis_name: str = "stage"):
    """Pipeline-parallel training step (the pp() strategy's executor).

    Returns (init_state, train_step, state_logical_axes) like make_train_step,
    but the layer stack runs as a GPipe microbatch schedule over the mesh's
    ``stage`` axis (ray_tpu.parallel.pipeline). Differentiating through the
    schedule fuses gradient accumulation across the n_micro microbatches into
    the same XLA program — loss and gradients are EXACTLY those of the
    sequential step on the full batch (tested vs make_train_step).

    The reference delegates PP to vLLM (SURVEY §2.4,
    llm/_internal/serve/engines/vllm/vllm_models.py:233); this is the native
    TPU design instead: stage-sharded scanned layers + ppermute ring, no
    runtime-brokered activations. Embedding/final-norm/lm_head compute
    replicated on every stage (cheap relative to the stack); batch dims may
    additionally shard over data axes present in the mesh. The MoE aux-loss
    term is not threaded through the schedule — use dense stacks with pp (or
    ep over a separate axis).
    """
    import optax

    from ray_tpu.parallel.pipeline import pipeline_apply

    if cfg.n_experts:
        raise ValueError(
            "make_pipeline_train_step does not thread the MoE aux loss through "
            "the pipeline schedule; use a dense stack with pp (or make_train_step "
            "with ep over a separate mesh axis)"
        )
    if cfg.n_dense_layers:
        raise ValueError("make_pipeline_train_step stages one stack of identical layers (n_dense_layers = 0)")
    optimizer = optimizer or optax.adamw(3e-4, weight_decay=0.01)
    base_init, _base_step, state_logical_axes = make_train_step(cfg, optimizer)

    from jax.sharding import PartitionSpec as P

    data_axes = tuple(a for a in ("replica", "data", "fsdp") if a in mesh.shape)
    x_spec = P(None, data_axes if data_axes else None)

    def pipelined_loss(params, batch):
        if batch.get("segment_ids") is not None or batch.get("positions") is not None:
            raise NotImplementedError(
                "packed sequences (segment_ids/positions) are not threaded "
                "through the pipeline schedule yet; use make_train_step"
            )
        tokens = batch["tokens"]
        inputs, targets = tokens[:, :-1], tokens[:, 1:]
        B, S = inputs.shape
        if B % n_micro:
            raise ValueError(f"batch {B} not divisible by n_micro={n_micro}")
        x = params["embed"].astype(cfg.dtype)[inputs]
        mb = B // n_micro
        xm = x.reshape(n_micro, mb, S, x.shape[-1])

        def stage_fn(lp, h):
            pos = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), h.shape[:2])
            y, _aux = _layer(h, lp, cfg, pos)
            return y

        if cfg.remat:
            stage_fn = jax.checkpoint(stage_fn)
        h = pipeline_apply(
            stage_fn, params["layers"], xm, mesh=mesh, axis_name=axis_name, x_spec=x_spec
        )
        h = h.reshape(B, S, -1)
        h = _rms_norm(h, params["final_norm"], cfg.norm_eps)
        logits = jnp.einsum("bsd,dv->bsv", h, params["lm_head"].astype(cfg.dtype))
        mask = batch.get("mask")
        return _ce_from_logits(logits, targets, None if mask is None else mask[:, 1:])

    def train_step(state, batch):
        loss, grads = jax.value_and_grad(pipelined_loss)(state["params"], batch)
        updates, opt = optimizer.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": loss, "grad_norm": gnorm, "step": state["step"] + 1},
        )

    return base_init, train_step, state_logical_axes


def _opt_axes_like(opt_state, p_axes):
    """Optimizer state mirrors param structure (adam mu/nu); scalars -> ().

    Walk the opt_state; any subtree with the params' treedef gets p_axes,
    everything else (counts, scalars) gets ().
    """
    import jax

    def recurse(node):
        try:
            if jax.tree.structure(node) == jax.tree.structure(
                jax.tree.map(lambda a: 0, p_axes, is_leaf=lambda x: isinstance(x, tuple))
            ):
                return p_axes
        except Exception:
            pass
        if isinstance(node, (list, tuple)) and not hasattr(node, "_fields"):
            return type(node)(recurse(c) for c in node)
        if hasattr(node, "_fields"):  # NamedTuple (optax states)
            return type(node)(*(recurse(getattr(node, f)) for f in node._fields))
        if isinstance(node, dict):
            return {k: recurse(v) for k, v in node.items()}
        return ()

    return recurse(opt_state)


class Transformer:
    """OO convenience wrapper over the functional API."""

    def __init__(self, cfg: TransformerConfig):
        self.cfg = cfg

    def init(self, key):
        return init_params(key, self.cfg)

    def apply(self, params, tokens):
        logits, _ = forward(params, tokens, self.cfg)
        return logits

    @property
    def param_axes(self):
        return param_logical_axes(self.cfg)
