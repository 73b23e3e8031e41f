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
class LayerKind:
    """One kind of layer in a model whose layers are not all alike: what mixes
    a layer's tokens (softmax attention over cached rows, a recurrence over a
    state a head: a delta rule, a selective state-space scan, or a gated short
    convolution with no state but its tail), its heads, its window and its
    rope. Two layers of one kind have weights of one shape and cache what the
    same rule keeps."""
    name: str  # the key of this kind's stack in params["kind_layers"], of its pools in the engine
    n_heads: int
    # "attention": softmax attention, query heads grouped over the model's KV
    # heads. "delta": the gated delta rule with a decay a channel
    # (ops/linear_attention.py): n_heads heads of the model's head_dim for
    # queries, keys and values alike, each behind a causal depthwise
    # convolution of conv_size taps and SiLU; a state [head_dim, head_dim] a
    # head in float32 and no token rows; the decay and the output gate
    # projected through low_rank columns; beta_scale 2 lets a step's
    # eigenvalue reach -1. No window, no rope, none of the model's attn_gate.
    # "ssd": the selective state-space recurrence with a scalar decay a head
    # (Mamba-2, ops/ssd.py): n_heads heads of head_width columns behind ONE
    # input projection [z | x B C | dt], a causal depthwise convolution of
    # conv_size taps with a bias over x, B and C together and SiLU; B and C
    # [state_size] shared by the heads of each of n_groups groups; a state
    # [state_size, heads x head_width] a layer in float32 and no token rows;
    # a skip D x a head, the gate silu(z) applied before an RMS norm over all
    # the heads' columns. No window, no rope, none of the model's attn_gate.
    # "conv": a gated short convolution and nothing else: one input projection
    # to [B | C | u] (three times d_model columns), z = B u, a causal depthwise
    # convolution of conv_size taps over z with no bias and no activation, the
    # gate C on its output, one output projection [d_model, d_model]. No heads
    # (n_heads 0), no state: what a sequence keeps is its last conv_size - 1
    # rows of z. No window, no rope, none of the model's attn_gate.
    # "latent": softmax attention through low-rank query and key/value
    # projections with their inner norms (the model's q_lora_rank, kv_lora_rank,
    # qk_nope_head_dim, qk_rope_head_dim, v_head_dim): what a token caches is
    # the kv_lora_rank latent and one roped key all n_heads heads share. The
    # roped columns turn by the kind's frequencies (YaRN's where it has a
    # factor), the softmax scale is times softmax_factor, and the model's
    # attn_gate lies on the heads' v_head_dim-wide outputs. No window.
    # ``attention_kind="latent"`` is a model whose one kind is this.
    mixer: str = "attention"
    conv_size: int = 0
    # A delta layer's decay and output gate through this many columns; 0: the
    # decay a HEAD from one matrix [D, H] (g = -exp(a_log) softplus(h wa +
    # dt_bias), the same for a head's every channel) and the gate full-rank,
    # [D, H, head_dim].
    low_rank: int = 0
    beta_scale: float = 1.0
    # A delta layer's queries and keys on this many heads (0: n_heads), each
    # convolved and normed there and then serving n_heads / n_key_heads
    # neighbouring value heads.
    n_key_heads: int = 0
    gate_scale: float = 1.0  # a delta layer's output gate is gate_scale sigmoid(.)
    softmax_factor: float = 1.0  # a latent layer's softmax scale times this (YaRN's mscale^2 where a model asks it)
    head_width: int = 0
    state_size: int = 0
    n_groups: int = 0
    # Position i sees j with i - window < j <= i (its own among them); 0: every j <= i.
    window: int = 0
    rope_theta: float = 10_000.0
    rope_share: float = 1.0  # the leading share of a head's columns that is roped; the rest pass (0: no rope)
    # YaRN (factor 0: plain rope): frequencies below the original length's
    # reach divided by `yarn_factor`, blended between the two betas' columns,
    # and cos / sin multiplied by `attention_factor`.
    yarn_factor: float = 0.0
    yarn_original_len: int = 0
    yarn_beta_fast: float = 32.0
    yarn_beta_slow: float = 1.0
    attention_factor: float = 1.0

    @property
    def plain_rope(self) -> bool:
        return self.rope_share == 1.0 and not self.yarn_factor and self.attention_factor == 1.0

    @property
    def state(self) -> bool:
        """Keeps a float32 state a slot beside its convolution's tail, rewritten by a kernel of its own."""
        return self.mixer in ("delta", "ssd")

    @property
    def recurrent(self) -> bool:
        """Keeps by slot what does not grow with the context (a state and a
        tail, or a tail alone), and no rows of tokens."""
        return self.state or self.mixer == "conv"

    @property
    def key_heads(self) -> int:
        return self.n_key_heads or self.n_heads


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
    # The loss head's chunk of the sequence (head_loss: the full [B, S, V]
    # logits never materialize). 0: read off the shapes, the fewest equal
    # chunks whose logits fit a byte budget; > 0: this many positions a chunk.
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
    # Every RMS norm's scale is norm_gating sigmoid(w) of its weight w (2: a
    # weight of zeros is a scale of one; weights are then drawn N(0, 1/4), so
    # that a program that multiplies by w itself is far off); 0: the scale is
    # w. A delta layer's head norm keeps its plain weight.
    norm_gating: float = 0.0
    # SwiGLU with its two branches clamped, silu(min(gate, limit)) *
    # clip(up, -limit, limit), in dense, shared and routed experts alike; 0: no clamp.
    swiglu_limit: float = 0.0
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
    # passes. Served, the layer hands out its [pairs, live tiles] counts;
    # trained (_layer), it differentiates through the grouped matmul's custom
    # VJP and hands out the balance term over all n_experts beside them.
    # experts_held = 0: every expert here and every expert on every token
    # (_moe_ffn), the form for a handful of experts.
    experts_held: int = 0
    first_expert: int = 0
    expert_d_ff: int = 0
    n_shared_experts: int = 0
    routed_scaling: float = 1.0
    router_score: str = "softmax"  # softmax | sigmoid, over the router's logits in float32
    # The training loss is the mean NLL + router_aux_coef x the routed layers'
    # balance terms summed (cross_entropy_loss).
    router_aux_coef: float = 0.01
    # Held experts chosen by score + a bias a scored expert (a float32 leaf
    # "router_bias" [n_experts] a routed layer, no part of the weight) and
    # weighed by the score alone, s_e / (the chosen scores' sum + 1e-6). Off:
    # no leaf, the choice by the score, no 1e-6.
    router_bias: bool = False
    # A head's width; 0 -> d_model // n_heads.
    head_dim: int = 0
    # Layers of more than one kind: the LayerKinds of one period (a latent
    # kind among them where attention_kind is "gqa": the kind says it),
    # layer l being of kind layer_pattern[l % len]. The leading dense layers
    # are of one kind and lie in params["dense_layers"]; the layers after them
    # are whole periods, each kind's in one stack of params["kind_layers"],
    # and run as one scan over periods (run_layers). Empty: every layer is of
    # the one kind n_heads and rope_theta describe, params["layers"].
    layer_pattern: tuple = ()
    # An attention layer's output times sigmoid(h Wg), h the layer's normed
    # input, before the output projection: "per_head" one scalar a head (Wg
    # [D, H]), "elementwise" one a column (Wg [D, H, head_dim]; a latent
    # layer's [D, H, v_head_dim], and "elementwise" alone).
    attn_gate: str = ""
    # A "gqa" layer's queries and keys RMS-normed over a head's columns before
    # the rope, one weight of head_dim for all query heads ("q_norm") and one
    # for all key heads ("k_norm"), eps norm_eps. Off: no such leaves.
    qk_norm: bool = False
    # Four scalars of a model parametrised for width-independent learning
    # rates, each off at its default (the program is then what it was):
    # the embedding times embed_multiplier, every sublayer's output times
    # residual_multiplier before it joins the residual, attention scores times
    # attention_multiplier in place of head_dim^-1/2 (0: that), logits divided
    # by logits_divisor. tie_embeddings: the head is the embedding, one array
    # (no params["lm_head"]).
    embed_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    attention_multiplier: float = 0.0
    logits_divisor: float = 1.0
    tie_embeddings: bool = False

    @property
    def kv_heads(self) -> int:
        return self.n_kv_heads or self.n_heads

    @property
    def latent(self) -> bool:
        """Some kind of the model's layers is latent attention."""
        return any(k.mixer == "latent" for k in self.kinds)

    @property
    def kinds(self) -> tuple:
        """The model's distinct layer kinds, in the order layers first meet them."""
        if not self.layer_pattern:
            mixer = "latent" if self.attention_kind == "latent" else "attention"
            return (LayerKind("layers", self.n_heads, mixer=mixer, rope_theta=self.rope_theta),)
        return tuple(dict.fromkeys(self.layer_pattern))

    def kind_of(self, layer: int) -> LayerKind:
        return self.layer_pattern[layer % len(self.layer_pattern)] if self.layer_pattern else self.kinds[0]

    def layers_of(self, kind: LayerKind) -> int:
        return sum(self.kind_of(l) == kind for l in range(self.n_layers))

    @property
    def period(self) -> tuple:
        """The kinds of one period in the order the layers after the leading
        dense ones pass them."""
        p = len(self.layer_pattern)
        return tuple(self.kind_of(self.n_dense_layers + j) for j in range(p))

    @property
    def n_periods(self) -> int:
        return (self.n_layers - self.n_dense_layers) // len(self.layer_pattern)

    def __post_init__(self):
        if not self.head_dim:
            assert self.d_model % self.n_heads == 0
            object.__setattr__(self, "head_dim", self.d_model // self.n_heads)
        assert self.n_heads % self.kv_heads == 0
        assert self.attn_gate in ("", "per_head", "elementwise")
        if self.layer_pattern:
            object.__setattr__(self, "layer_pattern", tuple(self.layer_pattern))
            p = len(self.layer_pattern)
            assert self.attention_kind == "gqa", (
                "a model that is latent throughout has no layer pattern: its kinds say it")
            assert len({k.name for k in self.kinds}) == len(self.kinds), "two kinds under one name"
            assert all(k.n_heads % self.kv_heads == 0 for k in self.kinds if k.mixer == "attention")
            for k in self.kinds:
                assert k.mixer in ("attention", "latent", "delta", "ssd", "conv"), k.mixer
                assert k.mixer != "latent" or not k.window, f"a latent layer has no window: {k}"
                assert k.mixer != "conv" or (k.conv_size > 1 and not k.n_heads and not k.window), (
                    f"a conv layer has a short convolution, no heads and no window: {k}")
                assert k.mixer != "delta" or (
                    k.conv_size > 1 and k.low_rank >= 0 and k.n_heads % k.key_heads == 0 and not k.window), (
                    f"a delta layer has a short convolution, low-rank sizes or none, key heads that divide its "
                    f"heads and no window: {k}")
                assert k.mixer != "ssd" or (
                    k.conv_size > 1 and k.head_width > 0 and k.state_size > 0 and k.n_groups > 0
                    and k.n_heads % k.n_groups == 0 and not k.window), (
                    f"an ssd layer has a short convolution, a head width, a state size, groups that divide its "
                    f"heads and no window: {k}")
            assert len({self.kind_of(l) for l in range(self.n_dense_layers)}) <= 1, (
                "the leading dense layers are one stack: of one kind")
            if (self.n_layers - self.n_dense_layers) % p:
                raise ValueError(
                    f"a trailing partial period is not written: {self.n_layers} layers, "
                    f"{self.n_dense_layers} of them leading dense ones, leave "
                    f"{self.n_layers - self.n_dense_layers} for periods of {p} (run_layers scans whole periods)")
        assert self.attention_kind in ("gqa", "latent"), self.attention_kind
        assert 0 <= self.n_dense_layers <= self.n_layers
        assert self.router_score in ("softmax", "sigmoid"), self.router_score
        assert not self.router_bias or self.experts_held, (
            "a selection bias is written for held experts (_held_experts_pass)")
        assert not (self.qk_norm and self.latent), "a head norm on q and k, on gqa layers (a latent layer has its own)"
        assert not self.latent or self.attn_gate in ("", "elementwise"), "a latent layer's output gate is elementwise"
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


def _conv_taps_init(key, shape, dtype):
    """A conv layer's taps [L, T, D], the oldest input's first: normal, the
    newest input's tap 4 times the others' in amplitude, a channel's T
    variances summing to 1 as T equal taps of variance 1 / T would (T = 3:
    1/18, 1/18, 8/9). With equal taps two thirds of a position's output is its
    two neighbours', and whatever differs at one position (a router's near-tie
    that bfloat16 resolves the other way) reaches every later position within
    two a layer at full size; PERF.md section 6, PR 50, has what that did to
    the serve check over 8 routed layers and what this split reads."""
    amplitude = jnp.asarray([0.25] * (shape[1] - 1) + [1.0], jnp.float32)
    amplitude = amplitude / jnp.sqrt(jnp.sum(amplitude ** 2))
    return (jax.random.normal(key, shape, jnp.float32) * amplitude[:, None]).astype(dtype)


def _inverse_softplus(y):
    return y + jnp.log(-jnp.expm1(-y))


def _norm_init(cfg: "TransformerConfig", shape, key=None):
    """A norm's weights: ones, or under ``norm_gating`` N(0, 1/4) from ``key``,
    whose scale norm_gating sigmoid(w) lies about one (0.5 .. 1.5 for most
    columns at 2)."""
    if not cfg.norm_gating:
        return jnp.ones(shape, cfg.param_dtype)
    return (0.5 * jax.random.normal(key, shape, jnp.float32)).astype(cfg.param_dtype)


def _init_stack(key: jax.Array, cfg: TransformerConfig, L: int, routed: bool, kind: LayerKind | None = None) -> tuple:
    """One stack of L identical layers of `kind` (None: the model's one kind;
    leading 'layers' dim on every leaf); `routed`: the FFN is experts behind
    a router, else dense of width d_ff.
    Returns (the stack, the iterator over the keys it left)."""
    pd = cfg.param_dtype
    kind = kind or cfg.kinds[0]
    k = iter(jax.random.split(key, 24 if kind.recurrent else 16))
    D, F, H = cfg.d_model, cfg.d_ff, kind.n_heads
    # keys beside k's, which a plain norm (ones) never asked for
    norm_keys = iter(jax.random.split(jax.random.fold_in(key, 54), 8)) if cfg.norm_gating else None

    def norm_w(*shape):
        return _norm_init(cfg, (L, *shape), next(norm_keys) if norm_keys else None)

    if kind.mixer == "ssd":
        I, C, T = H * kind.head_width, ssd_conv_channels(kind), kind.conv_size
        layer = {
            "attn_norm": norm_w(D),
            # [z | x B C | dt] from D columns: the outputs' axis before the inputs', so that the minor dimension
            # is whole lane tiles (as [D, 8512] the TPU compiler turned the stack round on every call, a copy of it)
            "w_in": _dense_init(next(k), (L, I + C + H, D), pd, in_axis=2),
            "conv": _dense_init(next(k), (L, T, C), pd, in_axis=1),  # the oldest input's tap first
            "conv_bias": jax.random.uniform(next(k), (L, C), jnp.float32, -T ** -0.5, T ** -0.5).astype(pd),
            "dt_bias": _inverse_softplus(jnp.exp(jax.random.uniform(
                next(k), (L, H), jnp.float32, math.log(0.001), math.log(0.1)))).astype(pd),
            "a_log": jnp.log(jax.random.uniform(next(k), (L, H), jnp.float32, 1.0, 16.0)).astype(pd),
            "d_skip": jnp.ones((L, H), pd),
            "o_norm": jnp.ones((L, I), pd),
            "wo": _dense_init(next(k), (L, H, kind.head_width, D), pd, in_axis=(1, 2)),
            "ffn_norm": norm_w(D),
        }
    elif kind.mixer == "conv":
        layer = {
            "attn_norm": norm_w(D),
            "w_in": _dense_init(next(k), (L, D, 3 * D), pd, in_axis=1),  # [B | C | u], in that order of thirds
            "conv": _conv_taps_init(next(k), (L, kind.conv_size, D), pd),
            "w_out": _dense_init(next(k), (L, D, D), pd, in_axis=1),
            "ffn_norm": norm_w(D),
        }
    elif kind.recurrent:
        Hd, R, T, Hk = cfg.head_dim, kind.low_rank, kind.conv_size, kind.key_heads
        heads = lambda: _dense_init(next(k), (L, D, H, Hd), pd, in_axis=1)
        key_heads = lambda: _dense_init(next(k), (L, D, Hk, Hd), pd, in_axis=1)
        a_step = lambda *shape: _inverse_softplus(jnp.exp(jax.random.uniform(
            next(k), (L, *shape), jnp.float32, math.log(0.001), math.log(0.1)))).astype(pd)
        layer = {
            "attn_norm": norm_w(D),
            "wq": key_heads(), "wk": key_heads(), "wv": heads(),
            # q's, k's and v's taps, the oldest first; with fewer key heads the three lie along one axis of heads
            "conv": _dense_init(next(k), (L, T, 3, H, Hd) if Hk == H else (L, T, 2 * Hk + H, Hd), pd, in_axis=1),
        }
        if R:
            layer.update({
                "wf_a": _dense_init(next(k), (L, D, R), pd, in_axis=1),
                "wf_b": _dense_init(next(k), (L, R, H, Hd), pd, in_axis=1),
                # a step's size before the projection moves it: softplus^-1 of 0.001 .. 0.1, log-uniform
                "dt_bias": a_step(H, Hd),
            })
        else:
            layer.update({"wa": _dense_init(next(k), (L, D, H), pd, in_axis=1), "dt_bias": a_step(H)})
        layer.update({
            "a_log": jnp.log(jax.random.uniform(next(k), (L, H), jnp.float32, 1.0, 16.0)).astype(pd),
            "wb": _dense_init(next(k), (L, D, H), pd, in_axis=1),
        })
        if R:
            layer.update({"wg_a": _dense_init(next(k), (L, D, R), pd, in_axis=1),
                          "wg_b": _dense_init(next(k), (L, R, H, Hd), pd, in_axis=1)})
        else:
            layer["wz"] = heads()
        layer.update({
            "o_norm": jnp.ones((L, Hd), pd),
            "wo": _dense_init(next(k), (L, H, Hd, D), pd, in_axis=(1, 2)),
            "ffn_norm": norm_w(D),
        })
    elif kind.mixer == "latent":
        R, Rq = cfg.kv_lora_rank, cfg.q_lora_rank
        nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        layer = {
            "attn_norm": norm_w(D),
            "wq_a": _dense_init(next(k), (L, D, Rq), pd, in_axis=1),
            "q_norm": norm_w(Rq),
            "wq_b": _dense_init(next(k), (L, Rq, H, nope + rope), pd, in_axis=1),
            "wkv_a": _dense_init(next(k), (L, D, R + rope), pd, in_axis=1),
            "kv_norm": norm_w(R),
            "wk_b": _dense_init(next(k), (L, R, H, nope), pd, in_axis=1),
            "wv_b": _dense_init(next(k), (L, R, H, vd), pd, in_axis=1),
            "wo": _dense_init(next(k), (L, H, vd, D), pd, in_axis=(1, 2)),
            "ffn_norm": norm_w(D),
        }
        if cfg.attn_gate:
            layer["wg"] = _dense_init(next(k), (L, D, H, vd), pd, in_axis=1)
    else:
        KV, Hd = cfg.kv_heads, cfg.head_dim
        layer = {
            "attn_norm": norm_w(D),
            "wq": _dense_init(next(k), (L, D, H, Hd), pd, in_axis=1),
            "wk": _dense_init(next(k), (L, D, KV, Hd), pd, in_axis=1),
            "wv": _dense_init(next(k), (L, D, KV, Hd), pd, in_axis=1),
            "wo": _dense_init(next(k), (L, H, Hd, D), pd, in_axis=(1, 2)),
            "ffn_norm": norm_w(D),
        }
        if cfg.attn_gate:
            layer["wg"] = _dense_init(next(k), (L, D, H) + ((Hd,) if cfg.attn_gate == "elementwise" else ()), pd, in_axis=1)
        if cfg.qk_norm:
            layer.update({"q_norm": norm_w(Hd), "k_norm": norm_w(Hd)})
    if cfg.sandwich_norm:
        layer.update({"post_attn_norm": norm_w(D), "post_ffn_norm": norm_w(D)})
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
        if cfg.router_bias:
            # small beside a sigmoid's scores, and enough to change the chosen set for a real share of tokens
            layer["router_bias"] = jax.random.uniform(next(k), (L, cfg.n_experts), jnp.float32, -0.05, 0.05)
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
    before it; a model with none has no such key (an empty first stack). A
    model with a layer pattern has, in place of ``layers``, ``kind_layers``:
    {a kind's name: the stack of that kind's layers after the dense ones, in
    the order a token passes them}."""
    pd = cfg.param_dtype
    D = cfg.d_model
    routed = bool(cfg.n_experts)
    if cfg.layer_pattern:
        k = iter(jax.random.split(jax.random.fold_in(key, 2), 2))
        stacks = {"kind_layers": {
            kind.name: _init_stack(jax.random.fold_in(key, 3 + i), cfg,
                                   cfg.n_periods * cfg.period.count(kind), routed, kind)[0]
            for i, kind in enumerate(dict.fromkeys(cfg.period))}}
    else:
        layer, k = _init_stack(key, cfg, cfg.n_layers - cfg.n_dense_layers, routed)
        stacks = {"layers": layer}
    if cfg.tie_embeddings:
        # one array for both ends: a row as long as a head's column, which embed_multiplier is there to scale up
        ends = {"embed": _dense_init(next(k), (cfg.vocab_size, D), pd, in_axis=1)}
    else:
        ends = {"embed": _dense_init(next(k), (cfg.vocab_size, D), pd) * (D ** 0.5),
                "lm_head": _dense_init(next(k), (D, cfg.vocab_size), pd, in_axis=0)}
    params = {"embed": ends.pop("embed"), **stacks,
              "final_norm": _norm_init(cfg, (D,), jax.random.fold_in(key, 54) if cfg.norm_gating else None), **ends}
    if cfg.n_dense_layers:
        params["dense_layers"], _ = _init_stack(
            jax.random.fold_in(key, 1), cfg, cfg.n_dense_layers, routed=False, kind=cfg.kind_of(0))
    return params


HELD_EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def ssd_conv_channels(kind: LayerKind) -> int:
    """The columns an ssd layer's convolution runs over: x, B and C side by side."""
    return kind.n_heads * kind.head_width + 2 * kind.n_groups * kind.state_size


def slot_state_shapes(cfg: TransformerConfig, kind: LayerKind) -> tuple:
    """What a layer of a recurrent kind keeps of a sequence, whatever its
    length: (its state, float32; the last conv_size - 1 inputs of its short
    convolution, in the activations' dtype). The engine's two pools of the
    kind are these behind [the kind's layers, slots]. An ssd layer's T - 1
    inputs lie end to end in one row: as [T - 1, channels] behind the slots a
    pool's minor tile would be 3 rows of a tile's 8 or 16, and the TPU
    compiler turned the whole pool round and back in every layer of a decode
    step (my chip run, PR 46); the mixer folds the row. A conv layer has no
    state (None) and its T - 1 rows of z = B u lie end to end alike."""
    T = kind.conv_size
    if kind.mixer == "conv":
        return None, ((T - 1) * cfg.d_model,)
    if kind.mixer == "ssd":
        from ray_tpu.ops.ssd import state_shape

        return state_shape(kind.n_heads, kind.head_width, kind.state_size), ((T - 1) * ssd_conv_channels(kind),)
    H, Hk = kind.n_heads, kind.key_heads
    return (H, cfg.head_dim, cfg.head_dim), (T - 1, *((3, H) if Hk == H else (2 * Hk + H,)), cfg.head_dim)


def recurrence(kind: LayerKind) -> tuple:
    """A kind's rule on its state (``kind.state``) as (its name in a trace,
    over a prompt, one token a slot): the kernels on a TPU backend, their ``jax.numpy`` forms
    elsewhere. Both take the operands the kind's mixer hands its ``attend``
    (the last two are the log decay and the step size: zero in both leaves a
    state as it was), then a state or a pool."""
    if kind.mixer == "ssd":
        from ray_tpu.ops.ssd import ssd_rule

        return ("ssd", *ssd_rule())
    from ray_tpu.ops.linear_attention import delta_rule

    return ("kda", *delta_rule())


def embed_tokens(params: dict, tokens, cfg: TransformerConfig):
    """tokens [...] int32 -> their embeddings [..., D] in cfg.dtype, as every program starts."""
    x = params["embed"].astype(cfg.dtype)[tokens]
    return x * cfg.embed_multiplier if cfg.embed_multiplier != 1.0 else x


def head_matrix(params: dict, cfg: TransformerConfig):
    """The head [D, V] in cfg.dtype: its own array, or the embedding turned."""
    return (params["embed"].T if cfg.tie_embeddings else params["lm_head"]).astype(cfg.dtype)


def hidden_logits(params: dict, x, cfg: TransformerConfig):
    """Final-normed hidden states [..., D] -> logits [..., V], as every program ends."""
    if cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"].astype(cfg.dtype))
    else:
        logits = x @ params["lm_head"].astype(cfg.dtype)
    return logits / cfg.logits_divisor if cfg.logits_divisor != 1.0 else logits


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


def _scan_periods(body, carry, stacks: dict, cfg: TransformerConfig, first: dict, xs: dict | None = None):
    """``lax.scan`` over the periods of a model with a layer pattern: the
    scan's body runs one period, ``body(carry, lp, kind, index) -> (carry,
    y)`` once for each of its layers in order. The kinds' stacks are whole
    operands of the loop and a layer's parameters are its index's slice of
    each (what a scan makes of its xs; a period's slice of a stack, taken
    first and then indexed by the layer, is a copy of it: 170 MB for three
    sliding layers' wq at the serve cell's widths, as compiled for a v5e);
    the held experts' matrices are handed on whole with the layer's index in
    its kind's stack, as in ``scan_stack``. `index` counts the layer among
    ALL of its kind's (`first[kind.name]` leading ones came before these
    stacks), and is where a layer's slice of `xs` (one tuple of arrays a
    kind, every layer of the kind along the leading axis) is taken from,
    handed to body behind the index. Returns (carry, {a kind's name: its
    layers' y, stacked in order})."""
    period = cfg.period
    a_period = {kind.name: period.count(kind) for kind in period}
    held = cfg.experts_held and all("router" in stack for stack in stacks.values())

    def layer_of(tree, at):
        return jax.tree.map(lambda a: lax.dynamic_index_in_dim(a, at, 0, keepdims=False), tree)

    def one_period(carry, t):
        seen = dict.fromkeys(a_period, 0)
        ys = {name: [] for name in a_period}
        for kind in period:
            j, c, stack = seen[kind.name], a_period[kind.name], stacks[kind.name]
            seen[kind.name] += 1
            at = t * c + j  # in its kind's stack
            if held:
                lp = layer_of({k: v for k, v in stack.items() if k not in HELD_EXPERT_WEIGHTS}, at)
                lp.update({k: stack[k] for k in HELD_EXPERT_WEIGHTS}, expert_layer=at)
            else:
                lp = layer_of(stack, at)
            index = first[kind.name] + at
            carry, y = body(carry, lp, kind, index, *(() if xs is None else layer_of(tuple(xs[kind.name]), index)))
            ys[kind.name].append(y)
        return carry, {name: jax.tree.map(lambda *a: jnp.stack(a), *y) for name, y in ys.items()}

    carry, ys = lax.scan(one_period, carry, jnp.arange(cfg.n_periods, dtype=jnp.int32))
    # [periods, a kind's layers a period, ...] -> [the kind's layers, ...]
    return carry, {name: jax.tree.map(lambda a: a.reshape(-1, *a.shape[2:]), y) for name, y in ys.items()}


def run_layers(body, carry, params: dict, cfg: TransformerConfig, xs: dict | None = None):
    """Every layer of the model in the order a token passes them:
    ``body(carry, lp, kind, index, *xs_i) -> (carry, y)``, lp one layer's
    parameters (scan_stack's), kind its LayerKind, index its place among the
    layers of its kind (an int32 scalar: what a cache kept by kind is indexed
    with), xs_i the layer's slice of each array of ``xs[kind.name]`` (arrays
    with every layer of the kind along the leading axis; scanned operands of a
    stack's scan).
    The leading dense layers where the model has any, then ``layers`` as one
    scan, or for a model with a layer pattern its periods (_scan_periods).
    Returns (carry, {a kind's name: y of its layers [that kind's layers,
    ...], in order})."""
    first = {kind.name: 0 for kind in cfg.kinds}
    ys: dict = {}

    def stack_of(name, kind):
        nonlocal carry
        n = jax.tree.leaves(params[name])[0].shape[0]
        at = first[kind.name]
        index = jnp.arange(at, at + n, dtype=jnp.int32)
        extra = () if xs is None else tuple(a[at:at + n] for a in xs[kind.name])
        carry, y = scan_stack(lambda c, lp, i, *x: body(c, lp, kind, i, *x), carry, params[name], cfg, index, *extra)
        first[kind.name] += n
        ys.setdefault(kind.name, []).append(y)

    if "dense_layers" in params:
        stack_of("dense_layers", cfg.kind_of(0))
    if cfg.layer_pattern:
        carry, y = _scan_periods(body, carry, params["kind_layers"], cfg, first, xs)
        for name, v in y.items():
            ys.setdefault(name, []).append(v)
    else:
        stack_of("layers", cfg.kinds[0])
    def joined(v):
        v = [y for y in v if y is not None]  # a stack whose layers hand nothing out
        if len(v) < 2:
            return v[0] if v else None
        return jax.tree.map(lambda *a: jnp.concatenate(a, axis=0), *v)

    return carry, {name: joined(v) for name, v in ys.items()}


def _stack_logical_axes(cfg: TransformerConfig, routed: bool, kind: LayerKind | None = None) -> dict:
    """A stack's logical axes (attention kinds differ in sizes only)."""
    kind = kind or cfg.kinds[0]
    if kind.mixer == "ssd":
        layer = {
            "attn_norm": ("layers", "embed"), "w_in": ("layers", None, "embed"), "conv": ("layers", None, None),
            "conv_bias": ("layers", None), "dt_bias": ("layers", "heads"), "a_log": ("layers", "heads"),
            "d_skip": ("layers", "heads"), "o_norm": ("layers", None),
            "wo": ("layers", "heads", "head_dim", "embed"), "ffn_norm": ("layers", "embed"),
        }
    elif kind.mixer == "conv":
        layer = {
            "attn_norm": ("layers", "embed"), "w_in": ("layers", "embed", None), "conv": ("layers", None, None),
            "w_out": ("layers", None, "embed"), "ffn_norm": ("layers", "embed"),
        }
    elif kind.recurrent:
        heads = ("layers", "embed", "heads", "head_dim")
        low = ("layers", None, "heads", "head_dim")
        grouped = kind.key_heads != kind.n_heads
        layer = {
            "attn_norm": ("layers", "embed"), "wq": heads, "wk": heads, "wv": heads,
            "conv": ("layers", None, "heads", "head_dim") if grouped else ("layers", None, None, "heads", "head_dim"),
            "a_log": ("layers", "heads"), "wb": ("layers", "embed", "heads"), "o_norm": ("layers", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"), "ffn_norm": ("layers", "embed"),
        }
        if kind.low_rank:
            layer.update({"wf_a": ("layers", "embed", None), "wf_b": low, "dt_bias": ("layers", "heads", "head_dim"),
                          "wg_a": ("layers", "embed", None), "wg_b": low})
        else:
            layer.update({"wa": ("layers", "embed", "heads"), "dt_bias": ("layers", "heads"), "wz": heads})
    elif kind.mixer == "latent":
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
        if cfg.attn_gate:
            layer["wg"] = ("layers", "embed", "heads", "head_dim")
    else:
        layer = {
            "attn_norm": ("layers", "embed"),
            "wq": ("layers", "embed", "heads", "head_dim"),
            "wk": ("layers", "embed", "kv_heads", "head_dim"),
            "wv": ("layers", "embed", "kv_heads", "head_dim"),
            "wo": ("layers", "heads", "head_dim", "embed"),
            "ffn_norm": ("layers", "embed"),
        }
        if cfg.attn_gate:
            layer["wg"] = ("layers", "embed", "heads") + (("head_dim",) if cfg.attn_gate == "elementwise" else ())
        if cfg.qk_norm:
            layer.update({"q_norm": ("layers", "head_dim"), "k_norm": ("layers", "head_dim")})
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
        if cfg.router_bias:
            layer["router_bias"] = ("layers", None)
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
    routed = bool(cfg.n_experts)
    axes = {
        "embed": ("vocab", "embed"),
        **({"kind_layers": {kind.name: _stack_logical_axes(cfg, routed, kind) for kind in dict.fromkeys(cfg.period)}}
           if cfg.layer_pattern else {"layers": _stack_logical_axes(cfg, routed)}),
        "final_norm": ("embed",),
        **({} if cfg.tie_embeddings else {"lm_head": ("embed", "vocab")}),
    }
    if cfg.n_dense_layers:
        axes["dense_layers"] = _stack_logical_axes(cfg, routed=False, kind=cfg.kind_of(0))
    return axes


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------

def _rms_norm(x, w, eps=1e-6, gating=0.0):
    """x / rms(x) times w, or times gating sigmoid(w) (TransformerConfig.norm_gating)."""
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    if gating:
        w = gating * jax.nn.sigmoid(w.astype(jnp.float32))
    return (x * lax.rsqrt(var + eps).astype(x.dtype)) * w.astype(x.dtype)


def model_norm(x, w, cfg: TransformerConfig):
    """The model's norm: every sublayer's, the latent projections' inner ones and the final one."""
    return _rms_norm(x, w, cfg.norm_eps, cfg.norm_gating)


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


def rope_inv_freq(kind: LayerKind, width: int):
    """The `width // 2` rotation frequencies of a kind's roped columns
    (numpy, float64: constants of the program). Plain: theta^(-2i/width).
    YaRN: with c(r) = width ln(L / (2 pi r)) / (2 ln theta) the column at
    which a frequency turns r times over the original length L, columns
    below low = floor(c(beta_fast)) keep their frequency, those above high =
    ceil(c(beta_slow)) have it divided by the factor, a linear blend between."""
    import numpy as np

    half = width // 2
    e = kind.rope_theta ** (-np.arange(half, dtype=np.float64) * 2.0 / width)
    if not kind.yarn_factor:
        return e

    def column(turns):
        return width * math.log(kind.yarn_original_len / (2 * math.pi * turns)) / (2 * math.log(kind.rope_theta))

    low = max(math.floor(column(kind.yarn_beta_fast)), 0)
    high = min(math.ceil(column(kind.yarn_beta_slow)), width - 1)
    keep = 1.0 - np.clip((np.arange(half, dtype=np.float64) - low) / max(high - low, 0.001), 0.0, 1.0)
    return e / kind.yarn_factor * (1.0 - keep) + e * keep


def _rope_kind(x, positions, kind: LayerKind):
    """x [B, S, H, Hd] roped as its layer's kind says: the leading
    rope_share of a head's columns rotated (rotate-half inside them) by the
    kind's frequencies, cos and sin times its attention_factor; the rest pass."""
    if kind.plain_rope:
        return _rope(x, positions, kind.rope_theta)
    if not kind.rope_share:  # a kind without positions
        return x
    width = int(x.shape[-1] * kind.rope_share)
    half = width // 2
    freqs = jnp.asarray(rope_inv_freq(kind, width), jnp.float32)
    angles = positions[..., None].astype(jnp.float32) * freqs  # [B, S, half]
    cos = (jnp.cos(angles) * kind.attention_factor)[:, :, None, :].astype(x.dtype)
    sin = (jnp.sin(angles) * kind.attention_factor)[:, :, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


def _flash(q, k, v, cfg: TransformerConfig, segment_ids, scale=None, window=0):
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
            q, k, v, causal=True, segment_ids=seg, scale=scale, window=window,
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


def _attention(q, k, v, cfg: TransformerConfig, positions=None, segment_ids=None, scale=None, window=0):
    """Dispatch to the configured attention implementation. window: a
    sliding layer's (0: none); flash and the reference take it.

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
            return _flash(q, k, v, cfg, segment_ids, window=window)
        # A latent layer: keys wider than values; the kernel takes one width.
        return _flash(*lane_padded(q, k, v), cfg, segment_ids, scale)[..., :v.shape[-1]]
    if scale is not None and impl != "reference":
        raise NotImplementedError(f"attention_impl={impl!r} is not written for latent attention")
    if window and impl != "reference":
        raise NotImplementedError(f"attention_impl={impl!r} is not written for an attention window")
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

    return mha_reference(q, k, v, causal=True, segment_ids=segment_ids, scale=scale, window=window)


def _clamped(gate, up, limit: float):
    """SwiGLU's two branches under ``swiglu_limit``: the gate from above, the other from both sides."""
    if not limit:
        return gate, up
    with jax.named_scope("swiglu_clamp"):
        return jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)


def _swiglu_product(project, w_gate, w_up, limit: float):
    """silu(project(w_gate)) * project(w_up), clamped under ``swiglu_limit``; without a limit the operations
    and their order are what the held experts' pass traced before the limit was there."""
    if not limit:
        return jax.nn.silu(project(w_gate)) * project(w_up)
    gate, up = _clamped(project(w_gate), project(w_up), limit)
    return jax.nn.silu(gate) * up


def _dense_ffn(x, p, limit: float = 0.0):
    gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"].astype(x.dtype))
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"].astype(x.dtype))
    gate, up = _clamped(gate, up, limit)
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
    gate, up = _clamped(gate, up, cfg.swiglu_limit)
    h = jax.nn.silu(gate) * up
    h = wlc(h, ("experts", "batch", "seq", "expert_mlp"))
    out = jnp.einsum("ebsf,efd->ebsd", h, p["w_down"].astype(x.dtype))
    out = jnp.einsum("ebsd,bse->bsd", out, route)
    aux = _load_balance_loss(weights, top_idx, E)
    return out, aux


def _load_balance_loss(weights, top_idx, n_experts):
    """Switch-transformer aux loss: mean_prob * mean_assignment per expert,
    the assignment a token's FIRST choice alone: _moe_ffn's loss stays what
    its configurations have trained on. A held layer's term counts every
    choice (``_balance_term``)."""
    me = jnp.mean(weights, axis=(0, 1))  # [E]
    ce = jnp.mean(
        jax.nn.one_hot(top_idx[..., 0], n_experts, dtype=jnp.float32), axis=(0, 1)
    )
    return n_experts * jnp.sum(me * ce)


def latent_scale(cfg: TransformerConfig, kind: LayerKind | None = None) -> float:
    """Softmax scale of a latent layer: over the whole query/key width, times the kind's softmax_factor."""
    return (kind.softmax_factor if kind else 1.0) / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)


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


def _latent_qkv(h, lp, cfg: TransformerConfig, positions, kind: LayerKind):
    """-> (q_nope [B,S,H,nope], q_rope [B,S,H,rope]) roped, c [B,S,R] normed,
    k_rope [B,S,rope] roped; the roped columns by the kind's frequencies."""
    dt, R = h.dtype, cfg.kv_lora_rank
    with jax.named_scope("mla_q"):
        cq = model_norm(jnp.einsum("bsd,dr->bsr", h, lp["wq_a"].astype(dt)), lp["q_norm"], cfg)
        q = jnp.einsum("bsr,rhk->bshk", cq, lp["wq_b"].astype(dt))
        q = wlc(q, ("batch", "seq", "heads", "head_dim"))
        q_nope, q_rope = q[..., :cfg.qk_nope_head_dim], q[..., cfg.qk_nope_head_dim:]
        q_rope = _rope_kind(q_rope, positions, kind)
    with jax.named_scope("mla_kv"):
        ckr = jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"].astype(dt))
        c = model_norm(ckr[..., :R], lp["kv_norm"], cfg)
        k_rope = _rope_kind(ckr[:, :, None, R:], positions, kind)[:, :, 0]
    return (q_nope, q_rope), c, k_rope


def _expert_tile(tokens: int, cfg: TransformerConfig) -> int:
    """Rows of a grouped-matmul tile: twice the pairs an expert expects, as a
    power of two in 16 .. 256, so that most experts fill one tile and an
    expert's weights are read once."""
    expect = 2 * tokens * cfg.expert_top_k // cfg.n_experts
    return min(256, max(16, 1 << max(expect - 1, 0).bit_length()))


PAIRS_A_PASS = 32_768  # (token, expert) pairs one pass of _held_experts_ffn lays out
EXPERT_ROWS = "expert_rows"  # the name a training pass gives the grouped matmuls' results, for its remat policy


LAYOUT_CHUNK = 2048  # rows a step of the way back's loop over live rows moves: whole tiles (a power of two up to 256)
LEVEL_CHUNK = 512  # tokens a step of a sum by token gathers for


def _layout_chunk(rows: int, cfg: TransformerConfig) -> int:
    """Whether the copies between token space and the grouped matmul's row
    space follow the pairs held (LAYOUT_CHUNK) or move every row of the plan
    and every pair of T x K (0), by the pass's shapes: a plan (``plan_rows``:
    every pair the routing COULD send here) of four chunks on, of which at
    most half can be live. A pass of 4,096 tokens x 8 choices with 16 of 64
    experts held plans 36,864 rows and fills about 11,000, a prompt's pass
    with an eighth or a sixteenth held less still: there a sum by token over
    the held pairs takes a v5e 0.7 ms where the gather of all 32,768 takes
    2.0. A decode step's few hundred to 1,792 rows and a short prompt's are
    moved whole (a loop's start costs more than its dead rows), and so is a
    pass that holds every expert (nearly every row is live and every pair is
    held: the loops read 2.8 ms where the plain forms read 1.9)."""
    return LAYOUT_CHUNK if rows >= 4 * LAYOUT_CHUNK and 2 * cfg.experts_held <= cfg.n_experts else 0


def _summed_by_token(rows, weights, plan, chunk: int):
    """float32 [T, D]: token t's held pairs' rows of ``rows`` [M, D] summed in
    float32 in the order of its choices, each times its weight first
    (``weights`` [T, K] float32; None: as it is). Without a ``chunk`` a gather
    of all T x K pairs, masked (a pair that is not held reads row 0); with
    one, ``_summed_by_level`` over the held pairs only."""
    if chunk:
        return _summed_by_level(rows, weights, plan.row_of_pair, plan.held, size=min(LEVEL_CHUNK, plan.held.shape[0]))
    pair = rows[plan.row_of_pair].astype(jnp.float32)  # [T, K, D]
    pair = pair if weights is None else pair * weights[..., None]
    return jnp.sum(jnp.where(plan.held[..., None], pair, 0.0), axis=1)


# One trace a shape signature for the life of the process, as the kernels' calls are (ops/__init__.py): a prompt's
# passes are 2,048 or 4,096 tokens whatever its bucket, and a warm start traces every prefill program again (the
# body is a hundred jax.numpy calls: 0.16 s a program where it was traced once a kind of layer and program).
@functools.partial(jax.jit, static_argnames=("size",))
def _summed_by_level(rows, weights, row_of_pair, held, *, size: int):
    """``_summed_by_token`` with gathers of the held pairs only and no scatter
    (a scatter-add of a float32 row of 2304 takes a v5e 244 ns, a gather 43):
    a token's held pairs are numbered 0, 1, .. (levels), the tokens are put in
    the order of how many they hold, most first, so that level j is the first
    n_j tokens of that order, gathered in chunks of ``size`` tokens and added
    to a prefix of the sum, which is read back into the tokens' own order at
    the end. The trip count is the levels' chunks, a runtime value: the rows
    moved are the held pairs, a chunk's rounding a level, and T."""
    (T, K), D = held.shape, rows.shape[1]
    holds = held.astype(jnp.int32)
    count = jnp.sum(holds, axis=1)  # [T]
    levels = jnp.arange(K, dtype=jnp.int32)
    # [T, pair, level]: the pair is its token's level-th held one
    onto = ((jnp.cumsum(holds, axis=1) - holds)[:, :, None] == levels) & held[:, :, None]
    level_tokens = jnp.sum((count[:, None] > levels).astype(jnp.int32), axis=0)  # [K]: n_j
    # a token's place in the order by count, ties in the tokens' order: a counting sort
    same = (count[:, None] == jnp.arange(K + 1, dtype=jnp.int32)).astype(jnp.int32)  # [T, K + 1]
    place = (jnp.concatenate([level_tokens, jnp.zeros(1, jnp.int32)])[count]  # the tokens that hold more
             + jnp.sum((jnp.cumsum(same, axis=0) - same) * same, axis=1))  # the earlier tokens that hold as many

    def by_level(of_pair):  # [T, K] of the pairs -> [K, T]: level j of the token at each place
        of_level = jnp.sum(jnp.where(onto, of_pair[:, :, None], 0), axis=1)
        return jnp.zeros_like(of_level).at[place].set(of_level, unique_indices=True).T

    level_rows = by_level(row_of_pair)
    level_weights = None if weights is None else by_level(weights)
    chunks = lax.div(level_tokens + (size - 1), size)
    ends = jnp.cumsum(chunks)

    def body(step, acc):
        level = jnp.sum((step >= ends).astype(jnp.int32))
        first = (step - (ends[level] - chunks[level])) * size
        start = jnp.minimum(first, T - size)  # a level's last chunk starts early, and adds no token twice
        at = start + jnp.arange(size, dtype=jnp.int32)
        mine = (at >= first) & (at < level_tokens[level])
        part = rows[jnp.where(mine, lax.dynamic_slice(level_rows, (level, start), (1, size))[0], 0)].astype(jnp.float32)
        if level_weights is not None:
            part = part * lax.dynamic_slice(level_weights, (level, start), (1, size))[0][:, None]
        part = lax.dynamic_slice(acc, (start, 0), (size, D)) + jnp.where(mine[:, None], part, 0.0)
        return lax.dynamic_update_slice(acc, part, (start, 0))

    return lax.fori_loop(0, ends[-1], body, jnp.zeros((T, D), jnp.float32))[place]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _token_rows(xt, plan, chunk: int):
    """The rows laid out for the grouped matmul, [M, D]: row r holds
    xt[plan.token_of_row[r]] (a padding row token 0's, which nothing reads
    back), one gather of every row of the plan: out of 4,096 tokens it runs at
    the speed the rows are written (0.30 ms for 36,864 of 2304 on a v5e; a
    loop over the live chunks reads 0.67). The backward sums a token's held
    pairs' rows' cotangents in float32 (``_summed_by_token``), where autodiff
    would scatter-add every row of the plan."""
    return xt[plan.token_of_row]


def _token_rows_fwd(xt, plan, chunk):
    return _token_rows(xt, plan, chunk), plan


def _token_rows_bwd(chunk, plan, g):
    with jax.named_scope("experts/layout"):
        return _summed_by_token(g, None, plan, chunk).astype(g.dtype), None


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _row_tokens(y, top_w, plan, tm: int, chunk: int):
    """The pairs' results brought back to their tokens, [T, D] in y's dtype:
    token t's held pairs' rows of y [M, D], each times its weight (``top_w``
    [T, K] float32), summed in float32 (``_summed_by_token``). The backward
    goes by row: a row's cotangent is its token's times its weight, a weight's
    the dot product of its row and its token's cotangent in float32. With a
    ``chunk`` (``_layout_chunk``) over the live rows only, ``plan.n_tiles``
    tiles of ``tm``, in chunks of that many rows, the last of which may reach
    into dead tiles (a plan that is no whole number of chunks has its last
    chunk start early, at M - chunk, and writes rows again what it wrote):
    the rows of dead tiles past it get nothing (``lax.empty``: a TPU's buffer
    as it was allocated, zeros elsewhere), as the kernels' own results hold
    nothing there, and the grouped matmul's backward, a walk of the live
    tiles, never reads them. Without one, once over every row of the plan."""
    return _summed_by_token(y, top_w, plan, chunk).astype(y.dtype)


def _row_tokens_fwd(y, top_w, plan, tm, chunk):
    return _row_tokens(y, top_w, plan, tm, chunk), (y, top_w, plan)


def _row_tokens_bwd(tm, chunk, res, g):
    y, top_w, plan = res
    M, D = y.shape
    weights = top_w.reshape(-1)

    def rows(start, size):
        pair = lax.dynamic_slice(plan.pair_of_row, (start,), (size,))
        theirs = g[lax.dynamic_slice(plan.token_of_row, (start,), (size,))].astype(jnp.float32)  # its token's cotangent
        weight = jnp.where(pair >= 0, weights[jnp.maximum(pair, 0)], 0.0)
        mine = lax.dynamic_slice(y, (start, 0), (size, D)).astype(jnp.float32)
        return (theirs * weight[:, None]).astype(y.dtype), jnp.sum(mine * theirs, axis=1)

    def body(i, carry):
        start = jnp.minimum(i * chunk, M - chunk)
        dy, dots = rows(start, chunk)
        return lax.dynamic_update_slice(carry[0], dy, (start, 0)), lax.dynamic_update_slice(carry[1], dots, (start,))

    with jax.named_scope("experts/layout"):
        if chunk:
            dy, dots = lax.fori_loop(0, lax.div(plan.n_tiles[0] * tm + (chunk - 1), chunk), body,
                                     (lax.empty((M, D), y.dtype), jnp.zeros(M, jnp.float32)))
        else:
            dy, dots = rows(0, M)
        return dy, jnp.where(plan.held, dots[plan.row_of_pair], 0.0).astype(top_w.dtype), None


_row_tokens.defvjp(_row_tokens_fwd, _row_tokens_bwd)


def _balance_term(chosen, score_sum, tokens: int, cfg: TransformerConfig):
    """n_experts x sum_i f_i P_i over ALL n_experts: f_i the share of the
    tokens' (token, choice) pairs that chose expert i, every one of the
    expert_top_k choices counted (``chosen`` [n_experts], the pairs on each),
    P_i the mean over the tokens of the router's normalised score of i
    (``score_sum`` [n_experts], its sum over them): 1 where the routing is
    uniform. The gradient is P's: a count has none. Every chip of an
    expert-parallel deployment computes it whole for its own tokens, since
    the router is whole everywhere."""
    f = chosen / (tokens * cfg.expert_top_k)
    return cfg.n_experts * jnp.sum(f * score_sum / tokens)


def _held_experts_ffn(x, p, cfg: TransformerConfig, balance: bool = False):
    """``_held_experts_pass`` over x [B, S, D], a prompt of many tokens in
    passes of at most PAIRS_A_PASS pairs: the rows laid out for the grouped
    matmul hold every pair a pass could send here (all of them), so one pass
    over 8,192 tokens of 10 choices would take 90,112 rows, 0.55 GB a copy of
    them and 1.0 GB for the weighted pairs in float32 (3.9 GB of temporaries
    in that prefill program, as compiled for a v5e). The tokens are padded
    with zero rows to whole passes, whose results are dropped; the counts are
    summed over the passes (padding's pairs among them: counts are read of
    decode steps, which are one pass).
    ``balance`` (a layer that trains): the second result is float32 [3], the
    balance term over all experts and all of the B x S tokens (the passes'
    sums joined before the product, padding left out), then the two counts."""
    B, S, D = x.shape
    T = B * S
    size = 1 << ((PAIRS_A_PASS // cfg.expert_top_k).bit_length() - 1)  # tokens a pass, a power of two
    a_pass = _held_experts_pass
    if balance:
        if p["w_gate"].ndim == 4:
            # Float32 stacks against bfloat16 rows: THIS layer's experts cast once a layer pass, [1, E, ...] handed
            # to the passes as a stack of one (0.20 GB of bfloat16 at 16 experts of 2304 x 896, there while the layer
            # runs; 0.6 GB of traffic a layer pass, forward and made again at 4 layers 4.8 GB a step: 6 ms of a
            # v5e's bandwidth). The grouped matmul then streams 2 bytes a parameter each of the dozen times a step a
            # tile reads its expert, and the passes' loop sums a gradient of one layer's shape, which autodiff
            # widens and lays into the stack's once. The two other places for the cast: float32 blocks cast in VMEM
            # keep no copy and double every one of those reads (23 ms a step at this size); one copy of all the
            # held stacks a step (0.79 GB, 3 ms) makes each layer's loop over passes carry a gradient of the whole
            # stack's shape: that step compiled to 17.06 GB for a v5e beside two rows, this one to 14.28.
            layer = {k: lax.dynamic_index_in_dim(p[k], p["expert_layer"], 0, keepdims=True).astype(x.dtype)
                     for k in HELD_EXPERT_WEIGHTS}
            p = {**p, **layer, "expert_layer": 0}
        # What a pass keeps for its backward is its tokens, the rows laid out for the grouped matmul and the three
        # products' rows (EXPERT_ROWS: 0.47 GB a pass of 4,096 tokens at widths 2304 / 896: the down product's
        # 36,864 rows, which the way back to the tokens reads again for the weights' cotangents, where PR 59 kept
        # the 32,768 pairs read back); the routing, the row plan, the hidden rows and the sums by token are made
        # again from them, and no product is. One gather of 32,768 or 36,864 rows of 2304 OUT OF as many rows (151-170
        # MB read and as much written, the source in HBM) is 1.42 ms on a v5e, 43 ns a row, 5.7 ms a layer's four
        # passes; the same rows out of a pass's 4,096 tokens (19 MB) 0.30-0.36 ms (PERF.md section 6, PR 60).
        a_pass = jax.checkpoint(_held_experts_pass, static_argnums=(2, 3),
                                policy=jax.checkpoint_policies.save_only_these_names(EXPERT_ROWS))
    if T <= size:
        out, counts, *stats = a_pass(x, p, cfg, balance)
    else:
        n = -(-T // size)
        xt = jnp.pad(x.reshape(T, D), ((0, n * size - T), (0, 0))).reshape(n, 1, size, D)
        if balance:
            real = (jnp.arange(n * size) < T).astype(jnp.float32).reshape(n, size)
            out, counts, *stats = lax.map(lambda chunk: a_pass(chunk[0], p, cfg, True, chunk[1]), (xt, real))
        else:
            out, counts, *stats = lax.map(lambda chunk: a_pass(chunk, p, cfg), xt)
        out = out.reshape(n * size, D)[:T].reshape(B, S, D)
        counts, stats = jnp.sum(counts, axis=0), [jnp.sum(s, axis=0) for s in stats]
    if not balance:
        return out, counts
    return out, jnp.stack([_balance_term(*stats, T, cfg), *counts.astype(jnp.float32)])


def _held_experts_pass(x, p, cfg: TransformerConfig, balance: bool = False, real=None):
    """A routed FFN as the chip that holds experts first_expert ..
    first_expert + experts_held - 1 serves it. Every token is scored over all
    n_experts (router logits in float32) and takes its expert_top_k best (by
    score plus ``router_bias`` where the model has one, which the weights
    leave out), weights normalised over all of them and scaled; the pairs that landed on
    experts held here are sorted by expert and multiplied by a grouped matmul
    (ops/grouped_matmul.py), none dropped whatever the imbalance; what the
    absent experts would have added is left out. Beside it the shared expert,
    which every token passes.
    Returns (out [B,S,D], int32 [2]: the pairs on held experts, and the live
    tiles of the grouped matmul, each of which reads its expert's matrices).
    ``balance``: two more, float32 [n_experts] each, for ``_balance_term``:
    the pairs that chose each of ALL the experts, and the tokens' normalised
    scores summed, over the tokens ``real`` [B x S] weighs 1 (None: all).
    Differentiable: through the normalised weights to the router, through the
    two gathers to x, through the grouped matmuls to the experts; the row
    plan is whole numbers and has no cotangent."""
    from jax.ad_checkpoint import checkpoint_name

    from ray_tpu.ops.grouped_matmul import expert_matmul, group_rows

    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("experts/route"):
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), p["router"].astype(jnp.float32),
                            precision=lax.Precision.HIGHEST)
        score = jax.nn.sigmoid(logits) if cfg.router_score == "sigmoid" else jax.nn.softmax(logits, axis=-1)
        if cfg.router_bias:  # chosen by score + bias, weighed by the score alone
            _, top_e = lax.top_k(score + p["router_bias"].astype(jnp.float32), cfg.expert_top_k)
            top_s = jnp.take_along_axis(score, top_e, axis=-1)
            top_w = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-6) * cfg.routed_scaling
        else:
            top_s, top_e = lax.top_k(score, cfg.expert_top_k)  # [T, K]
            top_w = top_s / jnp.sum(top_s, axis=-1, keepdims=True) * cfg.routed_scaling
        tm = _expert_tile(B * S, cfg)
        plan = group_rows(top_e, cfg.first_expert, cfg.experts_held, tm)
    # a pass that trains names what its remat policy keeps (_held_experts_ffn): the rows laid out, the gate's and
    # the up product's rows, the down product's rows (which the way back to the tokens reads again for the
    # weights' cotangents)
    kept = (lambda a: checkpoint_name(a, EXPERT_ROWS)) if balance else (lambda a: a)
    chunk = _layout_chunk(plan.pair_of_row.shape[0], cfg)
    with jax.named_scope("experts/layout"):
        xs = kept(_token_rows(xt, plan, chunk))  # [M, D]
    with jax.named_scope("experts/gmm"):
        # One layer's matrices [E, ...] (layer 0 of a stack of one: a free
        # reshape), or the whole stack with this layer's index (scan_stack).
        stacked = p["w_gate"].ndim == 4
        product = functools.partial(
            expert_matmul(),
            layer=p["expert_layer"] if stacked else 0,
            tile_expert=plan.tile_expert, n_tiles=plan.n_tiles, tm=tm)
        gmm = lambda rows, w: kept(product(rows, w))  # noqa: E731
        w_gate, w_up, w_down = (p[k] if stacked else p[k][None] for k in HELD_EXPERT_WEIGHTS)
        h = _swiglu_product(lambda w: gmm(xs, w), w_gate, w_up, cfg.swiglu_limit)
        y = gmm(h, w_down)  # [M, D]; rows past the live tiles hold nothing
    with jax.named_scope("experts/layout"):
        routed = _row_tokens(y, top_w, plan, tm, chunk).reshape(B, S, D)
    with jax.named_scope("experts/shared"):
        if cfg.n_shared_experts:
            shared = {"w_gate": p["ws_gate"], "w_up": p["ws_up"], "w_down": p["ws_down"]}
            routed = routed + _dense_ffn(x, shared, cfg.swiglu_limit)
    counts = jnp.stack([jnp.sum(plan.sizes), plan.n_tiles[0]]).astype(jnp.int32)
    if not balance:
        return routed, counts
    with jax.named_scope("experts/balance"):
        real = jnp.ones(B * S, jnp.float32) if real is None else real
        share = score / jnp.sum(score, axis=-1, keepdims=True) if cfg.router_score == "sigmoid" else score
        chosen = jnp.sum(jax.nn.one_hot(top_e, cfg.n_experts, dtype=jnp.float32) * real[:, None, None], axis=(0, 1))
    return routed, counts, chosen, jnp.sum(share * real[:, None], axis=0)


def _delta_mixer(h, lp, cfg: TransformerConfig, kind: LayerKind, attend):
    """A delta layer's mixer on its normed input h [B, S, D]: projections to
    q~, k~ [B, S, Hk, Hd] and v~ [B, S, H, Hd] (Hk = H unless the kind has
    fewer key heads), the short convolution and SiLU on each, q and k normed to
    length 1 a head (q times Hd^-1/2 besides) and, with fewer key heads, each
    repeated over the H / Hk value heads it serves, the decay g =
    -exp(a_log) softplus(f(h) + dt_bias) (a channel through the low-rank f; a
    head where the kind has no low rank, the same in a head's every channel)
    and the step size beta = beta_scale sigmoid(h wb) in float32, the rule,
    then a head's output RMS-normed and times gate_scale sigmoid(z(h)), z
    low-rank or whole as f. Returns (o [B, S, H, Hd] before the output
    projection, kept).

    ``attend`` is the program's side, a pair (tail, rule). tail: the T - 1
    inputs of the convolution before position 0, [B, T - 1, 3, H, Hd] (None:
    zeros, a sequence's start). rule(q, k, v, g, beta, window) -> (o
    [B, S, H, Hd], kept), taken as rule((q, k, v, g, beta), window): the delta
    rule over these positions on whatever
    state the program keeps (ops/linear_attention.py); window
    [B, T - 1 + S, 3, H, Hd] is the tail and the convolution's inputs behind
    it, of which a program keeps its next tail. With fewer key heads q~, k~
    and v~ lie along ONE axis of 2 Hk + H heads, in tail, taps and window
    alike ([B, T - 1, 2 Hk + H, Hd])."""
    from ray_tpu.ops.linear_attention import delta_prep, delta_prep_reference

    dt, T = h.dtype, kind.conv_size
    S, Hd = h.shape[1], cfg.head_dim
    H, Hk = kind.n_heads, kind.key_heads
    tail, rule = attend
    with jax.named_scope("qkv"):
        u = [jnp.einsum("bsd,dhk->bshk", h, lp[w].astype(dt)) for w in ("wq", "wk", "wv")]
        if Hk == H:
            u = wlc(jnp.stack(u, axis=2), ("batch", "seq", None, "heads", "head_dim"))
        else:
            u = wlc(jnp.concatenate(u, axis=2), ("batch", "seq", "heads", "head_dim"))
    with jax.named_scope("short_conv"):
        if tail is None:
            tail = jnp.zeros((u.shape[0], T - 1, *u.shape[2:]), dt)
        window = jnp.concatenate([tail.astype(dt), u], axis=1)
        # one pass over a prompt where there is a kernel to make it (ops/linear_attention.py); a decode step's one
        # position, every other backend and the "reference" implementation take the jax.numpy lines
        q, k, v = (delta_prep if S > 1 and _runs_kernels(cfg) else delta_prep_reference)(window, lp["conv"], Hk)
        if Hk != H:  # key head j serves value heads j H / Hk .. (j + 1) H / Hk - 1
            q, k = jnp.repeat(q, H // Hk, axis=2), jnp.repeat(k, H // Hk, axis=2)
    with jax.named_scope("decay"):
        if kind.low_rank:
            f = jnp.einsum("bsr,rhk->bshk", jnp.einsum("bsd,dr->bsr", h, lp["wf_a"].astype(dt)), lp["wf_b"].astype(dt))
            g = -jnp.exp(lp["a_log"].astype(jnp.float32))[:, None] * jax.nn.softplus(
                f.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
        else:
            f = jnp.einsum("bsd,dh->bsh", h, lp["wa"].astype(dt))
            g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * jax.nn.softplus(
                f.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
            # the rule takes a decay a channel (ops/linear_attention.py): a head's, in each of its key channels
            g = jnp.broadcast_to(g[..., None], (*g.shape, Hd))
        beta = kind.beta_scale * jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, lp["wb"].astype(dt)).astype(jnp.float32))
    o, kept = rule((q, k, v, g, beta), window)
    with jax.named_scope("out_gate"):
        if kind.low_rank:
            gate = jnp.einsum("bsr,rhk->bshk", jnp.einsum("bsd,dr->bsr", h, lp["wg_a"].astype(dt)), lp["wg_b"].astype(dt))
        else:
            gate = jnp.einsum("bsd,dhk->bshk", h, lp["wz"].astype(dt))
        o = _rms_norm(o.astype(dt), lp["o_norm"], cfg.norm_eps)
        gate = jax.nn.sigmoid(gate.astype(jnp.float32))
        if kind.gate_scale != 1.0:
            gate = kind.gate_scale * gate
        o = o * gate.astype(dt)
    return o, kept


def _ssd_mixer(h, lp, cfg: TransformerConfig, kind: LayerKind, attend):
    """An ssd layer's mixer on its normed input h [B, S, D]: one projection to
    [z | x B C | dt], the short convolution with its bias and SiLU over x, B
    and C together, dt = softplus(dt + dt_bias) and the log decay g =
    -exp(a_log) dt a head in float32, the rule, the skip d_skip x, then the
    gate silu(z) and an RMS norm over all the heads' columns (the gate first).
    Returns (o [B, S, H, P] before the output projection, kept).

    ``attend`` is the program's side, a pair (tail, rule), as for a delta
    layer. tail: the T - 1 inputs of the convolution before position 0,
    [B, T - 1, channels] or end to end as the engine's pool keeps them
    [B, (T - 1) x channels] (None: zeros). rule((x, B, C, g, dt), window) -> (y
    [B, S, H, P], kept): ops/ssd.py over these positions on whatever state the
    program keeps; window [B, T - 1 + S, channels] is the tail and the
    convolution's inputs behind it."""
    dt_, T = h.dtype, kind.conv_size
    B_, S = h.shape[:2]
    H, P, G, N = kind.n_heads, kind.head_width, kind.n_groups, kind.state_size
    I = H * P
    tail, rule = attend
    with jax.named_scope("in_proj"):
        zxd = jnp.einsum("bsd,cd->bsc", h, lp["w_in"].astype(dt_))
        z, u, dt = zxd[..., :I], zxd[..., I:-H], zxd[..., -H:]
    with jax.named_scope("short_conv"):
        if tail is None:
            tail = jnp.zeros((B_, T - 1, u.shape[-1]), dt_)
        window = jnp.concatenate([tail.astype(dt_).reshape(B_, T - 1, u.shape[-1]), u], axis=1)
        taps = lp["conv"].astype(jnp.float32)
        y = sum(window[:, j:j + S].astype(jnp.float32) * taps[j] for j in range(T)) + lp["conv_bias"].astype(jnp.float32)
        y = jax.nn.silu(y).astype(dt_)
        x = y[..., :I].reshape(B_, S, H, P)
        Bm = y[..., I:I + G * N].reshape(B_, S, G, N)
        Cm = y[..., I + G * N:].reshape(B_, S, G, N)
    with jax.named_scope("decay"):
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"].astype(jnp.float32))
        g = -jnp.exp(lp["a_log"].astype(jnp.float32)) * dt
    o, kept = rule((x, Bm, Cm, g, dt), window)
    with jax.named_scope("out_gate"):
        o = o.astype(jnp.float32) + lp["d_skip"].astype(jnp.float32)[:, None] * x.astype(jnp.float32)
        o = (o.reshape(B_, S, I) * jax.nn.silu(z.astype(jnp.float32))).astype(dt_)
        o = _rms_norm(o, lp["o_norm"], cfg.norm_eps).reshape(B_, S, H, P)
    return o, kept


def _conv_mixer(h, lp, cfg: TransformerConfig, kind: LayerKind, attend):
    """A conv layer's mixer on its normed input h [B, S, D]: one projection to
    [B | C | u], z = B u, the causal depthwise convolution of T taps over z in
    float32 (no bias, no activation), the gate C on its output. Returns (y
    [B, S, D] before the output projection, kept).

    ``attend`` is the program's side, a pair (tail, keep), as for the other
    recurrent kinds but with no rule to run. tail: the T - 1 rows of z before
    position 0, [B, T - 1, D] or end to end as the engine's pool keeps them
    [B, (T - 1) x D] (None: zeros, a sequence's start). keep(window) -> kept:
    window [B, T - 1 + S, D] is the tail and these positions' z behind it, of
    which a program keeps its next tail."""
    dt, T = h.dtype, kind.conv_size
    B_, S, D = h.shape
    tail, keep = attend
    with jax.named_scope("in_proj"):
        bcu = jnp.einsum("bsd,dc->bsc", h, lp["w_in"].astype(dt))
        b, c, u = bcu[..., :D], bcu[..., D:2 * D], bcu[..., 2 * D:]
    with jax.named_scope("short_conv"):
        if tail is None:
            tail = jnp.zeros((B_, T - 1, D), dt)
        window = jnp.concatenate([tail.astype(dt).reshape(B_, T - 1, D), b * u], axis=1)
        taps = lp["conv"].astype(jnp.float32)
        y = sum(window[:, j:j + S].astype(jnp.float32) * taps[j] for j in range(T))
    with jax.named_scope("out_gate"):
        y = (c.astype(jnp.float32) * y).astype(dt)
    return y, keep(window)


def decoder_block(x, lp, cfg: TransformerConfig, positions, attend, kind: LayerKind | None = None,
                  balance: bool = False):
    """The one decoder block that training, prefill and decode all run: what
    the model is (norms, projections, rope, the attention's and the FFN's
    kind) lives here, what a program does with what a layer caches is its
    ``attend``.

    x: [B, S, D] in cfg.dtype; lp: one layer's parameters (its FFN is routed
    if they hold a router, dense otherwise); positions: [B, S]; kind: the
    layer's LayerKind (None: the model's one kind), which says how its "gqa"
    heads (or a "latent" kind's roped columns) are roped; its window is the
    ``attend``'s to keep.
    attend(q, k, v) -> (o [B,S,H,v width], kept). "gqa": q [B,S,H,Hd],
    k and v [B,S,KV,Hd], q and k roped, grouped K/V as they are (native GQA).
    "latent" (the kind's mixer): q = (q_nope [B,S,H,nope], q_rope [B,S,H,rope]), k = the normed
    latent c [B,S,R], v = the roped shared key k_rope [B,S,rope], which is
    what such a layer caches; the attention side expands them over a prompt
    (``latent_expand``) or absorbs the projections in decode. ``kept`` is
    whatever the attention side wants handed out of the layer (a prompt's
    rows, the carried pools, None). A recurrent kind's ``attend`` is a pair
    (``_delta_mixer``, ``_ssd_mixer`` and ``_conv_mixer`` say of what).
    Returns (x, aux, kept): aux is the MoE balance term of a training layer, a zero for a dense one, and the
    [pairs, live tiles] counts of a layer that serves held experts; with ``balance`` (a layer that trains) a
    layer of held experts hands out float32 [balance term, pairs, live tiles] (``_held_experts_ffn``)."""
    dt = x.dtype
    kind = kind or cfg.kinds[0]
    norm = functools.partial(model_norm, cfg=cfg)

    def joined(x, out):
        """x + the sublayer's output, times the model's multiplier."""
        return x + (out * cfg.residual_multiplier if cfg.residual_multiplier != 1.0 else out)

    if kind.recurrent:
        mixer = {"ssd": _ssd_mixer, "delta": _delta_mixer, "conv": _conv_mixer}[kind.mixer]
        o, kept = mixer(norm(x, lp["attn_norm"]), lp, cfg, kind, attend)
    elif kind.mixer == "latent":
        h = norm(x, lp["attn_norm"])
        q, k, v = _latent_qkv(h, lp, cfg, positions, kind)
    else:
        with jax.named_scope("qkv"):
            h = norm(x, lp["attn_norm"])
            q = jnp.einsum("bsd,dhk->bshk", h, lp["wq"].astype(dt))
            k = jnp.einsum("bsd,dhk->bshk", h, lp["wk"].astype(dt))
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"].astype(dt))
            q = wlc(q, ("batch", "seq", "heads", "head_dim"))
            k = wlc(k, ("batch", "seq", "kv_heads", "head_dim"))
            if cfg.qk_norm:
                with jax.named_scope("qk_norm"):
                    q, k = norm(q, lp["q_norm"]), norm(k, lp["k_norm"])
            q = _rope_kind(q, positions, kind)
            k = _rope_kind(k, positions, kind)
            if cfg.attention_multiplier:
                # every attention here scales by head_dim^-1/2: the rest of the model's own scale rides on q
                # (1/8 for a multiplier of 1/64 on heads of 64: a power of two, exact in any dtype)
                q = q * jnp.asarray(cfg.attention_multiplier * math.sqrt(cfg.head_dim), q.dtype)
    if not kind.recurrent:
        o, kept = attend(q, k, v)
    if cfg.attn_gate and not kind.recurrent:
        with jax.named_scope("attn_gate"):
            if cfg.attn_gate == "elementwise":
                gate = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", h, lp["wg"].astype(dt)).astype(jnp.float32))
                o = o * gate.astype(dt)
            else:
                gate = jax.nn.sigmoid(jnp.einsum("bsd,dh->bsh", h, lp["wg"].astype(dt)).astype(jnp.float32))
                o = o * gate[..., None].astype(dt)
    with jax.named_scope("attn_out"):
        if kind.mixer == "conv":  # no heads: the gated columns through one [D, D] projection
            a = jnp.einsum("bsc,cd->bsd", o, lp["w_out"].astype(dt))
        else:
            o = wlc(o, ("batch", "seq", "heads", "head_dim"))
            a = jnp.einsum("bshk,hkd->bsd", o, lp["wo"].astype(dt))
        if cfg.sandwich_norm:
            a = norm(a, lp["post_attn_norm"])
        x = joined(x, a)
    with jax.named_scope("ffn"):
        h = norm(x, lp["ffn_norm"])
        if "router" not in lp:
            ffn_out, aux = _dense_ffn(h, lp, cfg.swiglu_limit), jnp.zeros((), jnp.float32)
        elif cfg.experts_held:
            ffn_out, aux = _held_experts_ffn(h, lp, cfg, balance)
        else:
            ffn_out, aux = _moe_ffn(h, lp, cfg)
        if cfg.sandwich_norm:
            ffn_out = norm(ffn_out, lp["post_ffn_norm"])
        x = joined(x, ffn_out)
    x = wlc(x, ("batch", "seq", "embed"))
    return x, aux, kept


def _runs_kernels(cfg: TransformerConfig) -> bool:
    """Whether a recurrent layer's Pallas kernels run: the configured
    implementation is a kernel's and the backend a TPU's."""
    return cfg.attention_impl in ("auto", "flash") and jax.default_backend() == "tpu"


def _whole_sequence_rule(ops, _window, cfg: TransformerConfig, kind: LayerKind):
    """A recurrent layer's rule over whole sequences from an empty state,
    nothing kept: the chunked kernel where the configured implementation is a
    kernel's and the backend a TPU's (forward only: the kernels have no
    backward pass), its ``jax.numpy`` form for "reference" and everywhere else."""
    if kind.mixer == "ssd":
        from ray_tpu.ops.ssd import ssd_chunk as chunk, ssd_chunk_reference as chunk_reference
    else:
        from ray_tpu.ops.linear_attention import kda_chunk as chunk, kda_chunk_reference as chunk_reference

    return (chunk if _runs_kernels(cfg) else chunk_reference)(*ops, out_dtype=cfg.dtype)[0], None


def _layer(x, lp, cfg: TransformerConfig, positions, segment_ids=None, kind: LayerKind | None = None,
           stats: bool = False):
    """The block over whole sequences: attention over the layer's own K/V by
    the configured implementation (inside the kind's window where it has
    one), nothing kept. x: [B, S, D] in cfg.dtype. Returns (x, the layer's
    balance term), or with ``stats``, as the training loss asks, (x, float32
    [balance term, pairs on held experts, live tiles])."""
    kind = kind or cfg.kinds[0]

    def attend(q, k, v):
        if kind.mixer != "latent":
            return _attention(q, k, v, cfg, positions, segment_ids, window=kind.window), None
        k, v = latent_expand(lp, k, v, x.dtype)
        q = jnp.concatenate(q, axis=-1)
        return _attention(q, k, v, cfg, positions, segment_ids, scale=latent_scale(cfg, kind)), None

    if kind.recurrent:
        if segment_ids is not None:
            raise NotImplementedError(
                f"packed sequences are not written for a {kind.mixer} layer: "
                f"{'its state and its convolution' if kind.state else 'its convolution'} would "
                "have to start again at each document's first position (ROADMAP M4)")
        attend = (None, functools.partial(_whole_sequence_rule, cfg=cfg, kind=kind) if kind.state
                  else lambda _window: None)
    x, aux, _ = decoder_block(x, lp, cfg, positions, attend, kind, balance=stats)
    if stats:  # a layer of held experts hands out all three; a layer of _moe_ffn or a dense one its term and no counts
        return x, (aux if aux.ndim else jnp.pad(aux[None], (0, 2)))
    # Outside the training loss a layer of held experts hands out counts, not a loss term.
    return x, (jnp.zeros((), jnp.float32) if cfg.experts_held else aux)


def _hidden_and_stats(params: dict, tokens: jax.Array, cfg: TransformerConfig, segment_ids=None, positions=None,
                      stats: bool = True):
    """tokens [B, S] int32 -> (final-norm hidden states [B, S, D], float32 [3]:
    the routed layers' balance terms, pairs on held experts and live tiles,
    each summed over the layers; without ``stats`` the balance terms of the
    layers of _moe_ffn alone, a scalar: ``forward_hidden``)."""
    B, S = tokens.shape
    x = embed_tokens(params, tokens, cfg)
    x = wlc(x, ("batch", "seq", "embed"))
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))

    def body_of(kind):
        body = functools.partial(_layer, cfg=cfg, positions=positions, segment_ids=segment_ids, kind=kind, stats=stats)
        if not cfg.remat:
            return body
        if cfg.remat_policy == "dots":
            return jax.checkpoint(body, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        if cfg.remat_policy == "full":
            return jax.checkpoint(body)
        raise ValueError(f"unknown remat_policy {cfg.remat_policy!r} (full|dots)")

    x, auxes = run_layers(lambda h, lp, kind, _index: body_of(kind)(h, lp), x, params, cfg)
    aux = functools.reduce(lambda a, b: a + b, [jnp.sum(a, axis=0) if stats else jnp.sum(a) for a in auxes.values()])
    return model_norm(x, params["final_norm"], cfg), aux


def forward_hidden(params: dict, tokens: jax.Array, cfg: TransformerConfig,
                   segment_ids=None, positions=None):
    """tokens [B, S] int32 -> (final-norm hidden states [B, S, D], moe_aux).
    The shared trunk of forward() and the chunked-CE training loss."""
    return _hidden_and_stats(params, tokens, cfg, segment_ids, positions, stats=False)


def forward(params: dict, tokens: jax.Array, cfg: TransformerConfig,
            segment_ids=None, positions=None) -> jax.Array:
    """tokens [B, S] int32 -> logits [B, S, vocab].

    Packed sequences: pass ``segment_ids`` [B, S] (attention masked within
    segments) and per-segment-restarting ``positions`` [B, S] for RoPE.
    """
    x, aux = forward_hidden(params, tokens, cfg, segment_ids, positions)
    logits = hidden_logits(params, x, cfg)
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


# The loss head walks the sequence in chunks whose logits [B, chunk, V] stay
# under this many bytes, and in at most so many: the walk is unrolled, and a
# warm start reads, deserialises and loads every chunk's fusions. Chosen on the
# train cell, [3, 4096] tokens x 32768 in bfloat16 (805 MB) beside two layers'
# backward under remat "dots", TPU v5e, jax 0.9.0, 2026-10-03 (PERF.md section
# 6, PR 52): 4 chunks of 201 MB read 34,635 tokens/s, 8 read 34,785 on a step
# program of 242 fusions where 4 make 196 and the whole logits, which the
# compiler computed twice, 163 at 32,141. A ROLLED walk keeps the program at
# 160 fusions whatever the count, and is slower: lax.scan over chunk-major
# inputs 33,533 (4 chunks) and 33,701 (8), fori_loop over dynamic slices
# 33,196, 3.2-4.2% of the step under the unrolled form of the same chunks (not
# the 6x an older JAX measured of a rematted scan); the head's three products
# run as fast inside the loop, the rest of the step does not.
_LOSS_HEAD_CHUNK_BYTES = 192 << 20
_LOSS_HEAD_MAX_CHUNKS = 32


def _loss_head_chunk(B: int, S: int, V: int, itemsize: int, ce_chunk: int) -> int:
    """The chunk's length: ``ce_chunk`` where it is given, else S over the
    fewest chunks whose logits fit _LOSS_HEAD_CHUNK_BYTES (at most
    _LOSS_HEAD_MAX_CHUNKS), rounded up: the last chunk may be shorter."""
    if ce_chunk > 0:
        return min(ce_chunk, S)
    return -(-S // min(-(-B * S * V * itemsize // _LOSS_HEAD_CHUNK_BYTES), _LOSS_HEAD_MAX_CHUNKS))


def _head_walk(x, head, targets, mask, chunk: int, divisor: float, dims: str, grads: bool):
    """The mean NLL of targets [B, S] under logits x @ head / divisor, over the
    positions mask [B, S] keeps (None: all), one chunk of the sequence at a
    time: a chunk's logits [B, chunk, V] in x's dtype, its logsumexp and picked
    logit in float32, and nothing of [B, S, V] ever whole. The head lies as
    ``dims`` says, "dv" or a tied embedding's "vd". With ``grads`` also
    (d loss / d x [B, S, D] in x's dtype, d loss / d head as head lies and in
    its dtype), made while a chunk's logits are there: dlogits = (softmax -
    onehot) * mask / count rounded to x's dtype (what a bf16 dot_general's
    cotangent is), dx's chunk = dlogits @ head^T, dW += x_chunk^T @ dlogits in
    float32."""
    B, S, _ = x.shape
    w = head.astype(x.dtype)
    mask = jnp.ones((B, S), jnp.float32) if mask is None else mask.astype(jnp.float32)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    vocab = jnp.arange(head.shape[dims.index("v")], dtype=targets.dtype)
    tot = jnp.zeros((), jnp.float32)
    dW = jnp.zeros(head.shape, jnp.float32) if grads else None
    dx = []
    # The optimization_barrier chains each chunk's input on the chunk before:
    # without it XLA overlaps the chunks' matmul islands and every chunk's
    # logits are live at once, the exact thing chunking exists to prevent.
    for lo in range(0, S, chunk):
        at = slice(lo, lo + chunk)
        x_c = x[:, at]
        if lo:
            x_c, tot, dW = lax.optimization_barrier((x_c, tot, dW))
        logits = wlc(jnp.einsum(f"bcd,{dims}->bcv", x_c, w), ("batch", "seq", "vocab"))
        logits = (logits / divisor if divisor != 1.0 else logits).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        hit = targets[:, at, None] == vocab
        tot += jnp.sum((lse - jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)) * mask[:, at])
        if grads:
            dlogits = (jnp.exp(logits - lse[..., None]) - hit) * (mask[:, at] / (count * divisor))[..., None]
            dlogits = dlogits.astype(x.dtype)
            dx.append(jnp.einsum(f"bcv,{dims}->bcd", dlogits, w))
            dW += jnp.einsum(f"bcd,bcv->{dims}", x_c, dlogits, preferred_element_type=jnp.float32)
    loss = tot / count
    return (loss, jnp.concatenate(dx, axis=1), dW.astype(head.dtype)) if grads else loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _head_loss(x, head, targets, mask, chunk: int, divisor: float, dims: str):
    return _head_walk(x, head, targets, mask, chunk, divisor, dims, grads=False)


def _head_loss_fwd(x, head, targets, mask, chunk, divisor, dims):
    loss, dx, dW = _head_walk(x, head, targets, mask, chunk, divisor, dims, grads=True)
    return loss, (dx, dW)


def _head_loss_bwd(chunk, divisor, dims, grads, g):
    # targets are whole numbers and nothing is asked of the mask: no cotangent.
    return (*((d * g).astype(d.dtype) for d in grads), None, None)


_head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def head_loss(params: dict, x, targets, mask, cfg: TransformerConfig):
    """Final-normed hidden states x [B, S, D] -> the mean NLL of targets [B, S]
    over the positions mask keeps. Differentiated, the head makes its gradients
    where it makes its logits (three matmuls of the head's size a step, no
    second pass over the logits: _head_walk). The head comes in as the
    parameter it is and lies, so a tied embedding's two gradients are summed
    by autodiff and nothing is turned."""
    head, dims = (params["embed"], "vd") if cfg.tie_embeddings else (params["lm_head"], "dv")
    chunk = _loss_head_chunk(*targets.shape, cfg.vocab_size, jnp.dtype(x.dtype).itemsize, cfg.ce_chunk)
    with jax.named_scope("loss_head"):
        return _head_loss(x, head, targets, mask, chunk, float(cfg.logits_divisor), dims)


def loss_and_metrics(params, batch, cfg: TransformerConfig):
    """batch: {"tokens": [B, S+1] int32, optional "mask"/"segment_ids"/
    "positions"} -> (scalar mean NLL + router_aux_coef x the routed layers'
    balance terms, {"nll", "balance_loss" (the terms summed, before the
    coefficient), "expert_pairs", "expert_live_tiles" (held experts' counts
    summed over the routed layers; 0 without any)}). segment_ids enable
    packed-sequence training (attention + loss respect example boundaries)."""
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
    x, stats = _hidden_and_stats(
        params, inputs, cfg,
        segment_ids=None if segs is None else segs[:, :-1],
        positions=None if pos is None else pos[:, :-1],
    )
    nll = head_loss(params, x, targets, mask, cfg)
    return nll + cfg.router_aux_coef * stats[0], {
        "nll": nll, "balance_loss": stats[0], "expert_pairs": stats[1], "expert_live_tiles": stats[2]}


def cross_entropy_loss(params, batch, cfg: TransformerConfig):
    """``loss_and_metrics``' loss alone."""
    return loss_and_metrics(params, batch, cfg)[0]


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
        (loss, metrics), grads = jax.value_and_grad(loss_and_metrics, has_aux=True)(
            state["params"], batch, cfg
        )
        updates, opt = optimizer.update(grads, state["opt"], state["params"])
        params = optax.apply_updates(state["params"], updates)
        gnorm = optax.global_norm(grads)
        return (
            {"params": params, "opt": opt, "step": state["step"] + 1},
            {"loss": loss, **metrics, "grad_norm": gnorm, "step": state["step"] + 1},
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
    if cfg.n_dense_layers or cfg.layer_pattern:
        raise ValueError("make_pipeline_train_step stages one stack of identical layers "
                         "(n_dense_layers = 0, no layer_pattern: layers of two kinds are two stacks)")
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
        x = embed_tokens(params, inputs, cfg)
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
        h = model_norm(h, params["final_norm"], cfg)
        logits = hidden_logits(params, h, cfg)
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
