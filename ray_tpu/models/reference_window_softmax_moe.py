"""The plain reference of the decoder with window and full attention layers
and softmax-routed experts that models/transformer.py TRAINS (``layer_pattern``,
``experts_held``, ``router_aux_coef``): the published layer of
Mellum2-12B-A2.5B, written down once in float32 ``jax.numpy`` with no kernel,
no remat and no passes, a Python loop over layers and over experts, the whole
[S, S] score matrix masked; its loss is a plain function of the parameters and
its gradients are ``jax.grad`` of that. It imports nothing of transformer.py
and reads that module's parameter tree (``kind_layers`` {a kind's name as
``layer_types`` spells it: that kind's layers, stacked in order}) because the
weights under test are the only ones there are. The attention (masked scores
whole, the rope plain or with YaRN's frequencies and ``attention_factor`` on
cos and sin; this tree has no gate's weight, so no gate), the norm and SwiGLU
are reference_window_moe.py's. tests/test_window_softmax_moe_train.py holds the
program to it.

The layer (x is [T, D]; N an RMSNorm with a learned weight before each
sublayer; no bias; a layer's kind k = layer_types[l] gives its rope
(rope_parameters[k]) and, for a sliding layer, the window W):

    h = N(x); q = h Wq as [H, d]; kk = h Wk, v = h Wv as [KV, d]; rotate-half
      rope on the whole head
    a_h = softmax(q_h kk_g^T / sqrt(d) + mask) v_g, g = h // (H / KV); mask
      causal inside a document, and in a sliding layer i sees j with
      i - W < j <= i
    x = x + concat_h(a_h) Wo
    h2 = N(x); p = softmax(h2 Wr) over every expert, float32; the K largest;
      w_e = p_e / (sum of the K); x = x + sum_e w_e E_e(h2), E_e a SwiGLU

    loss = mean NLL of the next token inside documents
           + alpha x sum over layers of  n_experts x sum_i f_i P_i
      f_i: the share of the (token, choice) pairs that chose expert i, all K
      choices counted; P_i: the mean over tokens of p_i (the Mixtral /
      Qwen-MoE form: 1 a layer under uniform routing; the gradient is P's)

Departures from the published layer, all of them:

- `held` = (first, count) restricts the sum over chosen experts to ids
  first .. first + count - 1, as the chip that holds those computes it: the
  weights stay normalised over all K chosen, and the balance term stays over
  ALL experts (the router is whole on every chip). held=None sums every
  expert in the tree.
- Not in the published config, so assumed: no norm on q or kk, softmax before
  the top-k, alpha (`router_aux_loss_coef`, 0.001 where the file has none),
  the window's ends, attention_factor on cos and sin, rotate-half pairing.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.models.reference_window_moe import F32, _norm, _swiglu, attention

SLIDING = "sliding_attention"


def routed_ffn(x, lp, model: dict, held=None):
    """x [B,S,D] (already normed) -> (the routed layer's FFN output: the sum
    over the chosen experts among `held`, all in the tree when None, an
    expert at a time; the layer's balance term n_experts x sum_i f_i P_i)."""
    K = model["num_experts_per_tok"]
    logits = jnp.einsum("bsd,de->bse", x, lp["router"].astype(F32), precision="highest")
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(p, K)
    weight = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    first, count = held if held is not None else (0, lp["w_gate"].shape[0])
    out = jnp.zeros_like(x)
    for j in range(count):
        mine = jnp.sum(jnp.where(top_e == first + j, weight, 0.0), axis=-1)  # [B,S]: 0 unless chosen
        out = out + mine[..., None] * _swiglu(x, lp["w_gate"][j], lp["w_up"][j], lp["w_down"][j])
    E = p.shape[-1]
    f = jnp.mean(jax.nn.one_hot(top_e, E, dtype=F32), axis=(0, 1, 2))  # pairs on i / (tokens x K)
    return out, E * jnp.sum(f * jnp.mean(p, axis=(0, 1)))


def logits(params, tokens, model: dict, held=None, segment_ids=None, positions=None):
    """tokens [B,S] -> (logits [B,S,V] float32, the layers' balance terms
    summed). `model`: the published keys (rms_norm_eps, layer_types,
    sliding_window, rope_parameters, num_experts_per_tok); the depth is
    layer_types', the widths and head counts are the tree's."""
    eps = float(model["rms_norm_eps"])
    B, S = tokens.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.broadcast_to(jnp.tril(jnp.ones((S, S), bool))[None], (B, S, S))
    if segment_ids is not None:
        allowed = allowed & (segment_ids[:, :, None] == segment_ids[:, None, :])
    x = params["embed"].astype(F32)[tokens]
    seen, balance = {}, jnp.zeros((), F32)
    for kind in model["layer_types"]:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        lp = {k: v[i] for k, v in params["kind_layers"][kind].items()}
        window = model["sliding_window"] if kind == SLIDING else 0
        h = _norm(x, lp["attn_norm"], eps)
        x = x + attention(h, lp, model["rope_parameters"][kind], window, positions, allowed)
        out, term = routed_ffn(_norm(x, lp["ffn_norm"], eps), lp, model, held)
        x, balance = x + out, balance + term
    return _norm(x, params["final_norm"], eps) @ params["lm_head"].astype(F32), balance


def packed_loss(params, batch: dict, model: dict, held=None):
    """The training loss of a packed batch {"tokens", "segment_ids",
    "positions", "mask", each [B, S+1]}: the mean next-token NLL over the
    targets inside documents + alpha x the balance terms."""
    tok, seg = batch["tokens"], batch["segment_ids"]
    lg, balance = logits(params, tok[:, :-1], model, held, seg[:, :-1], batch["positions"][:, :-1])
    nll = -jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1), tok[:, 1:, None], axis=-1)[..., 0]
    w = ((seg[:, 1:] == seg[:, :-1]) & (batch["mask"][:, 1:] > 0)).astype(F32)
    return jnp.sum(nll * w) / jnp.sum(w) + float(model.get("router_aux_loss_coef", 0.001)) * balance
