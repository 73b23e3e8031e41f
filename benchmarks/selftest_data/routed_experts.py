"""A fixture of the self-test and of the serve check's calibration, not an
architecture of the benchmark: a decoder whose FFN is a set of top-k routed
experts. No model, configuration or cell is added here.

The counting half (`attention_dims`, `param_counts`) gives the seam
(harness/cellspec.py `architecture`) an implementor in which the parameters a
token multiplies and the parameters that lie in memory differ. Keys as
OLMoE's published config names them (intermediate_size is one expert's width;
q and k each carry an RMSNorm over the projected width, which the counts
include).

The reference half (`logits`, `transformer_kwargs`, `shrink`, `routing`) is
what harness/refcheck.py's limits for a routed model were read on
(tests/control.py --config ../selftest_data/routed_experts_olmoe): the plain
float32 form of the program's `_moe_ffn` as it stands (every expert computed,
the best k of the softmax kept and renormalised), at OLMoE's widths. It is not
OLMoE (no QK-norm, and OLMoE does not renormalise); it has OLMoE's router,
which is what the check's statistic must bear."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from harness.cellspec import load_architecture


def attention_dims(model: dict) -> tuple:
    H = model["num_attention_heads"]
    return (model["num_hidden_layers"], H, model["num_key_value_heads"],
            model.get("head_dim") or model["hidden_size"] // H)


def param_counts(model: dict) -> dict:
    d, V, F = model["hidden_size"], model["vocab_size"], model["intermediate_size"]
    E, K = model["num_experts"], model["num_experts_per_tok"]
    L, H, KV, hd = attention_dims(model)
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    expert, router = 3 * d * F, d * E
    norms = 2 * d + H * hd + KV * hd  # attention and FFN norms, q_norm, k_norm
    head = 0 if model.get("tie_word_embeddings") else d * V
    return {"embedding": V * d, "lm_head": head,
            "per_layer_matmul": attn + K * expert + router,
            "matmul": L * (attn + K * expert + router) + d * V,
            "resident_matmul": L * (attn + E * expert + router) + d * V,
            "total": V * d + head + L * (attn + E * expert + router + norms) + d}


def routing(model: dict) -> int:
    """The top-k choices a token meets: one a layer (cellspec.routing)."""
    return model["num_hidden_layers"]


def transformer_kwargs(model: dict) -> dict:
    return dict(
        vocab_size=model["vocab_size"], d_model=model["hidden_size"], n_layers=model["num_hidden_layers"],
        n_heads=model["num_attention_heads"], n_kv_heads=model["num_key_value_heads"],
        d_ff=model["intermediate_size"], n_experts=model["num_experts"],
        expert_top_k=model["num_experts_per_tok"], max_seq_len=model["max_position_embeddings"],
        rope_theta=float(model["rope_theta"]), attention_impl="auto", param_dtype="bfloat16")


def shrink(model: dict) -> None:
    model.update(hidden_size=128, num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=4,
                 intermediate_size=64, num_experts=16, num_experts_per_tok=8, vocab_size=512,
                 max_position_embeddings=512)


def logits(params, tokens, model: dict, flip=None):
    """tokens [B,S] -> logits [B,S,V], float32, from the program's parameter
    tree (architectures/dense.py's, with router [L,D,E], w_gate / w_up
    [L,E,D,F] and w_down [L,E,F,D]). `flip` = (layer, position) plants what a
    near-tie does to a forward in lower precision: there the first expert
    dropped takes the place of the last one kept (tests/test_reference_check.py)."""
    eps, theta, K = float(model["rms_norm_eps"]), float(model["rope_theta"]), model["num_experts_per_tok"]
    dense = load_architecture("dense")  # its norm and rotary positions; the loop below is dense.logits's but for the FFN
    rms_norm, B, S = dense._rms_norm, *tokens.shape
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    allowed = jnp.tril(jnp.ones((S, S), bool))[None, None]
    x = params["embed"].astype(jnp.float32)[tokens]
    for i in range(model["num_hidden_layers"]):
        lp = {k: v[i].astype(jnp.float32) for k, v in params["layers"].items()}
        h = rms_norm(x, lp["attn_norm"], eps)
        q = dense._rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), positions, theta)
        k = dense._rotary(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), positions, theta)
        v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
        rep = q.shape[2] // k.shape[2]
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        s = jnp.einsum("bqhk,bthk->bhqt", q, k) / jnp.sqrt(jnp.float32(q.shape[-1]))
        o = jnp.einsum("bhqt,bthk->bqhk", jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1), v)
        x = x + jnp.einsum("bshk,hkd->bsd", o, lp["wo"])
        h = rms_norm(x, lp["ffn_norm"], eps)
        share, expert = jax.lax.top_k(jax.nn.softmax(h @ lp["router"], axis=-1), K + 1)  # [B,S,K+1]
        dropped = jnp.full((S,), K)  # of the K + 1 best, by position: the last, or with a flip the last but one
        if flip is not None and flip[0] == i:
            dropped = dropped.at[flip[1]].set(K - 1)
        share = jnp.where(jnp.arange(K + 1) == dropped[:, None], 0.0, share)
        share = share / share.sum(-1, keepdims=True)
        every = jnp.einsum("ebsf,efd->bsed", jax.nn.silu(jnp.einsum("bsd,edf->ebsf", h, lp["w_gate"]))
                           * jnp.einsum("bsd,edf->ebsf", h, lp["w_up"]), lp["w_down"])
        x = x + (jnp.take_along_axis(every, expert[..., None], axis=2) * share[..., None]).sum(2)
    x = rms_norm(x, params["final_norm"].astype(jnp.float32), eps)
    return x @ params["lm_head"].astype(jnp.float32)
