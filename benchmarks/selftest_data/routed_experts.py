"""A fixture of the self-test, not an architecture of the benchmark: the
counting half of the seam (harness/cellspec.py `architecture`) for a decoder
whose FFN is a set of routed experts, so that the seam has an implementor in
which the parameters a token multiplies and the parameters that lie in memory
differ. No reference, no mapping onto the program: no model is added here.
Keys as OLMoE's published config names them (intermediate_size is one
expert's width; q and k each carry an RMSNorm over the projected width)."""
from __future__ import annotations


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
