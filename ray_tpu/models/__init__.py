"""ray_tpu.models: flagship model families, written mesh-first.

Models are pure-JAX pytrees with *logical axis* annotations
(ray_tpu.parallel.sharding): the same model code runs under any
ShardingStrategy (DP/FSDP/TP/SP/EP) — the strategy decides how each logical
axis maps onto the device mesh and XLA compiles in the collectives.

Beside transformer.py, one plain float32 reference a family of served layers
(no kernel, cache or batching; the tests hold the program to them):
reference_mla_moe.py (latent attention, a shared expert and held experts),
reference_window_moe.py (window and full attention layers, held experts),
reference_linear_moe.py (delta-rule layers beside gated NoPE attention, held
experts), reference_ssm_hybrid.py (Mamba-2 layers beside NoPE attention, a tied
head), reference_conv_moe.py (gated short-convolution layers beside QK-normed
roped attention, every expert held, a router's selection bias),
reference_latent_delta_moe.py (one gated latent-attention layer in four beside
gated-delta-rule layers with grouped key heads and a decay a head, gated norms
before and after every sublayer, a clamped SwiGLU, held experts).
"""
from ray_tpu.models.transformer import (
    Transformer,
    TransformerConfig,
    cross_entropy_loss,
    make_pipeline_train_step,
    make_train_step,
)

__all__ = [
    "Transformer",
    "TransformerConfig",
    "cross_entropy_loss",
    "make_pipeline_train_step",
    "make_train_step",
]
