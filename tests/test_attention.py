"""Flash-attention kernel exactness vs the jnp oracle, run in Pallas
interpret mode on CPU (the kernels themselves, not the fallback; real-TPU
execution is covered by chip_smoke.py and the benchmark's train cell). Covers MHA, native GQA (grouped KV heads,
no repeat), segment masking (packed sequences), and backward gradients."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import flash_attention, mha_reference

B, S, D = 2, 256, 64


def _qkv(key, H, KV):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)
    return q, k, v


def _segs():
    # Two segments per row, boundary at different positions per batch row.
    bounds = jnp.array([100, 160])
    pos = jnp.arange(S)[None, :]
    return (pos >= bounds[:, None]).astype(jnp.int32)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 2)])
def test_flash_forward_matches_reference(H, KV):
    q, k, v = _qkv(jax.random.PRNGKey(0), H, KV)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_flash_segment_mask_matches_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), 4, 2)
    segs = _segs()
    ref = mha_reference(q, k, v, causal=True, segment_ids=segs)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=segs, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_flash_segment_isolation():
    """Tokens after a segment boundary must be unaffected by tokens before it."""
    q, k, v = _qkv(jax.random.PRNGKey(2), 4, 4)
    segs = _segs()
    out1 = flash_attention(q, k, v, segment_ids=segs, block_q=128, block_k=128, interpret=True)
    # Perturb segment-0 keys/values of row 0; segment-1 outputs must not move.
    k2 = k.at[0, :100].add(1.0)
    v2 = v.at[0, :100].add(1.0)
    out2 = flash_attention(q, k2, v2, segment_ids=segs, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[0, 100:]), np.asarray(out2[0, 100:]), atol=1e-6
    )
    assert not np.allclose(np.asarray(out1[0, :100]), np.asarray(out2[0, :100]))


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2)])
def test_flash_backward_matches_reference(H, KV):
    q, k, v = _qkv(jax.random.PRNGKey(3), H, KV)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
        )


def test_flash_backward_with_segments():
    q, k, v = _qkv(jax.random.PRNGKey(4), 4, 2)
    segs = _segs()

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, segment_ids=segs, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True, segment_ids=segs)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
        )


def test_packed_sequence_training_step():
    """End-to-end: packed batch (segment_ids + restarting positions) trains
    and matches the loss of the equivalent unpacked batch."""
    from ray_tpu.models import TransformerConfig, cross_entropy_loss
    from ray_tpu.models.transformer import init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, attention_impl="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Two examples of length 8 packed into one row of 16.
    ex = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    packed_tokens = ex.reshape(1, 16)
    segs = jnp.array([[0] * 8 + [1] * 8])
    positions = jnp.array([list(range(8)) + list(range(8))])
    packed_loss = cross_entropy_loss(
        params,
        {"tokens": packed_tokens, "segment_ids": segs, "positions": positions},
        cfg,
    )
    # Unpacked: mean of the two examples' per-token NLL (equal lengths).
    unpacked_loss = cross_entropy_loss(params, {"tokens": ex}, cfg)
    np.testing.assert_allclose(float(packed_loss), float(unpacked_loss), rtol=1e-5)
