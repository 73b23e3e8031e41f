"""The model/engine seam (PR 30): models/transformer.py holds the one
decoder block that training, prefill and decode run; llm/engine.py supplies
only what a program does with K/V (its ``attend``)."""
import ast
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.transformer import (
    TransformerConfig, _rms_norm, decoder_block, forward, init_params,
)
from ray_tpu.ops.attention import mha_reference

RAY_TPU = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"
LAYER_WEIGHTS = {"attn_norm", "wq", "wk", "wv", "wo", "ffn_norm", "router", "w_gate", "w_up", "w_down",
                 # a latent layer's, the sandwich's and the shared expert's (PR 36)
                 "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wk_b", "wv_b", "post_attn_norm", "post_ffn_norm",
                 "ws_gate", "ws_up", "ws_down"}


@pytest.mark.parametrize("n_experts", [0, 4])
def test_decoder_block_with_reference_attention_gives_forwards_logits(n_experts):
    """A caller that brings nothing but an attention (here the plain
    reference, nothing kept) gets the model forward() computes, dense FFN
    or routed experts: the block is the whole layer."""
    cfg = TransformerConfig(
        vocab_size=97, d_model=64, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=128,
        max_seq_len=32, dtype=jnp.float32, attention_impl="reference", n_experts=n_experts,
    )
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.arange(2 * 24).reshape(2, 24) * 7 % 97, jnp.int32)
    positions = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))

    def attend(q, k, v):
        return mha_reference(q, k, v, causal=True), None

    def layer(x, lp):
        x, aux, kept = decoder_block(x, lp, cfg, positions, attend)
        assert kept is None
        return x, aux

    x, auxes = jax.lax.scan(layer, params["embed"].astype(cfg.dtype)[tokens], params["layers"])
    logits = _rms_norm(x, params["final_norm"]) @ params["lm_head"].astype(cfg.dtype)
    want, want_aux = forward(params, tokens, cfg)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(float(jnp.sum(auxes)), float(want_aux), rtol=1e-5)
    assert (float(want_aux) > 0) == bool(n_experts)


def _weight_reads(tree):
    """Subscripts by a layer weight's name, e.g. lp["wq"]."""
    return [n for n in ast.walk(tree) if isinstance(n, ast.Subscript)
            and isinstance(n.slice, ast.Constant) and n.slice.value in LAYER_WEIGHTS]


def test_engine_has_no_layer_mathematics_of_its_own():
    """llm/engine.py reads no layer weight, ropes nothing and calls no FFN:
    a sixth copy of the layer cannot come back unseen. Across ray_tpu/ the
    projection over wq is written once, in decoder_block."""
    tree = ast.parse((RAY_TPU / "llm" / "engine.py").read_text())
    names = {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    names |= {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    names |= {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) for a in n.names}
    assert not names & {"_rope", "_dense_ffn", "_moe_ffn", "_attention"}, names
    assert [ast.unparse(n) for n in _weight_reads(tree)] == []
    assert "decoder_block" in names

    wq_einsums = []
    for path in sorted(RAY_TPU.rglob("*.py")):
        for call in ast.walk(ast.parse(path.read_text())):
            if (isinstance(call, ast.Call) and isinstance(call.func, ast.Attribute)
                    and call.func.attr == "einsum"
                    and any(r.slice.value == "wq" for a in call.args for r in _weight_reads(a))):
                wq_einsums.append(f"{path.relative_to(RAY_TPU)}:{call.lineno}")
    assert len(wq_einsums) == 1 and wq_einsums[0].startswith("models/transformer.py"), wq_einsums
