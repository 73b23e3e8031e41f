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


RULE_READS = {"latent", "recurrent", "window", "mixer"}  # what says which cache rule a kind of layer has
GONE_FROM_THE_ENGINE = {
    "_window", "_recurrent", "_kind_pools", "_slot_pools", "_tok_axis", "_row_width", "_kv_width", "_ring_pages",
    "_group", "_write_prompt", "_write_ring", "_prompt_attend", "_decode_attend", "_live_pages", "_window_walk",
}


def _imports(tree):
    """Every module a file imports, `from a import b` as both a and a.b."""
    mods = set()
    for n in ast.walk(tree):
        if isinstance(n, ast.Import):
            mods |= {a.name for a in n.names}
        elif isinstance(n, ast.ImportFrom):
            mods |= {n.module or ""} | {f"{n.module}.{a.name}" for a in n.names}
    return mods


def test_engine_knows_no_cache_rule_of_its_own():
    """llm/engine.py asks its rules (PR 49): it reads no attribute that says
    which rule a layer has, names no rule class, keeps none of the attributes
    and dispatchers that spelled the rules by `if`; llm/cache_rules.py imports
    nothing of the engine, and reads what a kind is in ``rule_for`` alone."""
    from ray_tpu.llm import cache_rules
    from ray_tpu.llm.engine import LLMEngine

    tree = ast.parse((RAY_TPU / "llm" / "engine.py").read_text())
    attrs = {n.attr for n in ast.walk(tree) if isinstance(n, ast.Attribute)}
    names = attrs | {n.id for n in ast.walk(tree) if isinstance(n, ast.Name)}
    names |= {a.name for n in ast.walk(tree) if isinstance(n, ast.ImportFrom) for a in n.names}
    names |= {n.name for n in ast.walk(tree) if isinstance(n, (ast.FunctionDef, ast.ClassDef))}
    assert not attrs & RULE_READS, attrs & RULE_READS
    rule_classes = {name for name, obj in vars(cache_rules).items()
                    if isinstance(obj, type) and obj.__module__ == cache_rules.__name__}
    assert {"PagedRows", "SlotRing", "SlotState", "LatentRows"} <= rule_classes
    assert not names & rule_classes, names & rule_classes
    assert not names & GONE_FROM_THE_ENGINE, names & GONE_FROM_THE_ENGINE
    assert not [name for name in GONE_FROM_THE_ENGINE | {"paged"} if hasattr(LLMEngine, name)]
    assert "paged" not in attrs  # nothing read LLMEngine.paged but a test
    assert "rule_for" in names

    rules = ast.parse((RAY_TPU / "llm" / "cache_rules.py").read_text())
    assert not [m for m in _imports(rules) if m.startswith("ray_tpu.llm.engine") or m == "ray_tpu.llm"], \
        _imports(rules)
    picker = next(n for n in rules.body if isinstance(n, ast.FunctionDef) and n.name == "rule_for")
    inside = {id(n) for n in ast.walk(picker)}
    outside = [n for n in ast.walk(rules) if isinstance(n, ast.Attribute) and n.attr in RULE_READS
               and id(n) not in inside]
    # a rule may keep the window it was told as its own attribute, and read another rule's
    assert [ast.unparse(n) for n in outside if not (
        n.attr == "window" and isinstance(n.value, ast.Name) and n.value.id in ("self", "other"))] == []
