"""The seam between the harness and an architecture's file (harness/cellspec.py
`architecture`): how a configuration finds its file, and that the dense file's
reference is the one the harness has always used.

    python3 -m pytest benchmarks/tests -q        (CPU, toy widths, under a minute)
"""
import json
import os
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path.insert(0, BENCH_DIR)

from harness import cellspec, flops  # noqa: E402

TOY = {"hidden_size": 32, "num_hidden_layers": 2, "num_attention_heads": 4, "num_key_value_heads": 2,
       "intermediate_size": 48, "vocab_size": 64, "max_position_embeddings": 64,
       "rope_theta": 10000.0, "rms_norm_eps": 1e-5}


def _load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def toy_params(model, seed=20260927):
    """The program's parameter tree by shape (architectures/dense.py's
    docstring), from numpy's generator: nothing of the program is called."""
    rng = np.random.default_rng(seed)
    d, L, F, V = model["hidden_size"], model["num_hidden_layers"], model["intermediate_size"], model["vocab_size"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = d // H

    def w(*shape, fan_in):
        return (rng.standard_normal(shape) / np.sqrt(fan_in)).astype(np.float32)

    layers = {"wq": w(L, d, H, hd, fan_in=d), "wk": w(L, d, KV, hd, fan_in=d), "wv": w(L, d, KV, hd, fan_in=d),
              "wo": w(L, H, hd, d, fan_in=d), "w_gate": w(L, d, F, fan_in=d), "w_up": w(L, d, F, fan_in=d),
              "w_down": w(L, F, d, fan_in=F),
              "attn_norm": 1 + 0.1 * w(L, d, fan_in=1), "ffn_norm": 1 + 0.1 * w(L, d, fan_in=1)}
    return {"embed": w(V, d, fan_in=1), "lm_head": w(d, V, fan_in=d), "layers": layers,
            "final_norm": 1 + 0.1 * w(d, fan_in=1)}


def toy_batch(model, seed=7):
    """Two packed rows of 12 positions: documents of 5 + 7 and of 9 + padding."""
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, model["vocab_size"], (2, 12)).astype(np.int32)
    seg = np.array([[1] * 5 + [2] * 7, [1] * 9 + [0] * 3], np.int32)
    pos = np.array([list(range(5)) + list(range(7)), list(range(9)) + [0] * 3], np.int32)
    return {"tokens": tokens, "segment_ids": seg, "positions": pos, "mask": (seg > 0).astype(np.int32)}


@pytest.mark.parametrize("name", ["internlm2-1.8b.json", "mistral-7b-v0.3-l2.json", "mistral-7b-v0.3.json"])
def test_a_configuration_without_the_key_is_dense(name):
    model = _load("configs", name)
    assert "architecture" not in model
    arch = cellspec.architecture(model)
    assert arch.__file__ == os.path.join(BENCH_DIR, "architectures", "dense.py")
    for fn in ("logits", "packed_loss", "transformer_kwargs", "shrink", "attention_dims", "param_counts"):
        assert callable(getattr(arch, fn)), fn
    assert cellspec.transformer_kwargs(model) == arch.transformer_kwargs(model)
    assert flops.param_counts(model) == arch.param_counts(model)
    # the optional declaration: dense.py has none and resolves as before PR 28
    assert not hasattr(arch, "routing") and cellspec.routing(model) is None


def test_a_configuration_names_another_architecture_by_file():
    moe = _load("selftest_data", "routed_experts_olmoe.json")
    arch = cellspec.architecture(moe)
    assert arch.__file__ == os.path.join(BENCH_DIR, "architectures", "..", "selftest_data", "routed_experts.py")
    counts = flops.param_counts(moe)
    assert counts["total"] == 6_919_161_856 and counts["resident_matmul"] > 5 * counts["matmul"]
    assert cellspec.routing(moe) == moe["num_hidden_layers"] == 16  # one top-k choice a layer
    with pytest.raises(SystemExit, match="no file"):
        cellspec.architecture({"architecture": "no-such-architecture"})


def test_dense_refuses_a_head_dim_the_program_cannot_hold():
    model = dict(_load("configs", "internlm2-1.8b.json"), head_dim=64)
    with pytest.raises(SystemExit, match="head_dim"):
        cellspec.transformer_kwargs(model)


def test_shrink_goes_through_the_architecture():
    spec = cellspec.shrink_for_rehearsal(cellspec.load_cell("internlm2-1.8b.backlog"))
    assert (spec["config"]["hidden_size"], spec["config"]["num_hidden_layers"]) == (128, 2)
    assert spec["config"]["engine"]["max_slots"] == 4


@pytest.mark.parametrize("what", ["logits", "logits_segments", "packed_loss"])
def test_dense_reference_is_the_one_that_moved(what):
    """architectures/dense.py is harness/reference.py, moved: its outputs on
    seeded toy weights were written to data/dense_frozen.json by the module
    as it stood before the move (float32 on a CPU; the tolerance is float32's
    rounding over a few hundred additions, 1e-5 of values of order 1)."""
    import jax

    dense = cellspec.load_architecture("dense")
    frozen = _load("tests", "data", "dense_frozen.json")
    params, batch = toy_params(TOY), toy_batch(TOY)
    with jax.default_matmul_precision("highest"):
        if what == "logits":
            got = dense.logits(params, batch["tokens"], TOY)
        elif what == "logits_segments":
            got = dense.logits(params, batch["tokens"], TOY, batch["segment_ids"], batch["positions"])
        else:
            got = dense.packed_loss(params, batch, TOY)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(frozen[what], np.float32),
                               rtol=0, atol=1e-5)
