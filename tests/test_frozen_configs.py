"""Every configuration of the benchmark, at its architecture file's toy widths
(``shrink``), builds the parameter tree and gives the outputs it gave at PR 53,
before a layer's mixer could be latent attention beside other kinds (PR 54):
the tree's paths, shapes and dtypes and a digest of every leaf's bytes, the
whole-sequence ``forward``'s logits, and the tokens the engine serves for one
prompt (prefill, then decode through the model's cache rules), float32 on the
CPU. A field added to ``TransformerConfig`` or ``LayerKind`` is off at its
default: the numbers here do not move. ``tests/data/frozen_configs.json`` was
written by this file run as a script on the tree of PR 53
(``python tests/test_frozen_configs.py --write``); write it again only with a
PR whose purpose is to change what these configurations compute."""
import hashlib
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
FROZEN = os.path.join(ROOT, "tests", "data", "frozen_configs.json")
CONFIGS = ("mistral-7b-v0.3-l2", "internlm2-1.8b", "mistral-7b-v0.3", "openpangu-ultra-moe-718b-ep16",
           "laguna-s-2.1-ep8", "solar-open2-250b-ep8", "granite-4.0-h-micro", "lfm2-24b-a2b-l9")


def _measure(name: str) -> dict:
    import jax
    import jax.numpy as jnp

    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from harness import cellspec

    from ray_tpu.llm import EngineConfig, LLMEngine
    from ray_tpu.models.transformer import TransformerConfig, forward, init_params

    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        config = json.load(f)
    arch = cellspec.architecture(config)
    arch.shrink(config)
    kwargs = {**arch.transformer_kwargs(config), "dtype": jnp.float32, "param_dtype": jnp.float32,
              "attention_impl": "reference"}
    kwargs = {k: v for k, v in kwargs.items() if k in TransformerConfig.__dataclass_fields__}  # a train group's extras
    cfg = TransformerConfig(**kwargs)
    params = init_params(jax.random.PRNGKey(7), cfg)
    leaves = jax.tree_util.tree_leaves_with_path(params)
    tree = {jax.tree_util.keystr(path): [list(a.shape), str(a.dtype),
                                         hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]]
            for path, a in leaves}
    tokens = np.random.default_rng(11).integers(0, cfg.vocab_size, size=(2, 40)).astype(np.int32)
    logits = np.asarray(forward(params, jnp.asarray(tokens), cfg)[0], np.float32)
    out = {"tree": tree, "logits_sha": hashlib.sha256(logits.tobytes()).hexdigest()[:16],
           "logits_head": [float(v) for v in logits[0, -1, :8]], "logits_abs_sum": float(np.abs(logits).sum())}
    if cfg.n_experts and not cfg.experts_held:
        return out  # the training form of a routed model is not served
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(
        max_slots=2, max_seq=128, page_size=16, prefill_buckets=(32, 64), decode_block=4))
    out["served"] = [int(t) for t in eng.generate(tokens[0, :21], max_tokens=10)["tokens"]]
    return out


@pytest.mark.parametrize("name", CONFIGS)
def test_a_configuration_builds_the_tree_and_the_outputs_it_did_at_pr_53(name):
    with open(FROZEN) as f:
        want = json.load(f)[name]
    got = _measure(name)
    assert list(got["tree"]) == list(want["tree"])  # the same leaves in the same order
    assert got["tree"] == want["tree"]  # of the same shapes and dtypes, drawn from the same keys
    np.testing.assert_allclose(got["logits_head"], want["logits_head"], rtol=0, atol=1e-6)
    assert abs(got["logits_abs_sum"] - want["logits_abs_sum"]) <= 1e-6 * want["logits_abs_sum"]
    assert got.get("served") == want.get("served")


if __name__ == "__main__":
    if sys.argv[1:] != ["--write"]:
        raise SystemExit("usage: python tests/test_frozen_configs.py --write")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    frozen = {name: _measure(name) for name in CONFIGS}
    with open(FROZEN, "w") as f:
        json.dump(frozen, f, indent=1)
    print({name: (len(v["tree"]), v["logits_sha"], v.get("served")) for name, v in frozen.items()})
