"""The serve check's three sets of logits (harness/replica.py
`reference_check`) with the weights on the device once, against the form it
replaced, kept here word for word as the frozen side: the yardstick computed
from a second, rounded copy of the whole parameter tree. Toy widths, the CPU;
the same comparison at the cells' own sizes is a chip run (PERF.md section 6,
PR 35). And the decode wrapper's block length, read by name."""
import dataclasses
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR), HERE]

import control  # noqa: E402
from harness import cellspec, refcheck, schedule  # noqa: E402

ROUTED = os.path.join(os.pardir, "selftest_data", "routed_experts_olmoe")
CASES = [(config, layers, seed) for config, layers in (("internlm2-1.8b", 2), ("internlm2-1.8b", 5), (ROUTED, 2))
         for seed in (1, 2, 3)]


def _parents_check(params, cfg, model, prompt, served):
    """benchmarks/harness/replica.py `bench_reference_check` at commit 1b99f35
    (PR 33), `eng.params` and `eng.cfg` made arguments."""
    import jax
    import jax.numpy as jnp

    from harness.cellspec import architecture, routing
    from ray_tpu.models.transformer import forward

    reference = architecture(model)
    P, n = len(prompt), len(served)
    toks = jnp.asarray([list(prompt) + list(served)], jnp.int32)
    with jax.default_matmul_precision("highest"):
        plain = jax.jit(lambda p, t: reference.logits(p, t, model)[0, P - 1: P - 1 + n])
        ref = plain(params, toks)
        coarse = plain(jax.jit(refcheck.coarse_weights)(params), toks)
    cfg = dataclasses.replace(cfg, attention_impl="reference")
    own = jax.jit(lambda p, t: forward(p, t, cfg)[0][0, P - 1: P - 1 + n])(params, toks)
    return refcheck.judge(ref, own, coarse, served, routing(model))


def _toy(config, layers):
    with open(os.path.join(BENCH_DIR, "configs", config + ".json")) as f:
        model = json.load(f)
    cellspec.architecture(model).shrink(model)
    model["num_hidden_layers"] = layers
    return model


@pytest.mark.parametrize("config,layers,seed", CASES, ids=[f"{os.path.basename(c)}-{n}l-seed{s}" for c, n, s in CASES])
def test_the_check_gives_the_verdict_of_the_form_it_replaced_key_for_key(config, layers, seed):
    import jax
    import numpy as np

    from harness.replica import reference_check
    from ray_tpu.models.transformer import TransformerConfig, init_params

    model = _toy(config, layers)
    cfg = TransformerConfig(**cellspec.transformer_kwargs(model))
    params = init_params(jax.random.PRNGKey(seed), cfg)
    tokens = schedule.prompt_tokens(seed, 10 ** 6, control.PROMPT + control.SERVED, model["vocab_size"])
    prompt = tokens[:control.PROMPT]
    with jax.default_matmul_precision("highest"):
        best = np.asarray(cellspec.architecture(model).logits(params, np.asarray([tokens], np.int32), model))
    served = [int(t) for t in best[0, control.PROMPT - 1:-1].argmax(-1)]
    served[3] = int(np.argsort(best[0, control.PROMPT + 2])[-2])  # one token that trails the best
    verdict, temp_bytes = reference_check(params, cfg, model, prompt, served)
    frozen = _parents_check(params, cfg, model, prompt, served)
    assert list(verdict) == list(frozen)
    for key in frozen:
        assert verdict[key] == frozen[key], key
    assert verdict["tokens"] == control.SERVED and verdict["worst_trail"] > 0 and verdict["coarse_logit_error"] > 0
    assert set(temp_bytes) == {"reference", "coarse", "own"}


@pytest.mark.parametrize("bits", (refcheck.COARSE_MANTISSA_BITS, control.CONTROL_MANTISSA_BITS))
def test_read_coarsely_is_coarse_weights_value_for_value(bits):
    """Whole, by layer, by a slice of a layer, and through jax.numpy."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    stack = jax.random.normal(jax.random.PRNGKey(bits), (3, 8, 16), jnp.bfloat16)
    rounded = refcheck.coarse_weights({"w": stack}, bits)["w"]
    assert not np.array_equal(np.asarray(rounded, np.float32), np.asarray(stack, np.float32))
    view = refcheck.read_coarsely({"w": stack}, bits)["w"]
    assert (view.shape, view.dtype, view.ndim) == (stack.shape, stack.dtype, 3)
    for got, want in ((view.astype(jnp.float32), rounded.astype(jnp.float32)),
                      (view[1].astype(jnp.float32), rounded[1].astype(jnp.float32)),
                      (view[2][:, 4:9].astype(jnp.bfloat16), rounded[2][:, 4:9]),
                      (jnp.ones((8,), jnp.float32) @ view[0], jnp.ones((8,), jnp.float32) @ rounded[0]),
                      (jnp.asarray(view), rounded)):
        assert got.dtype == want.dtype and np.array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_decode_wrapper_reads_the_block_length_by_its_name():
    from harness.replica import block_length_reader

    class TwoPools:
        def _decode_impl(self, params, k_pages, v_pages, last_tokens, lengths, page_tables, n_steps, key, temps):
            pass

    class OneCache:
        def _decode_impl(self, params, cache, last_tokens, lengths, n_steps, key):
            pass

    two, one = block_length_reader(TwoPools()._decode_impl), block_length_reader(OneCache()._decode_impl)
    assert two(("p", "k", "v", "last", "len", "tab", 8, "key", "t"), {}) == 8  # a[6], as the wrapper read it before
    assert one(("p", "cache", "last", "len", 4, "key"), {}) == 4               # a[6] would not exist, a[5] is the key
    assert one(("p", "cache", "last", "len"), {"n_steps": 2, "key": "key"}) == 2
    with pytest.raises(ValueError):
        block_length_reader(lambda params, cache, steps: None)


def test_the_engines_decode_program_still_has_the_block_length_where_the_wrapper_read_it():
    """a[6] until PR 35: the counters of the three serve cells are unchanged."""
    import inspect

    from ray_tpu.llm.engine import LLMEngine

    assert list(inspect.signature(LLMEngine._decode_impl).parameters).index("n_steps") - 1 == 6  # less `self`


def test_the_wrappers_decode_counters_are_the_programs_own_record():
    """The benchmark's counters around the decode dispatch against what the
    program records of the same steps (stats()["trace"]["steps"], PR 24)."""
    from harness.replica import BenchLLMServer

    model = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=256, max_seq_len=256,
                 rope_theta=10000.0, attention_impl="auto", param_dtype="bfloat16")
    engine = dict(kv_layout="paged", page_size=32, max_slots=4, max_seq=128, prefill_buckets=[32, 64],
                  decode_block=4, prefix_cache=True)
    server = BenchLLMServer(model, engine, warmup_buckets=(32,))
    try:
        server.bench_counters(reset=True)
        t0 = __import__("time").monotonic()
        for i, n in enumerate((3, 9, 14)):
            server.generate(schedule.prompt_tokens(5, i, 16 + i, 512), max_tokens=n)
        counters = server.bench_counters()
        blocks = [s["block"] for s in server.stats()["trace"]["steps"] if s["t"] >= t0 and s["block"]]
    finally:
        server._stop = True
    assert counters["decode_blocks"] == len(blocks) > 0 and counters["decode_steps"] == sum(blocks)
    assert counters["slot_steps_total"] == 4 * sum(blocks) and 0 < counters["slot_steps_active"] <= sum(blocks)
