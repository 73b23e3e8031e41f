"""The batched sampler (llm/sampling.py ``sample_batch``): a candidate is
computed only when some row of the batch will take it, and a row draws the
token it drew when every candidate was computed for every row.

The function as it stood before the conditionals is kept here as the plain
reference: all three candidates for all rows, then the two selects.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine, SamplingParams
from ray_tpu.llm.sampling import TOPK_CAP, sample_batch
from ray_tpu.models import TransformerConfig

V = 311


def reference_sample_batch(logits, temps, top_ps, top_ks, key, cap=None):
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    cap = min(TOPK_CAP if cap is None else cap, V)
    top_vals, top_idx = jax.lax.top_k(scaled, cap)
    ks = jnp.where(top_ks <= 0, cap, jnp.minimum(top_ks, cap))
    pos = jnp.arange(cap)[None, :]
    masked = jnp.where(pos < ks[:, None], top_vals, -jnp.inf)
    probs = jax.nn.softmax(masked, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    keep = (cum - probs) < top_ps[:, None]
    masked = jnp.where(keep, masked, -jnp.inf)
    k1, k2 = jax.random.split(key)
    choice = jax.random.categorical(k1, masked, axis=-1)
    truncated = jnp.take_along_axis(top_idx, choice[:, None], axis=-1)[:, 0]
    full = jax.random.categorical(k2, scaled, axis=-1)
    plain = (top_ps >= 1.0) & (top_ks <= 0)
    out = jnp.where(plain, full, truncated)
    return jnp.where(temps <= 0.0, greedy, out).astype(jnp.int32)


# a row's kind: (temperature, top_p, top_k)
GREEDY = (0.0, 1.0, 0)
GREEDY_WITH_TOP_K = (0.0, 0.9, 5)  # temperature 0 wins over the truncation asked for
TOP_K = (0.8, 1.0, 7)
TOP_P = (1.3, 0.9, 0)
TOP_K_AND_P = (0.7, 0.8, 40)
PLAIN = (0.9, 1.0, 0)

# name -> (the rows of the batch, cap)
BATCHES = {
    "all rows greedy": ([GREEDY] * 5 + [GREEDY_WITH_TOP_K], None),
    "all truncated, top-k only": ([TOP_K] * 6, None),
    "all truncated, top-p only": ([TOP_P] * 6, None),
    "all truncated, top-k and top-p": ([TOP_K_AND_P] * 6, None),
    "all plain temperature": ([PLAIN, (0.3, 1.0, 0), (2.0, 1.0, 0)] * 2, None),
    "greedy beside truncated": ([GREEDY, TOP_K, GREEDY, TOP_P, TOP_K_AND_P, GREEDY], None),
    "greedy beside plain": ([PLAIN, GREEDY, GREEDY, PLAIN, GREEDY, GREEDY], None),
    "truncated beside plain": ([TOP_K, PLAIN, TOP_P, PLAIN, TOP_K_AND_P, PLAIN], None),
    "all three kinds": ([GREEDY, TOP_K, PLAIN, TOP_P, GREEDY_WITH_TOP_K, TOP_K_AND_P, PLAIN], None),
    "cap below the vocabulary": ([GREEDY, TOP_K, PLAIN, TOP_P, TOP_K_AND_P], 16),
    "cap below a row's top-k": ([TOP_K_AND_P, GREEDY, PLAIN], 8),
    "cap above the vocabulary": ([GREEDY, TOP_K, PLAIN, TOP_P, TOP_K_AND_P], 4 * V),
    "one row, greedy": ([GREEDY], None),
    "one row, truncated": ([TOP_K_AND_P], None),
    "one row, plain": ([PLAIN], None),
}


def _params(rows):
    temps, top_ps, top_ks = zip(*rows)
    return jnp.asarray(temps, jnp.float32), jnp.asarray(top_ps, jnp.float32), jnp.asarray(top_ks, jnp.int32)


@pytest.mark.parametrize("batch", sorted(BATCHES))
def test_a_row_draws_the_token_it_drew_before(batch):
    """Token for token against the reference on the same keys, jitted as the
    engine's programs have it; a greedy row is the argmax."""
    rows, cap = BATCHES[batch]
    params = _params(rows)
    got_fn = jax.jit(sample_batch, static_argnames="cap")
    want_fn = jax.jit(reference_sample_batch, static_argnames="cap")
    rng = np.random.default_rng(len(batch))
    left_the_argmax = False
    for seed in range(8):
        # peaked and flat rows: a nucleus of one candidate and one wider than the cap
        logits = jnp.asarray(rng.normal(size=(len(rows), V)) * rng.choice([0.5, 3.0, 9.0], size=(len(rows), 1)),
                             jnp.float32)
        key = jax.random.PRNGKey(1000003 * seed + 17)
        got = np.asarray(got_fn(logits, *params, key, cap=cap))
        want = np.asarray(want_fn(logits, *params, key, cap=cap))
        np.testing.assert_array_equal(got, want)
        assert got.dtype == np.int32 and got.shape == (len(rows),)
        greedy = np.asarray(jnp.argmax(logits, axis=-1))
        for r, (temp, _, _) in enumerate(rows):
            if temp <= 0:
                assert got[r] == greedy[r]
        left_the_argmax |= bool((got != greedy).any())
    # the draws are draws: with a sampled row in the batch, some row left its argmax on some key
    assert left_the_argmax == any(temp > 0 for temp, _, _ in rows)


def _walk(jaxpr, inside, seen):
    """Every primitive of a jaxpr and of the jaxprs under it, with whether it
    lies inside a ``cond``'s branch."""
    for eqn in jaxpr.eqns:
        seen.append((eqn.primitive.name, inside))
        below = inside or eqn.primitive.name == "cond"
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else (value,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    _walk(sub, below, seen)


def test_the_costly_candidates_sit_inside_conditionals():
    rows = [GREEDY, TOP_K, PLAIN, TOP_P]
    logits = jnp.zeros((len(rows), V), jnp.float32)
    seen = []
    _walk(jax.make_jaxpr(sample_batch)(logits, *_params(rows), jax.random.PRNGKey(0)).jaxpr, False, seen)
    names = {name for name, _ in seen}
    assert {"cond", "top_k", "random_bits", "argmax", "cumsum"} <= names
    inside_only = {"top_k", "random_bits", "cumsum", "exp", "log", "div", "gather"}
    assert [name for name, inside in seen if name in inside_only and not inside] == []
    assert [inside for name, inside in seen if name == "argmax"].count(False) == 1  # the greedy candidate
    assert sum(1 for name, _ in seen if name == "cond") == 2
    # what the engine must never do: under vmap a cond is a select, both sides computed
    seen = []
    batched = jax.vmap(sample_batch, in_axes=(0, 0, 0, 0, None))
    _walk(jax.make_jaxpr(batched)(logits[None], *(p[None] for p in _params(rows)), jax.random.PRNGKey(0)).jaxpr,
          False, seen)
    assert "cond" not in {name for name, _ in seen}


CFG = TransformerConfig(
    vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)
ENGINE_KW = dict(max_slots=4, max_seq=128, page_size=16, prefill_buckets=(16, 32))


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW))


def test_the_conditionals_survive_to_the_compiled_decode_program(engine):
    """``_decode_impl`` lowered and compiled as the engine jits it: the
    sampler's two conditionals are there, under the step scan's loop, so
    nobody has wrapped the sampler in a ``vmap``."""
    eng = engine
    lowered = eng._decode_jit.lower(
        eng.params, eng.cache, eng.d_last, eng.d_lengths, eng.d_page_tables, jax.random.PRNGKey(0), 2,
        eng.d_temps, eng.d_top_ps, eng.d_top_ks)
    # the sampler's, by their scope: off the TPU the reference attention
    # writes each slot's row under a conditional of its own (a slot without a
    # sequence writes nothing; PR 42), and those are not the sampler's
    sampled = [line for line in lowered.compile().as_text().splitlines()
               if " conditional(" in line and "/sample/cond" in line]
    assert len(sampled) == 2 and all("/while/body/" in line for line in sampled)
    assert lowered.as_text(debug_info=True).count('loc("sample/cond"(') == 2


def _run(eng, requests):
    """Requests (id -> (prompt, SamplingParams)) run to their ends: each
    one's tokens, and the step records of the steps that held a decode block."""
    before = eng.trace_snapshot()["steps_total"]
    for rid, (prompt, sp) in requests.items():
        eng.add_request(rid, prompt, sampling=sp)
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    snap = eng.trace_snapshot()
    steps = snap["steps"][len(snap["steps"]) - (snap["steps_total"] - before):]
    return done, [s for s in steps if s["block"]]


def test_step_records_count_the_slots_that_sample(engine):
    """``sampled`` of a step's record: the active slots whose request has a
    temperature, 0 in a step whose sampler took only the argmax; and a greedy
    request's tokens do not depend on who samples beside it."""
    eng = engine
    prompt_g = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    prompt_s = np.array([2, 7, 1, 8, 2, 8], np.int32)
    # 28: the engine is a block ahead, so the sampled request's row rides one
    # block past its end; the greedy one has to outlive that block too
    greedy = SamplingParams(max_tokens=28)
    warm = SamplingParams(temperature=0.7, top_p=0.95, max_tokens=12)

    alone, blocks = _run(eng, {"g": (prompt_g, greedy)})
    assert len(alone["g"]) == 28 and len(blocks) >= 2
    assert [s["sampled"] for s in blocks] == [0] * len(blocks)
    assert [s["active"] for s in blocks] == [1] * len(blocks)

    sampled_alone, blocks = _run(eng, {"s": (prompt_s, warm)})
    assert len(sampled_alone["s"]) == 12
    assert [s["sampled"] for s in blocks] == [1] * len(blocks)

    # the slot the sampled request left keeps its temperature on the host's
    # mirror; it is not active, so it does not count (and the program treats
    # a slot without pages as greedy)
    assert (eng.samp_temps > 0).any()
    again, blocks = _run(eng, {"g": (prompt_g, greedy)})
    assert again == alone and [s["sampled"] for s in blocks] == [0] * len(blocks)

    # `sampled` is the host's count at dispatch, and the device's too: a row the
    # program samples is one with pages and a temperature in the mirrors it is
    # handed. A finished sampled row keeps both for the one block that was
    # enqueued before the host walked its last token, and is counted there.
    decode, on_device = eng._decode_jit, []

    def spy(*args):
        on_device.append(int(np.count_nonzero((np.asarray(args[4])[:, 0] > 0) & (np.asarray(args[7]) > 0))))
        return decode(*args)

    eng._decode_jit = spy
    try:
        mixed, blocks = _run(eng, {"g": (prompt_g, greedy), "s": (prompt_s, warm)})
    finally:
        eng._decode_jit = decode
    assert [s["sampled"] for s in blocks] == on_device
    assert mixed["g"] == alone["g"] and len(mixed["s"]) == 12
    both = [s for s in blocks if s["active"] == 2]
    assert both and [s["sampled"] for s in both] == [1] * len(both)
    # the greedy request outlives the sampled one: its last blocks only take the argmax
    assert blocks[-1]["active"] == 1 and blocks[-1]["sampled"] == 0
    assert {s["sampled"] for s in eng.trace_snapshot()["steps"] if not s["block"]} <= {0}


def test_a_slot_without_pages_is_a_greedy_row_of_the_decode_program(engine, monkeypatch):
    """The temperatures the sampler is handed inside ``_decode_impl``: a slot
    whose table row is empty reads 0 whatever the mirror holds for it."""
    from ray_tpu.llm import engine as engine_mod

    eng = LLMEngine(CFG, params=engine.params, engine_config=EngineConfig(**ENGINE_KW))
    handed = []

    def spy(logits, temps, top_ps, top_ks, key, cap=None):
        jax.debug.callback(lambda t: handed.append(np.asarray(t)), temps, ordered=True)
        return sample_batch(logits, temps, top_ps, top_ks, key, cap=cap)

    monkeypatch.setattr(engine_mod, "sample_batch", spy)
    eng.samp_temps[:] = 0.5  # as retired sampled requests would leave every slot
    eng.add_request("s", np.arange(5, dtype=np.int32), sampling=SamplingParams(temperature=0.7, max_tokens=6))
    while eng.has_work():
        eng.step()
    jax.effects_barrier()
    decode_rows = [t for t in handed if t.shape == (ENGINE_KW["max_slots"],)]
    assert decode_rows
    for temps in decode_rows:
        assert np.count_nonzero(temps) == 1 and temps.max() == np.float32(0.7)
