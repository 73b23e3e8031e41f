"""The table of prefill programs (bucket, k): a rung between any two configured
buckets a doubling apart, groups of k only while a group holds at most
GROUP_TOKENS tokens, and a warm-up that covers every rung between the lengths
it is given (PERF.md section 6, PR 48)."""
import dataclasses
import json
import os

import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.accel import device
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.engine import GROUP_TOKENS, bucket_ladder
from ray_tpu.models import TransformerConfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CFG = TransformerConfig(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=64,
    max_seq_len=2048, dtype=jnp.float32, attention_impl="reference",
)
SHORT_CFG = dataclasses.replace(CFG, max_seq_len=128)
LONG_CTX = (512, 768, 1024, 1536, 2048, 3072, 4096, 6144, 8192, 9344)
SHORT = (128, 256, 384, 512, 768, 1024, 1536, 2048)
# each committed configuration that serves: its engine's ladder, and the (bucket, k)
# programs its cell warms (the buckets its traffic reaches, harness/serve_cell._warmup_buckets)
LADDERS = {
    "laguna-s-2.1-ep8": (LONG_CTX, (512, 8192), 13),
    "solar-open2-250b-ep8": (LONG_CTX, (512, 8192), 13),
    "mistral-7b-v0.3": ((128, 256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096), (128, 3072), 21),
    "openpangu-ultra-moe-718b-ep16": ((256, 384, 512, 768, 1024, 1536, 2048, 3072, 4096), (256, 2048), 16),
    "internlm2-1.8b": (SHORT, (128, 1536), 19),
    "granite-4.0-h-micro": (SHORT, (128, 1536), 19),
}


def _group_sizes(bucket):
    """LLMEngine.group_sizes without an engine (it reads k_buckets alone)."""
    return LLMEngine.group_sizes(type("E", (), {"k_buckets": (8, 4, 2, 1)}), bucket)


@pytest.mark.parametrize("name", sorted(LADDERS))
def test_the_ladder_of_each_committed_serve_configuration(name):
    with open(os.path.join(ROOT, "benchmarks", "configs", name + ".json")) as f:
        engine = json.load(f)["engine"]
    ladder, (lo, hi), n_programs = LADDERS[name]
    got = bucket_ladder(engine["prefill_buckets"], engine["page_size"], engine["max_seq"])
    assert got == ladder
    assert set(engine["prefill_buckets"]) <= set(got)  # a configured length stays a bucket
    # no neighbours a doubling apart are left but those a page apart, and a rung is whole pages
    assert all(b < 2 * a or b - a == engine["page_size"] for a, b in zip(got, got[1:]))
    assert all(b % engine["page_size"] == 0 for b in got)
    warmed = [(b, k) for b in got if lo <= b <= hi for k in _group_sizes(b)]
    assert len(warmed) == n_programs


@pytest.mark.parametrize("buckets,ps,S,want", [
    ((16, 32, 64), 16, 128, (16, 32, 48, 64, 96, 128)),  # 24 is no whole page
    ((48,), 16, 128, (48, 96, 128)),  # (48 + 128) / 2 = 88 -> 96: strictly between
    ((100, 1000), 128, 512, (128, 384, 512)),  # rounded to pages; over max_seq dropped
    ((128, 192, 288), 32, 288, (128, 192, 288)),  # steps of 1.5: nothing to add
])
def test_the_ladder_rounds_to_pages_and_adds_only_rungs_that_lie_between(buckets, ps, S, want):
    assert bucket_ladder(buckets, ps, S) == want


@pytest.mark.parametrize("bucket,want", [
    (16, (8, 4, 2, 1)), (256, (8, 4, 2, 1)), (384, (4, 2, 1)), (512, (4, 2, 1)), (768, (2, 1)), (1024, (2, 1)),
    (1536, (1,)), (2048, (1,)), (8192, (1,)),
])
def test_a_group_holds_at_most_group_tokens(bucket, want):
    assert GROUP_TOKENS == 2048
    assert _group_sizes(bucket) == want
    assert all(k == 1 or k * bucket <= GROUP_TOKENS for k in want)


@pytest.fixture(scope="module")
def long_engine():
    """Pages of 128 and a ladder that crosses GROUP_TOKENS: 256, 640, 1024, 1536, 2048."""
    eng = LLMEngine(CFG, engine_config=EngineConfig(
        max_slots=8, max_seq=2048, page_size=128, total_pages=8 * 16 + 1, prefill_buckets=(256, 1024), decode_block=2))
    device._count_compiles()
    eng.warmup()
    return eng


def test_what_warmup_logs_is_what_a_step_can_dispatch(long_engine):
    eng = long_engine
    assert eng.buckets == (256, 640, 1024, 1536, 2048) and eng.k_buckets == (8, 4, 2, 1)
    warmed = [(p["bucket"], p["k"]) for p in eng.warmup_log if p["program"] == "prefill"]
    assert warmed == [(256, 8), (256, 4), (256, 2), (256, 1), (640, 2), (640, 1), (1024, 2), (1024, 1),
                      (1536, 1), (2048, 1)]
    assert warmed == [(b, k) for b in eng.buckets for k in eng.group_sizes(b)]
    # 8 requests a step, of one bucket each round and then of all of them: every group the
    # engine forms has its program, and none holds more than GROUP_TOKENS tokens
    dispatched, prefill_of = [], eng._prefill
    eng._prefill = lambda bucket, k: dispatched.append((bucket, k)) or prefill_of(bucket, k)
    compiled = device.compile_events()["count"]
    try:
        rounds = [[b - 3 * j - 5 for j in range(8)] for b in eng.buckets]
        rounds.append([256, 250, 200, 700, 1000, 1100, 600, 2000])
        for r, lengths in enumerate(rounds):
            for j, n in enumerate(lengths):
                eng.add_request(f"r{r}.{j}", (np.arange(n) * (j + 3) + r) % 97, 2)
            first = eng.step()
            assert len(first) == 8  # all eight admitted and prefilled in one step
            while eng.has_work():
                eng.step()
    finally:
        eng._prefill = prefill_of
    assert set(dispatched) <= set(warmed)
    assert {(256, 8), (640, 2), (1024, 2), (1536, 1), (2048, 1)} <= set(dispatched)
    assert all(k == 1 or k * b <= GROUP_TOKENS for b, k in dispatched)
    assert device.compile_events()["count"] == compiled
    # the step record counts the groups' own tokens and the rows their programs ran over
    steps = [s for s in eng.trace_snapshot()["steps"] if s["n_prefill"]]
    assert len(steps) == len(rounds)
    for s, lengths in zip(steps, rounds):
        assert s["prefill_tokens"] == sum(lengths)
        assert s["prefill_padded"] == sum(next(b for b in eng.buckets if b >= n) for n in lengths)
    assert sum(s["prefill_padded"] for s in steps) == sum(b * k for b, k in dispatched)
    quiet = [s for s in eng.trace_snapshot()["steps"] if not s["n_prefill"]]
    assert quiet and all(s["prefill_tokens"] == 0 == s["prefill_padded"] for s in quiet)


def test_warmup_of_the_configured_lengths_covers_every_rung_between_them():
    """A deployment names its configured buckets; the engine warms the rungs it
    put between them too, so no prompt from the shortest to the longest named
    length compiles anything, one at a time or as a burst."""
    configured = (16, 32, 64)
    eng = LLMEngine(SHORT_CFG, engine_config=EngineConfig(
        max_slots=4, max_seq=128, page_size=16, prefill_buckets=configured, decode_block=2))
    device._count_compiles()
    eng.warmup(buckets=configured)
    warmed = {p["bucket"] for p in eng.warmup_log if p["program"] == "prefill"}
    assert eng.buckets == (16, 32, 48, 64, 96, 128) and warmed == {16, 32, 48, 64}  # 96 and 128: beyond what was named
    compiled = device.compile_events()["count"]
    for n in range(configured[0], configured[-1] + 1):
        out = eng.generate((np.arange(n) * 5 + n) % 97, max_tokens=2)
        assert len(out["tokens"]) == 2
    for j, n in enumerate((17, 33, 40, 64)):
        eng.add_request(f"b{j}", (np.arange(n) * 7) % 97, 3)
    while eng.has_work():
        eng.step()
    assert device.compile_events()["count"] == compiled
    used = {r["bucket"] for r in eng.trace_snapshot()["requests"]}
    assert used == warmed
    # a raw prompt length warms the one bucket it pads to
    one = LLMEngine(SHORT_CFG, engine_config=EngineConfig(
        max_slots=2, max_seq=128, page_size=16, prefill_buckets=configured, decode_block=2))
    one.warmup(buckets=(40,), k_values=(1,))
    assert [(p["bucket"], p["k"]) for p in one.warmup_log if p["program"] == "prefill"] == [(48, 1)]


@pytest.mark.parametrize("n_prompt,rung,doubled", [(40, 48, 64), (90, 96, 128)])
def test_greedy_tokens_are_equal_on_a_rung_and_on_the_doubled_bucket(n_prompt, rung, doubled):
    """One prompt, padded to the midpoint rung (48 of 32..64, 96 of 64..128) by
    an engine whose list leaves room for it, and to the doubled bucket by an
    engine whose list has no neighbours a doubling apart (float32)."""
    prompt = (np.arange(n_prompt) * 11 + 5) % 97
    outs = []
    for buckets, bucket in (((32, 64), rung), ((64, 80), doubled)):
        eng = LLMEngine(SHORT_CFG, engine_config=EngineConfig(
            max_slots=2, max_seq=128, page_size=16, prefill_buckets=buckets, decode_block=4))
        out = eng.generate(prompt, max_tokens=12)
        assert eng.trace_snapshot()["requests"][0]["bucket"] == bucket
        outs.append(out["tokens"])
    assert outs[0] == outs[1] and len(outs[0]) == 12
