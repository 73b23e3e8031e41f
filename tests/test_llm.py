"""LLM engine + serving: KV-cache decode correctness vs full forward,
continuous batching consistency, TTFT reporting, serve integration.
Reference analogue: python/ray/llm/tests (MockVLLMEngine-based serving tests,
SURVEY §4) — here the engine is real, just tiny and on CPU."""
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.models import TransformerConfig
from ray_tpu.models.transformer import forward, init_params

CFG = TransformerConfig(
    vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)


def _naive_greedy(params, prompt, n):
    toks = list(map(int, prompt))
    out = []
    for _ in range(n):
        logits, _ = forward(params, jnp.asarray([toks], jnp.int32), CFG)
        nxt = int(jnp.argmax(logits[0, -1]))
        toks.append(nxt)
        out.append(nxt)
    return out


@pytest.fixture(scope="module")
def engine():
    return LLMEngine(CFG, engine_config=EngineConfig(
        max_slots=4, max_seq=128, page_size=16, prefill_buckets=(16, 32, 64)))


@pytest.mark.slow  # heavy battery; tier-1 budget (see CHANGES PR-13)
def test_cached_decode_matches_full_forward(engine):
    prompt = np.array([5, 17, 42, 7, 23], np.int32)
    want = _naive_greedy(engine.params, prompt, 12)
    got = engine.generate(prompt, max_tokens=12)
    assert got["tokens"] == want
    assert got["ttft_s"] is not None and got["ttft_s"] > 0


def test_continuous_batching_matches_solo(engine):
    """A request joining mid-decode must not perturb an in-flight one, and
    both must equal their solo outputs (slot isolation)."""
    p1 = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    p2 = np.array([2, 7, 1, 8], np.int32)
    solo1 = engine.generate(p1, max_tokens=10)["tokens"]
    solo2 = engine.generate(p2, max_tokens=10)["tokens"]

    engine.add_request("a", p1, 10)
    results = {}
    for _ in range(3):  # a starts decoding alone
        for rid, ev in engine.step().items():
            if ev.get("finished"):
                results[rid] = ev["tokens"]
    engine.add_request("b", p2, 10)  # b joins mid-flight
    while engine.has_work():
        for rid, ev in engine.step().items():
            if ev.get("finished"):
                results[rid] = ev["tokens"]
    assert results["a"] == solo1
    assert results["b"] == solo2


def test_slot_reuse_after_finish(engine):
    """More requests than slots: queueing + slot recycling must preserve
    per-request outputs."""
    prompts = [np.arange(3 + i, dtype=np.int32) % 97 for i in range(9)]
    solos = [engine.generate(p, max_tokens=6)["tokens"] for p in prompts]
    for i, p in enumerate(prompts):
        engine.add_request(f"r{i}", p, 6)
    results = {}
    while engine.has_work():
        for rid, ev in engine.step().items():
            if ev.get("finished"):
                results[rid] = ev["tokens"]
    for i in range(9):
        assert results[f"r{i}"] == solos[i], i


def test_eos_stops_generation():
    eng = LLMEngine(
        CFG,
        engine_config=EngineConfig(max_slots=2, max_seq=128, page_size=16, prefill_buckets=(16,), eos_id=0),
    )
    out = eng.generate(np.array([5, 6, 7], np.int32), max_tokens=40)
    if 0 in out["tokens"]:
        assert out["tokens"].index(0) == len(out["tokens"]) - 1


@pytest.mark.slow  # heavy battery; tier-1 budget (see CHANGES PR-13)
def test_llm_serve_deployment():
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.llm import build_llm_app

    rt.init(num_cpus=8)
    serve.start(proxy=False)
    try:
        app = build_llm_app(
            model_config=dict(
                vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                d_ff=128, max_seq_len=128, attention_impl="reference",
            ),
            engine_config={"max_slots": 4, "max_seq": 128, "page_size": 16, "prefill_buckets": (16, 32)},
        )
        handle = serve.run(app, name="llm_app", http=False)
        # Concurrent requests batch at iteration level on one replica.
        resps = [
            handle.remote({"tokens": [3, 1, 4, 1, 5], "max_tokens": 8})
            for _ in range(4)
        ]
        outs = [r.result(timeout=120) for r in resps]
        first = outs[0]["tokens"]
        assert len(first) == 8
        for o in outs:
            assert o["tokens"] == first  # same prompt, greedy -> same output
            assert o["ttft_s"] is not None
        serve.delete("llm_app")
    finally:
        serve.shutdown()
        rt.shutdown()


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------

def test_paged_pool_memory_independent_of_slots():
    """The point of paging: slot count is a scheduling knob, not a memory
    multiplier. 32 slots over a 16-page pool uses 16 pages of HBM, not
    32 x max_seq."""
    ec = EngineConfig(max_slots=32, max_seq=128, page_size=16, total_pages=17,
                      prefill_buckets=(16,), decode_block=2)
    eng = LLMEngine(CFG, engine_config=ec)
    assert eng.cache[0].shape[2] == 17 * 16  # pool tokens, NOT 32*128
    out = eng.generate([1, 2, 3], max_tokens=4)
    assert len(out["tokens"]) == 4


def test_paged_admission_waits_for_pages_then_proceeds():
    """Pool smaller than the aggregate demand: admission queues on the page
    budget (not slot count) and every request still completes."""
    ec = EngineConfig(max_slots=8, max_seq=128, page_size=16, total_pages=9,
                      prefill_buckets=(16,), decode_block=2)
    eng = LLMEngine(CFG, engine_config=ec)
    # Each request needs ceil((3 + 8 + 2)/16) = 1 page prompt... force more:
    # prompt 3 + max_tokens 20 + block 2 = 25 -> 2 pages. Pool has 8 usable.
    for r in range(8):
        eng.add_request(f"q{r}", [1, 2, 3], 20)
    results = {}
    concurrent_seen = 0
    while eng.has_work():
        active = sum(1 for s in eng.slots if s is not None)
        concurrent_seen = max(concurrent_seen, active)
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                results[rid] = ev["tokens"]
    assert len(results) == 8
    assert concurrent_seen <= 4  # 8 usable pages / 2 pages each
    first = results["q0"]
    assert all(results[f"q{r}"] == first for r in range(8))  # same prompt, greedy


def test_paged_pages_recycled_after_finish():
    ec = EngineConfig(max_slots=2, max_seq=128, page_size=16, total_pages=9,
                      prefill_buckets=(16,), decode_block=2)
    eng = LLMEngine(CFG, engine_config=ec)
    free0 = len(eng.free_pages)
    for _ in range(3):
        eng.generate([4, 5, 6], max_tokens=6)
    assert len(eng.free_pages) == free0  # every reservation returned


def test_paged_abort_frees_pages():
    ec = EngineConfig(max_slots=2, max_seq=128, page_size=16, total_pages=9,
                      prefill_buckets=(16,), decode_block=2)
    eng = LLMEngine(CFG, engine_config=ec)
    free0 = len(eng.free_pages)
    eng.add_request("gone", [1, 2, 3], 100)
    eng.step()  # admitted: pages reserved, decoding
    assert len(eng.free_pages) < free0
    eng.abort("gone")
    assert len(eng.free_pages) == free0
    assert not eng.has_work()
    # Engine still serves after the abort.
    out = eng.generate([1, 2, 3], max_tokens=4)
    assert len(out["tokens"]) == 4


def test_paged_decode_matches_across_pool_layouts():
    """Same request, different page pools (dense parity vs tight pool with
    non-trivial page scatter): identical greedy tokens."""
    prompt = [9, 8, 7, 6, 5, 4, 3, 2, 1]
    outs = []
    for total_pages in (0, 12):
        ec = EngineConfig(max_slots=3, max_seq=128, page_size=16,
                          prefill_buckets=(16,), total_pages=total_pages,
                          decode_block=4)
        eng = LLMEngine(CFG, engine_config=ec)
        # Fragment the free list so page tables are non-contiguous.
        eng.generate([1, 2], max_tokens=3)
        eng.generate([3, 4, 5], max_tokens=5)
        outs.append(eng.generate(prompt, max_tokens=12)["tokens"])
    assert outs[0] == outs[1]


POOL_CFG = TransformerConfig(
    vocab_size=97, d_model=64, n_layers=4, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)


def _big_pool_engine():
    """A tiny model beside a pool that dwarfs it (2 MiB an array), so that a
    program's temporaries say whether it holds a copy of a pool."""
    return LLMEngine(POOL_CFG, engine_config=EngineConfig(
        max_slots=2, max_seq=64, page_size=16, total_pages=256,
        prefill_buckets=(32,), decode_block=4,
    ))


def test_paged_decode_program_does_not_move_the_pool():
    """The decode block carries both KV pools through its layer loop and
    writes the new rows in place, so what it holds beside its arguments is a
    sliver of one pool. Passing the pools through the layer scan as xs / ys
    made the compiler keep copies of them: more than two pools of
    temporaries, on this backend as on the chip (PERF.md section 6, PR 25)."""
    eng = _big_pool_engine()
    eng.warmup(buckets=(32,), k_values=(1,))
    decode = [p for p in eng.warmup_log if p["program"] == "decode"]
    assert {p["block"] for p in decode} == {1, 4}
    for p in decode:
        assert 0 <= p["temp_bytes"] < eng.cache[0].nbytes // 2, (p, eng.cache[0].nbytes)


@pytest.mark.parametrize("program", ["prefill-k1", "prefill-k4", "tail"])
def test_paged_prefill_programs_do_not_move_the_pool(program):
    """No prefill program has a pool, or a layer's slice of one, as xs / ys
    of a scan: the layer scan hands out the prompt's K/V and each page is
    written once, in place, into the donated pools. With the pools passed
    through the layer scan (the form before PR 29) these three programs held
    5,425,136 / 5,426,960 / 5,416,840 bytes of temporaries on this backend
    beside pools of 2,097,152 bytes each: 2.6 pools, as on the chip (2.25 at
    bucket 1024; PERF.md section 6, PR 29); now they hold the prompt's own
    K/V."""
    eng = _big_pool_engine()
    i32 = lambda *shape: jnp.zeros(shape, jnp.int32)
    key = jax.random.PRNGKey(0)
    if program == "tail":
        jitted = eng._tail_prefill(32, 2)
        args = (eng.params, eng.cache, i32(32), jnp.int32(32), jnp.int32(40),
                i32(2), i32(2), key, jnp.zeros(1), jnp.ones(1), i32(1))
    else:
        k = int(program[-1])
        jitted = eng._prefill(32, k)
        args = (eng.params, eng.cache, i32(k, 32), jnp.ones(k, jnp.int32),
                i32(k, 2), key, jnp.zeros(k), jnp.ones(k), i32(k))
    temp_bytes = jitted.lower(*args).compile().memory_analysis().temp_size_in_bytes
    assert 0 <= temp_bytes < eng.cache[0].nbytes // 2, (temp_bytes, eng.cache[0].nbytes)


def test_first_prefill_entry_of_warmup_log_carries_temp_bytes():
    """warmup() compiles its first prefill program ahead anyway (for
    ``mosaic``): that entry says what the program holds beside its
    arguments; the others are not compiled a second time for it."""
    eng = _big_pool_engine()
    eng.warmup(k_values=(2, 1))
    prefill = [p for p in eng.warmup_log if p["program"] == "prefill"]
    assert [(p["bucket"], p["k"]) for p in prefill] == [(32, 2), (32, 1), (64, 2), (64, 1)]
    assert isinstance(prefill[0]["temp_bytes"], int)
    assert 0 <= prefill[0]["temp_bytes"] < eng.cache[0].nbytes // 2, prefill[0]
    assert all("temp_bytes" not in p for p in prefill[1:]), prefill


@pytest.mark.parametrize("prompt_len", [9, 20, 40])
def test_paged_prefill_into_scattered_pages_matches_contiguous(prompt_len):
    """One prefill program writes a prompt's pages wherever the free list
    puts them: a prompt that fills 1, 2 and all 3 pages of its bucket (the
    bucket's other page ids are 0, the dead sink), prefilled into scattered
    pages and then decoded, gives the tokens of the same request in a fresh,
    contiguous pool."""
    prompt = [(7 * i + 3) % 97 for i in range(prompt_len)]
    outs, tables = [], []
    for scattered in (False, True):
        eng = LLMEngine(CFG, engine_config=EngineConfig(
            max_slots=2, max_seq=128, page_size=16, total_pages=24,
            prefill_buckets=(48,), decode_block=4,
        ))
        if scattered:
            eng.free_pages = type(eng.free_pages)([19, 4, 11, 2, 23, 7, 13, 5, 17, 1])
        eng.add_request("r", prompt, 10)
        eng.step()
        tables.append(list(eng.slots[0].pages))
        toks = []
        while eng.has_work():
            for ev in eng.step().values():
                toks = ev.get("tokens", toks)
        outs.append(toks)
    n = len(tables[0])
    assert n >= -(-prompt_len // 16) and tables[0] == list(range(1, n + 1)), tables
    assert tables[1] == [19, 4, 11, 2][:n], tables
    assert len(outs[0]) == 10 and outs[0] == outs[1], outs


@pytest.mark.parametrize("prompt_len", [9, 20, 40])
def test_paged_greedy_matches_full_forward(prompt_len):
    """The engine against the model itself: a prompt that fills 1, 2 and 3
    pages is prefilled into its pages and decoded through the page table,
    and its greedy tokens are those of forward() run on the whole sequence
    again for every token (no cache at all)."""
    prompt = np.array([(5 * i + 2) % 97 for i in range(prompt_len)], np.int32)
    eng = LLMEngine(CFG, engine_config=EngineConfig(
        max_slots=2, max_seq=128, page_size=16, prefill_buckets=(16, 48), decode_block=4,
    ))
    assert eng.generate(prompt, max_tokens=10)["tokens"] == _naive_greedy(eng.params, prompt, 10)


def test_kv_layout_other_than_paged_is_refused():
    """kv_layout is a retired key (files under benchmarks/ still spell
    "paged"): the dense layout went in PR 30 and asking for it must not
    silently serve from another."""
    with pytest.raises(ValueError, match="removed in PR 30"):
        EngineConfig(max_slots=2, max_seq=128, kv_layout="dense")
    assert LLMEngine(CFG, engine_config=EngineConfig(
        max_slots=2, max_seq=128, page_size=16, kv_layout="paged")).paged is True


# ---------------------------------------------------------------------------
# the program's own record: request lifecycle, step phases, rings (PR 24)
# ---------------------------------------------------------------------------

MODEL_KW = dict(
    vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
    max_seq_len=128, dtype=jnp.float32, attention_impl="reference",
)
ENGINE_KW = dict(max_slots=4, max_seq=128, prefill_buckets=(16, 32),
                 page_size=16, prefix_cache=True)
ENGINE_STAMPS = ("arrived", "admitted", "first_token", "finished")


@pytest.fixture(scope="module")
def llm_server():
    """An LLMServer in this process (no cluster: it is a plain class), its
    engine's step slowed by 20 ms so that a stream's consumer is awake again
    before the next step ends."""
    from ray_tpu.llm.deployment import LLMServer

    srv = LLMServer(MODEL_KW, ENGINE_KW, warmup_buckets=(16,))
    step = srv.engine.step

    def slow_step():
        events = step()
        time.sleep(0.02)
        return events

    srv.engine.step = slow_step
    yield srv
    srv.__raytpu_exit__()


def test_perf_counter_and_monotonic_read_one_clock():
    """_Slot.arrived_at is a perf_counter reading and the lifecycle record is
    on monotonic; the benchmark's wrapper subtracts one from the other's
    twin. On this platform they are the same clock: asserted, not assumed."""
    assert (time.get_clock_info("perf_counter").implementation
            == time.get_clock_info("monotonic").implementation)
    for _ in range(5):
        a, b, c = time.monotonic(), time.perf_counter(), time.monotonic()
        assert a <= b <= c


def test_request_lifecycle_stamps_are_ordered(llm_server):
    prompts = [[3, 1, 4, 1, 5], [2, 7, 1, 8, 2, 8], [9, 9, 8]]
    outs = [None] * len(prompts)

    def consume(i):
        outs[i] = list(llm_server.generate_stream(prompts[i], max_tokens=24))

    before = llm_server.stats()["trace"]["requests_total"]
    threads = [threading.Thread(target=consume, args=(i,)) for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    trace = llm_server.stats()["trace"]
    assert trace["clock"] == "monotonic" and trace["requests_total"] == before + 3
    recs = trace["requests"][-3:]
    assert sorted(r["prompt_len"] for r in recs) == [3, 5, 6]
    order = ("arrived", "admitted", "first_token", "first_emitted", "first_yielded", "finished")
    for r in recs:
        stamps = [r[k] for k in order]
        assert all(isinstance(s, float) for s in stamps), r
        assert stamps == sorted(stamps), r
        assert r["n_out"] == 24 and r["finish_reason"] == "length" and r["trace"] is None
        assert r["slot"] in range(4) and r["bucket"] == 16 and r["prefix_hit_len"] == 0
    assert all(o[-1]["finished"] and len(o[-1]["tokens"]) == 24 for o in outs)
    assert not llm_server._life, "a finished request's record stayed with the loop"


def test_one_step_request_keeps_each_threads_order(llm_server):
    """A request that finishes in the step of its first token retires (the
    engine's stamp, inside the step) before the loop has emitted that token:
    the engine's stamps are ordered among themselves, the loop's follow the
    token. A blocking generate() has no stream to yield from."""
    llm_server.generate([5, 4, 3, 2], max_tokens=1)
    r = llm_server.stats()["trace"]["requests"][-1]
    engine_side = [r[k] for k in ENGINE_STAMPS]
    assert engine_side == sorted(engine_side)
    assert r["first_token"] <= r["first_emitted"] and r["first_yielded"] is None
    assert r["n_out"] == 1


def test_exact_prefix_hit_and_abort_are_recorded(llm_server):
    prompt = list(range(1, 33))  # two whole pages: cached at retire
    llm_server.generate(prompt, max_tokens=4)
    llm_server.generate(prompt, max_tokens=4)
    hit = llm_server.stats()["trace"]["requests"][-1]
    assert hit["prefix_hit_len"] == 32 and hit["bucket"] is None
    assert hit["arrived"] <= hit["admitted"] <= hit["first_token"] <= hit["finished"]
    gen = llm_server.generate_stream([7, 7, 7], max_tokens=100)
    next(gen)
    gen.close()  # the consumer left: the loop aborts the request in the engine
    deadline = time.time() + 30
    while time.time() < deadline:
        last = llm_server.stats()["trace"]["requests"][-1]
        if last["finish_reason"] == "abort":
            break
        time.sleep(0.05)
    assert last["finish_reason"] == "abort" and last["prompt_len"] == 3 and last["finished"]
    assert not llm_server._life


def test_stats_is_cheap_when_idle_and_says_where_startup_went():
    from ray_tpu.llm.deployment import LLMServer

    srv = LLMServer(MODEL_KW, ENGINE_KW, warmup_buckets=(16,))
    try:
        took = []
        for _ in range(20):
            t0 = time.perf_counter()
            stats = srv.stats()
            took.append(time.perf_counter() - t0)
        assert min(took) < 1e-3, took
        trace, startup = stats["trace"], stats["startup"]
        assert trace["requests"] == [] and trace["steps"] == [] and trace["steps_total"] == 0
        assert trace["dropped"] == {"requests": 0, "steps": 0}
        # warm-up compiled here, in this process: the program's counter saw it
        assert trace["compiles_total"] >= 1 and len(trace["compiles"]) >= 1
        assert {(p["bucket"], p["k"]) for p in startup["programs"] if p["program"] == "prefill"} \
            == {(16, k) for k in (8, 4, 2, 1)}
        decode = [p for p in startup["programs"] if p["program"] == "decode"]
        assert {p["block"] for p in decode} == {2, 8}
        # what each decode block holds on the device beside its arguments
        assert all(isinstance(p["temp_bytes"], int) and p["temp_bytes"] >= 0 for p in decode), decode
        assert all(p["seconds"] > 0 for p in startup["programs"])
        assert startup["warmup_s"] >= sum(p["seconds"] for p in startup["programs"]) * 0.99
        assert startup["engine_init_s"] > 0 and startup["fetch_params_s"] >= 0
    finally:
        srv.__raytpu_exit__()


def _drain(eng):
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    return done


def test_step_phases_sum_to_no_more_than_the_step():
    from ray_tpu.llm import engine as engine_mod

    eng = LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW))
    for i in range(6):
        eng.add_request(f"r{i}", np.arange(3 + 5 * i, dtype=np.int32) % 97, 12)
    _drain(eng)
    snap = eng.trace_snapshot()
    steps = snap["steps"]
    assert len(steps) == snap["steps_total"] > 1 and snap["dropped"]["steps"] == 0
    for s in steps:
        assert set(s["phase_s"]) <= set(engine_mod.STEP_PHASES)
        assert all(v >= 0 for v in s["phase_s"].values())
        assert sum(s["phase_s"].values()) <= s["dur"] + 1e-9
        assert sum(s["phase_s"].values()) >= 0.9 * s["dur"]  # phases follow one another: no hole
    assert [a["t"] for a in steps] == sorted(a["t"] for a in steps)
    assert sum(s["n_admitted"] for s in steps) == 6
    assert sum(s["n_prefill"] for s in steps) >= 2  # 6 requests in 4 slots: at least two waves
    assert steps[0]["waiting"] == 6 and steps[0]["active"] == 4 and steps[0]["block"] in (2, 8)
    for phase in engine_mod.STEP_PHASES:
        assert snap["phase_s"][phase] == pytest.approx(
            sum(s["phase_s"].get(phase, 0.0) for s in steps))
    assert snap["phase_n"]["decode_fetch"] == sum(1 for s in steps if s["block"])
    assert snap["phase_n"]["prefix_lookup"] == 6


def test_step_record_counts_the_page_steps_the_decode_block_walks():
    """``live_pages`` of a step's record against the lengths and tables the
    decode program was handed: each of the block's steps, every slot's
    ceil(length / page_size) with the step's own token counted, an empty slot
    the one step it is held at; a step with no block records 0."""
    ec = EngineConfig(**ENGINE_KW)
    ps, table = ec.page_size, ec.max_seq // ec.page_size
    eng = LLMEngine(CFG, engine_config=ec)
    handed = []
    decode = eng._decode_jit

    def spy(*args):
        # (params, cache, last, lengths, page_tables, key, n_steps, ...); copies: the mirrors' buffers are reused
        handed.append((np.array(args[3]), np.array(args[4]), args[6]))
        out = decode(*args)
        # a slot without pages is held at its length, the others advance
        np.testing.assert_array_equal(
            np.asarray(out[3]), handed[-1][0] + args[6] * (handed[-1][1][:, 0] > 0))
        return out

    eng._decode_jit = spy
    # a page's first rows, exactly one full page, one row short of two pages
    for rid, n_prompt in (("a", 3), ("b", ps), ("c", 2 * ps - 1)):
        eng.add_request(rid, np.arange(n_prompt, dtype=np.int32) % 97, 40)
    _drain(eng)
    eng.step()  # nothing to do: no block
    steps = eng.trace_snapshot()["steps"]
    blocks = [s for s in steps if s["block"]]
    assert len(blocks) == len(handed) > 3
    assert sorted(handed[0][0]) == [0, 3, ps, 2 * ps - 1]  # the fourth slot is empty
    for rec, (lens, tables, n) in zip(blocks, handed):
        live = tables[:, 0] > 0
        seen = np.where(live, lens + np.arange(1, n + 1)[:, None], 1)
        assert rec["block"] == n and rec["active"] == live.sum()
        assert rec["live_pages"] == np.minimum(-(-seen // ps), table).sum()
        assert n * ec.max_slots <= rec["live_pages"] <= n * ec.max_slots * table
    n = handed[0][2]
    assert blocks[0]["live_pages"] == sum(
        1 + -(-(3 + s) // ps) + -(-(ps + s) // ps) + -(-(2 * ps - 1 + s) // ps) for s in range(1, n + 1))
    assert [s["live_pages"] for s in steps if not s["block"]] == [0] * (len(steps) - len(blocks))
    assert steps[-1]["block"] == 0 and steps[-1]["live_pages"] == 0


def test_trace_rings_are_bounded_and_count_drops(monkeypatch):
    from ray_tpu.llm import engine as engine_mod

    monkeypatch.setattr(engine_mod, "TRACE_RING", 4)
    eng = LLMEngine(CFG, engine_config=EngineConfig(max_slots=2, max_seq=128, prefill_buckets=(16,)))
    for i in range(7):
        eng.add_request(f"r{i}", np.array([1 + i, 2, 3], np.int32), 10)
    _drain(eng)
    snap = eng.trace_snapshot()
    assert len(snap["requests"]) == 4 and snap["requests_total"] == 7
    assert [r["req_id"] for r in snap["requests"]] == ["r3", "r4", "r5", "r6"]
    assert len(snap["steps"]) == 4 and snap["steps_total"] > 4
    assert snap["dropped"] == {"requests": 3, "steps": snap["steps_total"] - 4}


def test_greedy_tokens_unchanged_with_and_without_a_trace_context(engine):
    """The instrumentation changes no token: with a trace active around
    add_request and without one, the engine's greedy output is the full
    forward's (what the engine gave before it recorded anything)."""
    from ray_tpu.util import tracing

    prompt = np.array([5, 17, 42, 7, 23], np.int32)
    want = _naive_greedy(engine.params, prompt, 12)
    engine.add_request("plain", prompt, 12)
    with tracing.span("test.llm"):
        traced_life = engine.add_request("traced", prompt, 12)
    assert traced_life["trace"] is not None and traced_life["trace"][0]
    done = _drain(engine)
    assert done["plain"] == want and done["traced"] == want
    recs = {r["req_id"]: r for r in engine.trace_snapshot()["requests"]}
    assert recs["plain"]["trace"] is None and recs["traced"] is traced_life


def test_a_capture_shows_the_steps_phases_inside_llm_step(engine, tmp_path):
    """The phases are TraceAnnotations: a profiler capture with the host
    tracer on holds one `llm.step` per step and its phases inside it, one
    after another, on the capture's own clock."""
    import glob

    from jax.profiler import ProfileData

    from ray_tpu.llm.engine import STEP_PHASES

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        engine.generate(np.array([1, 2, 3, 4], np.int32), max_tokens=12)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb"))
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns) for e in line.events
              if e.name.startswith("llm.step")]
             for plane in ProfileData.from_file(path).planes for line in plane.lines]
    [events] = [evs for evs in lines if evs]  # one thread stepped the engine
    whole = [e for e in events if e[0] == "llm.step"]
    phases = sorted((e for e in events if e[0] != "llm.step"), key=lambda e: e[1])
    assert len(whole) >= 2 and phases
    assert {name for name, _s, _e in phases} <= {f"llm.step.{p}" for p in STEP_PHASES}
    assert {"llm.step.admit", "llm.step.prefill_dispatch", "llm.step.prefill_fetch",
            "llm.step.decode_dispatch", "llm.step.decode_fetch", "llm.step.emit"} \
        <= {name for name, _s, _e in phases}
    for name, start, end in phases:
        assert any(s <= start and end <= e for _n, s, e in whole), name
    for (_n, _s, end), (_m, start, _e) in zip(phases, phases[1:]):
        assert end <= start  # never nested, never overlapping
