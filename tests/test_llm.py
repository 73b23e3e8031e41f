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
    # 32 and max_seq, and the rung between them (engine.bucket_ladder)
    assert [(p["bucket"], p["k"]) for p in prefill] == [(32, 2), (32, 1), (48, 2), (48, 1), (64, 2), (64, 1)]
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
    LLMEngine(CFG, engine_config=EngineConfig(max_slots=2, max_seq=128, page_size=16, kv_layout="paged"))


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


def test_ttft_is_observed_once_for_a_request_of_many_steps(llm_server, monkeypatch):
    """The engine writes ttft_s into a request's closing event too: the loop
    observes serve.ttft_s where it first stamps `first_emitted`, once."""
    observed = []
    monkeypatch.setattr(llm_server, "_ttft_hist", type("Hist", (), {"observe": staticmethod(observed.append)}))
    before = llm_server.stats()["trace"]["steps_total"]
    out = llm_server.generate([8, 6, 7, 5, 3, 0, 9], max_tokens=20)  # a first token and three blocks
    assert llm_server.stats()["trace"]["steps_total"] - before >= 3 and len(out["tokens"]) == 20
    assert observed == [out["ttft_s"]]
    llm_server.generate([5, 4, 3, 2, 1], max_tokens=1)  # first and last token in one event
    assert len(observed) == 2


def test_startup_stamps_bracket_what_startup_took(llm_server):
    """init_began / init_ended: on time.monotonic(), the lifecycle stamps'
    clock, around the three durations and next to nothing else (the imports and
    the backend lie before the first), so a client's set-up is what went
    before, the three, and what came after."""
    startup = llm_server.stats()["startup"]
    took = startup["fetch_params_s"] + startup["engine_init_s"] + startup["warmup_s"]
    assert startup["init_began"] + took <= startup["init_ended"] <= time.monotonic()
    assert startup["init_ended"] - startup["init_began"] - took < 1.0
    first = llm_server.stats()["trace"]["requests"]
    assert all(r["arrived"] >= startup["init_ended"] for r in first)


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


def test_step_records_carry_both_clocks_and_the_pages_slots_hold(cpu_tick):
    """Every step record has the stepping thread's CPU seconds beside its wall
    seconds, phase for phase, and the thread's CPU clock at its start; and
    `pages_reserved`, the pages slots hold once the step has admitted: with
    the free pages and the prefix cache's as they stand then (read where the
    decode dispatch begins, before any fetch retires a slot) it is the whole
    pool but the dead page. Ten requests on four slots, two prompts repeated.
    The CPU clock may step by a tick (`cpu_tick`: 10 ms on the chip's host) and
    then reads up to a tick over the wall clock: held to what both kinds keep."""
    eng = LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW))
    seen = []
    dispatch = eng._dispatch_decode

    def spy(ph, events):
        held = sum(len(s.pages) for s in eng.slots if s is not None)
        seen.append((len(eng.free_pages), len(eng._page_refs), held))
        return dispatch(ph, events)

    eng._dispatch_decode = spy
    prompts = [np.arange(3 + 7 * (i % 5), dtype=np.int32) % 97 for i in range(10)]
    for i, prompt in enumerate(prompts):
        eng.add_request(f"r{i}", prompt, 6 + 3 * i)
    _drain(eng)
    eng.step()  # nothing to do: no slot holds a page
    snap = eng.trace_snapshot()
    steps = snap["steps"]
    assert snap["pages_total"] == eng.ec.total_pages - 1 and len(steps) == len(seen) > 5
    for rec, (free, cached, held) in zip(steps, seen):
        assert list(rec["phase_cpu_s"]) == list(rec["phase_s"])
        assert all(cpu >= 0 for cpu in rec["phase_cpu_s"].values()), rec
        assert sum(rec["phase_cpu_s"].values()) <= rec["dur"] + 1e-3 + cpu_tick, rec
        assert rec["pages_reserved"] == held
        assert rec["pages_reserved"] + free + cached == snap["pages_total"]
    assert max(c for _f, c, _h in seen) > 0 and eng.prefix_cache_stats["hits"] >= 1  # the cache did hold pages
    assert max(s["pages_reserved"] for s in steps) > steps[-1]["pages_reserved"] == 0
    cpu_t = [s["cpu_t"] for s in steps]
    assert cpu_t == sorted(cpu_t) and cpu_t[-1] > cpu_t[0]
    for a, b in zip(steps, steps[1:]):  # a step's CPU lies between its start and the next one's
        assert b["cpu_t"] - a["cpu_t"] >= sum(a["phase_cpu_s"].values()) - 1e-6
    # from the first step's start to the last one's end the thread had no more CPU than time
    ran = steps[-1]["cpu_t"] + sum(steps[-1]["phase_cpu_s"].values()) - cpu_t[0]
    assert 0 < ran <= steps[-1]["t"] + steps[-1]["dur"] - steps[0]["t"] + 1e-3 + cpu_tick


def _life_of(eng, rid):
    return next(r for r in eng.trace_snapshot()["requests"] if r["req_id"] == rid)


@pytest.mark.parametrize("how", ["cold", "tail", "last_chunk", "exact_hit"])
def test_prefill_enqueued_lies_between_admission_and_the_first_token(how):
    """`prefill_enqueued`: the call that enqueues the request's prefill program
    has returned. A whole prompt's group, a partial prefix hit's tail program
    and a chunked prompt's last chunk each stamp it; an exact hit has no
    prefill and no stamp."""
    chunked = dict(chunked_prefill=16) if how == "last_chunk" else {}
    eng = LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, **chunked))
    base = (np.arange(40, dtype=np.int32) * 7 + 1) % 97
    if how in ("tail", "exact_hit"):  # a first request leaves its prompt's pages in the prefix cache
        eng.add_request("first", base, 4)
        _drain(eng)
    prompt = np.concatenate([base[:32], np.array([5, 6, 7], np.int32)]) if how == "tail" else base
    eng.add_request("r", prompt, 4)
    _drain(eng)
    life = _life_of(eng, "r")
    assert life["prefix_hit_len"] == {"cold": 0, "tail": 32, "last_chunk": 0, "exact_hit": 40}[how]
    if how == "exact_hit":
        assert life["prefill_enqueued"] is None and life["admitted"] <= life["first_token"]
        return
    assert life["admitted"] <= life["prefill_enqueued"] <= life["first_token"] <= life["finished"]
    if how == "last_chunk":
        # three chunks of 16, one a step: the stamp is the last one's, two steps after admission
        steps = [s["t"] for s in eng.trace_snapshot()["steps"] if s["t"] >= life["arrived"]]
        assert steps[0] <= life["admitted"] <= steps[1] <= steps[2] <= life["prefill_enqueued"]


def test_step_record_counts_the_grid_steps_the_decode_block_walks():
    """``live_pages`` and ``grid_steps`` of a step's record against the
    lengths and tables the decode program was handed: each of the block's
    steps, every slot's ceil(length / page_size) pages with the step's own
    token counted, in groups of the kernel's page group, an empty slot
    nothing; a step with no block records 0."""
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
    group = eng.rules[0].group
    assert group > 1  # so that a step holds more than a page
    for rec, (lens, tables, n) in zip(blocks, handed):
        live = tables[:, 0] > 0
        seen = np.where(live, lens + np.arange(1, n + 1)[:, None], 0)
        pages = np.minimum(-(-seen // ps), table)
        assert rec["block"] == n and rec["active"] == live.sum()
        assert rec["live_pages"] == pages.sum() and rec["grid_steps"] == (-(-pages // group)).sum()
        assert n * live.sum() <= rec["grid_steps"] <= rec["live_pages"] <= n * live.sum() * table
    n = handed[0][2]
    assert blocks[0]["live_pages"] == sum(
        -(-(3 + s) // ps) + -(-(ps + s) // ps) + -(-(2 * ps - 1 + s) // ps) for s in range(1, n + 1))
    assert blocks[0]["grid_steps"] == 3 * n < blocks[0]["live_pages"]  # a sequence of two pages takes one step
    for key in ("live_pages", "grid_steps"):
        assert [s[key] for s in steps if not s["block"]] == [0] * (len(steps) - len(blocks))
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


# ---------------------------------------------------------------------------
# The one-block look-ahead: a step enqueues its decode block before it fetches
# the one before, so the host is a block behind the device and never more.
# ---------------------------------------------------------------------------

AHEAD_KW = dict(max_slots=3, max_seq=128, page_size=16, prefill_buckets=(16, 32), decode_block=8)
AHEAD_PROMPTS = [(np.arange(3 + 2 * i, dtype=np.int32) * (i + 3) + i) % 97 for i in range(7)]


def _until_eos(tokens, eos):
    return tokens[: tokens.index(eos) + 1] if eos in tokens else tokens


def _staggered(eng, prompts, max_tokens, every=2):
    """Requests added one every ``every`` steps and run to their ends:
    id -> (tokens, finish_reason, the streamed new_tokens joined)."""
    done, streamed = {}, {}
    pending = list(enumerate(prompts))
    steps = 0
    while pending or eng.has_work():
        if pending and steps % every == 0:
            i, p = pending.pop(0)
            eng.add_request(f"r{i}", p, max_tokens)
        for rid, ev in eng.step().items():
            streamed.setdefault(rid, []).extend(ev.get("new_tokens", []))
            if ev.get("finished"):
                done[rid] = (ev["tokens"], ev["finish_reason"])
        steps += 1
    return {rid: (toks, why, streamed[rid]) for rid, (toks, why) in done.items()}


@pytest.fixture(scope="module")
def ahead_solos():
    """Each prompt's 20 greedy tokens, run alone (the first by the plain full
    forward too)."""
    eng = LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW))
    solos = [eng.generate(p, max_tokens=20)["tokens"] for p in AHEAD_PROMPTS]
    assert solos[0] == _naive_greedy(eng.params, AHEAD_PROMPTS[0], 20)
    return solos


def _mid_block_eos(solos):
    """A token some request emits inside a block of 8 and not at its end."""
    for toks in solos:
        for at, tok in enumerate(toks[1:], start=1):  # token `at` comes out of decode step at - 1
            if (at - 1) % 8 != 7 and at >= 3:
                return tok
    raise AssertionError("no candidate")


@pytest.mark.parametrize("with_eos", [False, True], ids=["budget", "eos_mid_block"])
def test_a_block_ahead_staggered_requests_and_reused_slots_match_their_solo_runs(ahead_solos, with_eos):
    """7 requests through 3 slots, one added every other step, blocks of 8:
    every request's tokens, finish reason and count are what it gives run
    alone, whether it ends by its budget or by an EOS inside a
    block; what was streamed is what was returned."""
    eos = _mid_block_eos(ahead_solos) if with_eos else -1
    eng = LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW, eos_id=eos))
    got = _staggered(eng, AHEAD_PROMPTS, 20)
    assert len(got) == len(AHEAD_PROMPTS)
    n_stopped = 0
    for i, solo in enumerate(ahead_solos):
        want = _until_eos(solo, eos)
        toks, why, streamed = got[f"r{i}"]
        assert toks == want and streamed == want, i
        assert why == ("stop" if want[-1] == eos else "length"), i
        n_stopped += why == "stop"
    assert n_stopped >= (1 if with_eos else 0)
    steps = eng.trace_snapshot()["steps"]
    blocks = [s for s in steps if s["block"]]
    assert sum(s["ahead"] for s in blocks) >= len(blocks) // 2  # the mechanism engages
    assert {s["ahead"] for s in steps} <= {0, 1} and all(not s["ahead"] for s in steps if not s["block"])
    lives = {r["req_id"]: r for r in eng.trace_snapshot()["requests"]}
    assert all(lives[f"r{i}"]["n_out"] == len(got[f"r{i}"][0]) for i in range(len(AHEAD_PROMPTS)))
    assert not eng.has_work() and eng._inflight is None and len(eng.free_pages) == eng.ec.total_pages - 1


def test_the_kernels_page_groups_with_empty_and_retiring_slots_emit_the_reference_paths_tokens(ahead_solos, monkeypatch):
    """The decode program traced as on a TPU (the step's walks of page
    groups, the Pallas kernel, here interpreted) under the same staggered
    traffic: 7 requests through 3 slots, so blocks go out with slots empty
    (lengths of 0: no grid step, no row written, zeros attended) and with
    rows that an EOS retires inside a block. Request for request the tokens
    of the reference path; a block's record holds no more grid steps than
    pages, and pages of its active slots alone."""
    import functools

    from ray_tpu.llm import cache_rules
    from ray_tpu.ops.paged_attention import paged_attention

    eos = _mid_block_eos(ahead_solos)
    eng = LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW, eos_id=eos))
    assert eng.rules[0].group > 1

    def as_on_a_tpu(*args):
        with monkeypatch.context() as m:  # while the program is traced, and no longer
            m.setattr(jax, "default_backend", lambda: "tpu")
            m.setattr(cache_rules, "paged_attention", functools.partial(paged_attention, interpret=True))
            return eng._decode_impl(*args)

    eng._decode_jit = jax.jit(as_on_a_tpu, donate_argnums=(1,), static_argnums=(6,))
    got = _staggered(eng, AHEAD_PROMPTS, 20)
    assert [got[f"r{i}"][0] for i in range(7)] == [_until_eos(s, eos) for s in ahead_solos]
    blocks = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    assert any(s["active"] < eng.ec.max_slots for s in blocks) and sum(s["dropped_rows"] for s in blocks) >= 1
    table = eng.ec.max_seq // eng.ec.page_size
    for s in blocks:
        assert s["block"] * s["active"] <= s["grid_steps"] <= s["live_pages"] <= s["block"] * s["active"] * table
    assert any(s["grid_steps"] < s["live_pages"] for s in blocks)  # a step that held more than a page


def test_a_block_ahead_a_finished_rows_tokens_never_reach_the_slots_next_request(ahead_solos):
    """One slot, two requests queued: the first ends by EOS in block s - 1, which
    the host finds out after block s went to the device with the row still
    live; the second takes the slot in the next step, before block s is
    walked. That step's record counts the dropped row, and the second
    request's tokens are its own."""
    first, second = ahead_solos[2], ahead_solos[3]
    eos = next(t for t in first[2:] if t not in second)
    eng = LLMEngine(CFG, engine_config=EngineConfig(**{**AHEAD_KW, "max_slots": 1}, eos_id=eos))
    eng.add_request("first", AHEAD_PROMPTS[2], 20)
    eng.add_request("second", AHEAD_PROMPTS[3], 20)
    done = _drain(eng)
    assert done["first"] == _until_eos(first, eos) and done["first"][-1] == eos
    assert done["second"] == second
    steps = eng.trace_snapshot()["steps"]
    readmit = next(k for k, s in enumerate(steps) if s["n_admitted"] and k > 0)
    # the step that admitted `second` walked the block `first`'s row rode along in
    assert steps[readmit]["dropped_rows"] == 1 and steps[readmit - 1]["block"] and steps[readmit - 1]["ahead"]
    assert sum(s["dropped_rows"] for s in steps) >= 1


def test_a_block_ahead_the_hosts_lengths_are_the_ones_the_decode_program_is_handed(ahead_solos):
    """At every call of the decode program ``lengths[active]`` equals the
    ``d_lengths`` rows it is handed (what ``cap``, ``live_pages`` and the
    benchmark's wrapper read there), a slot the host has retired is dead on
    the device too, and the record's ``live_pages`` is of those lengths."""
    eos = _mid_block_eos(ahead_solos)
    ec = EngineConfig(**AHEAD_KW, eos_id=eos)
    eng = LLMEngine(CFG, engine_config=ec)
    ps, table = ec.page_size, ec.max_seq // ec.page_size
    decode, calls = eng._decode_jit, []

    def spy(*args):
        active = [i for i, s in enumerate(eng.slots) if s is not None and i not in eng._prefilling]
        d_lengths, d_tables, n = np.array(args[3]), np.array(args[4]), args[6]
        np.testing.assert_array_equal(eng.lengths[active], d_lengths[active])
        np.testing.assert_array_equal(eng.page_tables, d_tables)
        assert sorted(active) == sorted(np.flatnonzero(d_tables[:, 0] > 0))
        seen = np.where(d_tables[:, 0] > 0, d_lengths + np.arange(1, n + 1)[:, None], 0)
        calls.append((n, int(np.minimum(-(-seen // ps), table).sum())))
        return decode(*args)

    eng._decode_jit = spy
    got = _staggered(eng, AHEAD_PROMPTS, 20)
    assert [got[f"r{i}"][0] for i in range(7)] == [_until_eos(s, eos) for s in ahead_solos]
    blocks = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    assert [(s["block"], s["live_pages"]) for s in blocks] == calls and len(calls) > 5


def test_a_block_ahead_abort_and_set_params_absorb_the_block_in_flight(ahead_solos):
    """``abort`` of a running request and ``set_params`` find a block the
    device still holds: they walk it first (its tokens are not lost: the next
    step hands them out), and ``has_work`` stays true until the last block is
    walked and its events returned."""
    eng = LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW))
    streamed, done = {}, {}

    def step():
        events = eng.step()
        for rid, ev in events.items():
            streamed.setdefault(rid, []).extend(ev.get("new_tokens", []))
            if ev.get("finished"):
                done[rid] = ev["tokens"]
        return events

    eng.add_request("keep", AHEAD_PROMPTS[1], 20)
    eng.add_request("gone", AHEAD_PROMPTS[4], 20)
    step()
    step()
    assert eng._inflight is not None
    free = len(eng.free_pages)
    eng.abort("gone")
    assert eng._inflight is None and "keep" in eng._carry and "gone" not in eng._carry
    assert len(eng.free_pages) > free and sum(s is not None for s in eng.slots) == 1
    gone = next(r for r in eng.trace_snapshot()["requests"] if r["req_id"] == "gone")
    assert gone["finish_reason"] == "abort" and gone["n_out"] == 1 + 8 + 8  # both blocks it rode were walked
    np.testing.assert_array_equal(np.asarray(eng.d_page_tables), eng.page_tables)
    step()
    assert eng._inflight is not None
    eng.set_params(eng.params)  # same weights: the tokens must not change
    assert eng._inflight is None and eng.has_work()
    while eng.has_work():
        step()
    assert done == {"keep": ahead_solos[1]} and streamed["keep"] == ahead_solos[1]
    # the end of work: a request that ended by its budget leaves nothing on the device...
    assert eng._inflight is None and not eng._carry
    # ...one that ends by EOS does: the step that returns its last event still holds a block
    eos = _mid_block_eos(ahead_solos)
    eng = LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW, eos_id=eos))
    i = next(k for k, s in enumerate(ahead_solos) if eos in s[1:])
    eng.add_request("x", AHEAD_PROMPTS[i], 20)
    events = {}
    while not events.get("x", {}).get("finished"):
        events = eng.step()
    assert eng._inflight is not None and eng.has_work() and all(s is None for s in eng.slots)
    assert eng.step() == {} and not eng.has_work()
    assert eng.trace_snapshot()["steps"][-1]["dropped_rows"] == 1
    assert eng.generate(AHEAD_PROMPTS[i], max_tokens=20)["tokens"] == _until_eos(ahead_solos[i], eos)
    assert eng._inflight is None  # generate() leaves no block behind


def test_a_block_ahead_a_write_past_a_rows_reservation_lands_in_the_dead_page(ahead_solos):
    """A finished row rides up to two blocks past its budget, one more than
    ``_pages_needed`` reserves: what it writes past its last page goes through
    the zero tail of its table row to page 0 and nowhere else. A prompt of 5
    with 3 tokens reserves one page of 16 (5 + 3 + 8); beside a long request
    its row is written up to position 20."""
    ec = EngineConfig(**{**AHEAD_KW, "total_pages": 12})
    eng = LLMEngine(CFG, engine_config=ec)
    ps = ec.page_size
    short = AHEAD_PROMPTS[1]
    assert len(short) == 5 and eng._pages_needed(5, 3) == 1
    eng.add_request("long", AHEAD_PROMPTS[5], 20)
    eng.add_request("short", short, 3)
    owned, past = set(), []
    decode = eng._decode_jit

    def spy(*args):
        for i, s in enumerate(eng.slots):
            if s is not None:
                owned.update(s.pages)
                past.append(int(eng.lengths[i]) + args[6] - len(s.pages) * ps)  # the block's last write + 1
        return decode(*args)

    eng._decode_jit = spy
    done = _drain(eng)
    assert max(past) == 5 + 16 - ps  # the device wrote the short row's positions 16..20 past its one page
    assert done == {"long": ahead_solos[5], "short": ahead_solos[1][:3]}
    pool = np.asarray(eng.cache[0])  # [L, KV, pages * ps, Hd]
    touched = {int(p) for p in np.flatnonzero(np.abs(pool).sum(axis=(0, 1, 3)).reshape(-1, ps).sum(axis=1))}
    assert 0 in touched and touched <= owned | {0}


def _events_of(eng, rids):
    """Run to the end of work: every event each request of ``rids`` was sent."""
    seen = {rid: [] for rid in rids}
    while eng.has_work():
        for rid, ev in eng.step().items():
            seen[rid].append(ev)
    return seen


@pytest.fixture(scope="module")
def ahead_engine():
    """One engine for the context-cap cases: each leaves it empty."""
    return LLMEngine(CFG, engine_config=EngineConfig(**AHEAD_KW))


@pytest.mark.parametrize("beside", [False, True], ids=["alone", "beside_a_running_row"])
@pytest.mark.parametrize("short_of_max_seq", [2, 1])
def test_a_block_ahead_a_prompt_that_fills_the_context_ends_with_its_first_token(
        ahead_engine, ahead_solos, short_of_max_seq, beside):
    """A prompt of max_seq - 2 or max_seq - 1 tokens leaves no room for the
    smallest compiled block. The step that admits it enqueues (or finds it
    cannot enqueue) its decode block before the prefill's token is fetched:
    the request must still end as it did when the fetch came first, in one
    event with its first token and ``length``, and a row running beside it
    keeps its own tokens."""
    eng = ahead_engine
    prompt = (np.arange(eng.ec.max_seq - short_of_max_seq, dtype=np.int32) * 7 + 3) % 97
    t0 = _naive_greedy(eng.params, prompt, 1)
    early = []
    if beside:
        eng.add_request("run", AHEAD_PROMPTS[1], 20)
        early = [eng.step()["run"], eng.step()["run"]]
        assert eng._inflight is not None
    eng.add_request("full", prompt, 20)
    seen = _events_of(eng, ["full", "run"])
    seen["run"] = early + seen["run"]
    assert len(seen["full"]) == 1
    ev = seen["full"][0]
    assert (ev["new_tokens"], ev["tokens"], ev["finished"], ev["finish_reason"]) == (t0, t0, True, "length")
    assert ev["ttft_s"] is not None and ev["ttft_s"] > 0
    if beside:
        assert [t for e in seen["run"] for t in e.get("new_tokens", [])] == ahead_solos[1]
        assert seen["run"][-1]["finish_reason"] == "length"
    life = [r for r in eng.trace_snapshot()["requests"] if r["req_id"] == "full"][-1]
    assert (life["n_out"], life["finish_reason"]) == (1, "length")
    assert eng._inflight is None and len(eng.free_pages) == eng.ec.total_pages - 1
    np.testing.assert_array_equal(np.asarray(eng.d_page_tables), 0)


def test_a_block_ahead_no_block_goes_out_over_a_row_the_context_cap_path_retired(ahead_engine, ahead_solos):
    """Two rows. The long one's budget ends exactly where its lengths leave
    less than the smallest block of headroom, so the step finds no fit with a
    block in flight, absorbs it, and the walk retires that row; the block it
    then enqueues for the other row must meet mirrors without the retired
    row, not its lengths two short of max_seq over pages the host has freed."""
    eng = ahead_engine
    long_prompt = (np.arange(100, dtype=np.int32) * 5 + 1) % 97
    decode, calls = eng._decode_jit, []

    def spy(*args):
        active = [i for i, s in enumerate(eng.slots) if s is not None and i not in eng._prefilling]
        d_lengths, d_tables = np.array(args[3]), np.array(args[4])
        np.testing.assert_array_equal(eng.lengths[active], d_lengths[active])
        np.testing.assert_array_equal(eng.page_tables, d_tables)
        assert int(d_lengths.max()) + args[6] <= eng.ec.max_seq - 1
        calls.append(len(active))
        return decode(*args)

    before = eng.trace_snapshot()["steps_total"]
    eng._decode_jit = spy
    try:
        eng.add_request("long", long_prompt, 27)  # 100 + 8 + 8 + 8 + 2 = 126: cap 1 after 1 + 26 tokens
        eng.add_request("other", AHEAD_PROMPTS[1], 60)
        done = _drain(eng)
    finally:
        eng._decode_jit = decode
    steps = eng.trace_snapshot()["steps"][before - eng.trace_snapshot()["steps_total"]:]
    assert len(done["long"]) == 27 and done["long"] == eng.generate(long_prompt, max_tokens=27)["tokens"]
    assert done["other"][:20] == ahead_solos[1]
    assert done["other"] == eng.generate(AHEAD_PROMPTS[1], max_tokens=60)["tokens"]
    # the step that absorbed before it dispatched: a block, not ahead, behind a step that was
    forced = [k for k, s in enumerate(steps) if s["block"] and not s["ahead"] and k and steps[k - 1]["ahead"]]
    assert forced and 1 in calls and 2 in calls
