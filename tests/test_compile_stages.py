"""A start's seconds by stage: what accel/device.compile_stages counts of
JAX's own reports (tracing, lowering, the backend; the compile cache's
verdict), what LLMEngine.warmup writes of them into each entry of
warmup_log, and what LLMServer's start-up record says with them. Tiny
programs on the CPU: what is held here is which seconds are counted and
how often, never how many."""
import time

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.accel import device
from ray_tpu.llm import EngineConfig, LLMEngine
from ray_tpu.llm.engine import WARMUP_STAGES
from ray_tpu.models import TransformerConfig

STAGES = {"trace_s", "lower_s", "backend_s", "miss_s", "retrieval_s", "traces", "hits", "misses", "executables"}
MODEL_KW = dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2, d_ff=128,
                max_seq_len=128, dtype=jnp.float32, attention_impl="reference")
ENGINE_KW = dict(max_slots=4, max_seq=128, prefill_buckets=(16, 32), page_size=16, prefix_cache=True)
EPS = 1e-9  # differences of cumulative floats


def _since(before):
    after = device.compile_stages()
    return {key: after[key] - before[key] for key in before}


@pytest.fixture()
def own_cache(tmp_path):
    """The persistent cache in a directory of the test's own, with nothing too
    small or too quick to be written; what was configured comes back after."""
    from jax.experimental.compilation_cache import compilation_cache

    names = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    was = {name: getattr(jax.config, name) for name in names}
    for name, value in zip(names, (str(tmp_path), 0, 0)):
        jax.config.update(name, value)
    compilation_cache.reset_cache()
    device._count_compiles()  # listen; enable_compile_cache would place the cache elsewhere
    try:
        yield tmp_path
    finally:
        for name, value in was.items():
            jax.config.update(name, value)
        compilation_cache.reset_cache()


def test_compile_stages_has_its_keys_and_compile_events_keeps_its_shape():
    device._count_compiles()
    x = jnp.arange(4.0)
    before, counted = device.compile_stages(), device.compile_events()["count"]
    jax.jit(lambda v: v * 5.0 - 2.0)(x).block_until_ready()
    took = _since(before)
    assert set(took) == STAGES
    assert took["executables"] == took["hits"] + took["misses"] == 1
    assert took["trace_s"] > 0 and took["lower_s"] > 0 and took["backend_s"] > 0 and took["traces"] == 0
    assert 0 <= took["miss_s"] <= took["backend_s"] + EPS and 0 <= took["retrieval_s"] <= took["backend_s"] + EPS
    events = device.compile_events()
    assert set(events) == {"count", "recent"} and events["count"] == counted + 1
    stamp, seconds = events["recent"][-1]
    assert stamp <= time.monotonic() and seconds == pytest.approx(took["backend_s"])
    # a reading is the caller's own: what is counted later does not move it
    assert device.compile_stages() is not device.compile_stages()


def test_a_nested_jits_trace_is_counted_once_with_its_callers():
    """JAX reports the inner function's trace first and inside the outer's:
    the total takes the outermost event's seconds, which hold the others."""
    device._count_compiles()
    reported = []

    def listen(name, seconds, **_kw):
        if name == device.TRACE_EVENT:
            reported.append(seconds)

    @jax.jit
    def inner(v):
        time.sleep(0.05)  # Python that runs as the function is traced
        return jnp.tanh(v @ v)

    @jax.jit
    def outer(v):
        return inner(v) + inner(v * 2.0) + 1.0

    x = jnp.ones((8, 8))
    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        before = device.compile_stages()
        outer(x).block_until_ready()
        took = _since(before)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert len(reported) >= 2 and max(reported) == reported[-1] >= 0.05  # the caller's ends last, around the rest
    assert took["trace_s"] == pytest.approx(reported[-1]) and took["trace_s"] < sum(reported)
    assert took["executables"] == 1  # one program: the inner function is part of it
    # JAX reports an event for the inner function's second call too, which its trace cache served, and for
    # whatever of jax.numpy the two called: events are no count of traces, and none of these is a kernel's
    assert len(reported) >= 3 and took["traces"] == 0


def _toy_kernel(v):
    """A pallas_call as ray_tpu/ops makes them, in interpret mode: the body doubles a block."""
    from jax.experimental import pallas as pl

    def body(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    return pl.pallas_call(body, out_shape=jax.ShapeDtypeStruct(v.shape, v.dtype), interpret=True, name="toy")(v)


def test_traces_counts_the_kernel_bodies_the_trace_cache_did_not_serve():
    """Two readings' difference is the kernel bodies traced between them,
    nested as they always are inside a program's trace: a call inside a jit
    with one identity is traced once a shape signature however often and from
    whatever program it is made, the bare call every time, and a function
    that holds no kernel never counts, traced or served."""
    device._count_compiles()
    kept = jax.jit(_toy_kernel, inline=True)

    def program(op):
        return jax.jit(lambda v: op(op(op(v))))

    x, other = jnp.ones((8, 128)), jnp.ones((16, 128))

    def traced(op, operand):
        before = device.compile_stages()
        program(op).lower(operand)
        return _since(before)["traces"]

    assert traced(kept, x) == 1  # three calls, one body
    assert traced(kept, x) == 0  # another program of equal shapes: served
    assert traced(kept, other) == 1  # another shape: the body again
    assert traced(_toy_kernel, x) == 3  # the bare call: a trace a call
    assert traced(jax.jit(jnp.tanh, inline=True), x) == 0  # no kernel


def test_a_second_call_of_a_compiled_program_adds_nothing():
    device._count_compiles()
    fn = jax.jit(lambda v: jnp.sin(v) * 7.0)
    x = jnp.arange(6.0)
    y = x + 1.0  # an eager operation is a program too: before the reading
    fn(x).block_until_ready()
    before = device.compile_stages()
    fn(x).block_until_ready()
    fn(y).block_until_ready()  # same shape and type: the program is there
    assert device.compile_stages() == before


def test_a_read_from_the_cache_is_a_hit_and_a_compile_a_miss(own_cache):
    """One small program twice, the in-memory caches cleared between: the
    first call compiles and writes, the second reads. Both are backend
    events; only the first is a miss and only its seconds are miss_s."""
    fn = jax.jit(lambda v: jnp.cos(v) * 11.0 + 13.0)
    x = jnp.arange(12.0)
    before = device.compile_stages()
    fn(x).block_until_ready()
    first = _since(before)
    assert (first["executables"], first["misses"], first["hits"]) == (1, 1, 0)
    assert first["miss_s"] == pytest.approx(first["backend_s"]) and first["retrieval_s"] == 0
    assert any(own_cache.iterdir())  # written
    jax.clear_caches()
    before = device.compile_stages()
    fn(x).block_until_ready()
    second = _since(before)
    assert (second["executables"], second["misses"], second["hits"]) == (1, 0, 1)
    assert second["miss_s"] == 0 and 0 < second["retrieval_s"] <= second["backend_s"] + EPS
    assert second["trace_s"] > 0 and second["lower_s"] > 0  # a hit saves the compile, not the way to it


# -- warm-up's entries ---------------------------------------------------------

@pytest.fixture(scope="module")
def warmed():
    device._count_compiles()
    eng = LLMEngine(TransformerConfig(**MODEL_KW), engine_config=EngineConfig(**ENGINE_KW))
    before = device.compile_stages()
    eng.warmup(buckets=(16,))
    return eng, _since(before)


def test_warmup_warms_the_programs_it_warmed_in_their_order(warmed):
    """The (program, bucket, k | block) sequence of the tree before the stages
    were written into the entries."""
    eng, _took = warmed
    got = [(p["program"], p.get("bucket"), p.get("k", p.get("block"))) for p in eng.warmup_log]
    assert got == [("prefill", 16, 8), ("prefill", 16, 4), ("prefill", 16, 2), ("prefill", 16, 1),
                   ("decode", None, 2), ("decode", None, 8), ("drop_rows", None, None), ("copy_pages", None, None)]


def test_every_warmup_entry_lays_its_seconds_to_stages(warmed):
    eng, took = warmed
    log = eng.warmup_log
    for p in log:
        assert set(WARMUP_STAGES) | {"t", "seconds"} <= set(p), p
        assert all(p[key] >= 0 for key in WARMUP_STAGES), p
        assert p["trace_s"] + p["lower_s"] + p["backend_s"] <= p["seconds"] + EPS, p
        assert p["miss_s"] <= p["backend_s"] + EPS and p["misses"] <= p["executables"], p
    # every program was new to this process: each entry traced, lowered and started an executable
    assert all(p["trace_s"] > 0 and p["lower_s"] > 0 and p["executables"] >= 1 for p in log)
    # the entries follow one another on the clock and none holds another's seconds
    assert all(a["t"] + a["seconds"] <= b["t"] + EPS for a, b in zip(log, log[1:]))
    # and together they hold no more than warm-up did
    for key in WARMUP_STAGES:
        assert sum(p[key] for p in log) <= took[key] + 1e-6, key


def test_a_decode_entry_brackets_the_compile_ahead_and_the_call(warmed):
    """`compiled_ahead` and the jitted call lie inside one entry, so a block
    that is lowered or started twice says so: at least the one executable and
    the one lowering a block needs are there."""
    eng, _took = warmed
    decode = [p for p in eng.warmup_log if p["program"] == "decode"]
    assert [p["block"] for p in decode] == list(eng.block_sizes)
    assert all(p["executables"] >= 1 and p["lower_s"] > 0 and isinstance(p["temp_bytes"], int) for p in decode)


# -- the replica's start-up record ----------------------------------------------

@pytest.fixture(scope="module")
def startup():
    from ray_tpu.llm.deployment import LLMServer

    srv = LLMServer(MODEL_KW, ENGINE_KW, warmup_buckets=(16,))
    try:
        yield srv.stats()["startup"]
    finally:
        srv.__raytpu_exit__()


def test_the_start_up_record_reaches_back_to_the_constructors_first_statement(startup):
    assert startup["ctor_began"] <= startup["init_began"] <= startup["init_ended"] <= time.monotonic()
    assert startup["init_began"] - startup["ctor_began"] < 60


def test_the_start_up_record_lays_its_durations_to_stages(startup):
    stages = startup["stages"]
    assert list(stages) == ["before", "engine_init", "warmup"]
    assert all(set(part) == STAGES for part in stages.values())
    assert all(value >= 0 for part in stages.values() for value in part.values())
    for part, seconds in (("engine_init", startup["fetch_params_s"] + startup["engine_init_s"]),
                          ("warmup", startup["warmup_s"])):
        assert sum(stages[part][key] for key in ("trace_s", "lower_s", "backend_s")) <= seconds + EPS, part
    # warm-up's entries are inside warm-up
    for key in WARMUP_STAGES:
        assert sum(p[key] for p in startup["programs"]) <= stages["warmup"][key] + 1e-6, key
    assert all(startup["init_began"] <= p["t"] <= startup["init_ended"] for p in startup["programs"])


def test_stats_compiles_keep_their_shape():
    from ray_tpu.llm.deployment import LLMServer

    srv = LLMServer(MODEL_KW, ENGINE_KW)
    try:
        trace = srv.stats()["trace"]
    finally:
        srv.__raytpu_exit__()
    assert isinstance(trace["compiles_total"], int) and trace["compiles_total"] >= len(trace["compiles"]) > 0
    assert all(len(c) == 2 and c[0] <= time.monotonic() and c[1] >= 0 for c in trace["compiles"])
