"""Distributed tracing: span propagation through every cross-process hop.

The acceptance path (ISSUE 2): one serve HTTP request drives
proxy -> replica -> nested actor; every resulting span must share one
trace_id with correct parent/child links, and export_timeline must emit
connected flow events (ph s/f) for the hops."""
import json
import threading
import time
import urllib.request

import pytest

import ray_tpu as rt
from ray_tpu import serve
from ray_tpu.util import tracing


@pytest.fixture(scope="module")
def serve_cluster():
    rt.init(num_cpus=16)
    serve.start(proxy=True)
    yield rt
    serve.shutdown()
    rt.shutdown()


# ---------------------------------------------------------------------------
# span API semantics (in-process)
# ---------------------------------------------------------------------------

def test_span_nesting_and_context(serve_cluster):
    assert tracing.current_trace() is None
    with tracing.span("outer") as outer:
        assert tracing.current_trace() == (outer.trace_id, outer.span_id)
        assert outer.parent_id == ""
        with tracing.span("inner") as inner:
            assert inner.trace_id == outer.trace_id
            assert inner.parent_id == outer.span_id
            assert tracing.current_trace() == (inner.trace_id, inner.span_id)
        assert tracing.current_trace() == (outer.trace_id, outer.span_id)
    assert tracing.current_trace() is None


def test_child_span_noop_without_active_trace(serve_cluster):
    with tracing.child_span("ignored") as s:
        assert s is None  # nullcontext: no ids minted, nothing recorded
        assert tracing.current_trace() is None
    with tracing.span("root") as root:
        with tracing.child_span("kid") as kid:
            assert kid is not None and kid.parent_id == root.span_id


def test_task_spans_share_trace_and_parent(serve_cluster):
    @rt.remote
    def leaf(x):
        return x + 1

    with tracing.span("task-root") as root:
        assert rt.get(leaf.remote(1), timeout=60) == 2

    events = _wait_trace(root.trace_id, want_kinds={"task_submitted", "task_exec_start"})
    subs = [e for e in events if e["kind"] == "task_submitted"]
    execs = [e for e in events if e["kind"] == "task_exec_start"]
    assert subs and execs
    assert all(e["trace_id"] == root.trace_id for e in subs + execs)
    assert subs[0]["span_id"] == root.span_id  # submission annotated with caller span
    assert execs[0]["parent_id"] == root.span_id  # exec span is the caller's child


def _wait_trace(trace_id: str, want_kinds=frozenset(), min_workers: int = 1,
                predicate=None, timeout_s: float = 90.0):
    """Poll the controller's trace index until the wanted event kinds, enough
    distinct worker processes, AND an optional predicate over the events all
    hold (remote workers flush their buffers on the reporter tick, so hops
    arrive staggered — see tracing.get_trace's staleness note)."""
    from ray_tpu.core import api

    core = api._require_worker()
    deadline = time.time() + timeout_s
    events: list = []
    while time.time() < deadline:
        core._run(core._flush_task_events())
        events = core._run(core.controller.call("get_trace", {"trace_id": trace_id}))
        if (set(want_kinds) <= {e.get("kind") for e in events}
                and len({e.get("worker") for e in events}) >= min_workers
                and (predicate is None or predicate(events))):
            return events
        time.sleep(0.5)
    return events


# ---------------------------------------------------------------------------
# acceptance: serve request through proxy -> replica -> actor
# ---------------------------------------------------------------------------

def test_serve_request_single_trace_across_hops(serve_cluster, tmp_path):
    @rt.remote
    class Shouter:
        def shout(self, s):
            return s.upper()

    @serve.deployment
    class Ingress:
        def __init__(self, downstream):
            self.downstream = downstream

        def __call__(self, request):
            return {"msg": rt.get(self.downstream.shout.remote("hello"), timeout=30)}

    downstream = Shouter.remote()
    rt.get(downstream.shout.remote("warm"), timeout=60)
    serve.run(Ingress.bind(downstream), name="traced_app", route_prefix="/traced")
    port = serve.http_port()

    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/traced", headers={"x-trace": "1"}
    )
    with urllib.request.urlopen(req, timeout=60) as resp:
        assert resp.status == 200
        assert json.loads(resp.read()) == {"msg": "HELLO"}

    # Find the request's trace via the root span name.
    from ray_tpu.core import api

    core = api._require_worker()
    deadline = time.time() + 45
    trace_id = None
    while time.time() < deadline and trace_id is None:
        traces = core._run(core.controller.call("list_traces", {"q": "serve.request"}))
        if traces:
            trace_id = traces[0]["trace_id"]
            break
        time.sleep(0.5)
    assert trace_id, "no serve.request trace was indexed"

    events = _wait_trace(
        trace_id, want_kinds={"span", "task_exec_start"}, min_workers=3,
        # All three hops must have landed: the replica's serve span and the
        # downstream actor's exec span arrive on their own reporter ticks.
        predicate=lambda evs: (
            any(e.get("name", "").startswith("serve.replica.") for e in evs)
            and any(e.get("fn") == "shout" and e["kind"] == "task_exec_start" for e in evs)
        ),
    )
    assert all(e.get("trace_id") == trace_id for e in events)

    spans = {}  # span_id -> event (spans + exec spans both mint span ids)
    for e in events:
        if e.get("span_id") and e["kind"] in ("span", "task_exec_start"):
            spans[e["span_id"]] = e

    roots = [e for e in spans.values() if e["kind"] == "span" and not e.get("parent_id")]
    assert len(roots) == 1 and roots[0]["name"] == "serve.request"

    # The request crossed at least proxy + replica + downstream-actor
    # processes, each contributing spans to the SAME trace.
    workers = {e.get("worker") for e in spans.values()}
    assert len(workers) >= 3, f"expected >=3 processes in trace, got {workers}"

    # Every non-root span's parent resolves inside the trace: one connected
    # tree, no orphaned hops.
    ids = set(spans)
    for e in spans.values():
        if e is roots[0]:
            continue
        assert e.get("parent_id") in ids, f"orphaned span {e}"

    # The replica's serve span and the downstream actor's exec span are on
    # the path: replica span parents the shout exec (via the replica's
    # active context at submission).
    replica_spans = [e for e in spans.values()
                     if e["kind"] == "span" and e["name"].startswith("serve.replica.")]
    assert replica_spans
    shout_execs = [e for e in spans.values()
                   if e["kind"] == "task_exec_start" and e.get("fn") == "shout"]
    assert shout_execs

    # Flow events connect the hops in the exported timeline.
    out = str(tmp_path / "serve_trace.json")
    tracing.export_timeline(out)
    data = json.load(open(out))
    flows = [e for e in data["traceEvents"] if e.get("ph") in ("s", "f")
             and e.get("args", {}).get("trace_id") == trace_id]
    starts = {e["id"] for e in flows if e["ph"] == "s"}
    finishes = {e["id"] for e in flows if e["ph"] == "f"}
    assert starts & finishes, "no connected flow (s/f) pair for the request's hops"

    serve.delete("traced_app")


def test_trace_overhead_guard_no_context_cost(serve_cluster):
    """With no span active, submission attaches None and no trace events are
    recorded — the guard path."""
    @rt.remote
    class Quiet:
        def ping(self):
            return b"ok"

    a = Quiet.remote()
    rt.get(a.ping.remote(), timeout=60)
    from ray_tpu.core import api

    core = api._require_worker()
    before = len(core.task_events)
    rt.get([a.ping.remote() for _ in range(50)], timeout=120)
    # Untraced actor calls emit no tracing events (task_finished bookkeeping
    # predates this feature and stays).
    new = core.task_events[before:]
    assert not [e for e in new
                if "trace_id" in e
                or e["kind"] in ("span", "task_submitted", "task_exec_start")]


# ---------------------------------------------------------------------------
# phase spans, rings, and a request's phases inside the LLM engine (PR 24)
# ---------------------------------------------------------------------------

def test_tracing_module_never_imports_jax():
    """Drivers and proxies import util/tracing and must never import jax:
    the phase primitive takes its annotation class from the caller."""
    import subprocess
    import sys

    code = ("import sys; from ray_tpu.util import tracing; from ray_tpu.accel import device; "
            "tracing.PhaseSpans('x', ('a',), 4); sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], timeout=120).returncode == 0


def test_phase_spans_time_count_and_annotate():
    seen = []

    class Note:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            seen.append(("enter", self.name))

        def __exit__(self, *exc):
            seen.append(("exit", self.name))

    ph = tracing.PhaseSpans("loop", ("a", "b", "c"), 3, annotation=Note)
    for i in range(5):
        ph.begin("a", iteration=i)
        time.sleep(0.002)
        ph.to("b")
        ph.rec["n"] = 7
        ph.to("a")
        time.sleep(0.001)
        ph.end()
    assert ph.rec is None
    # One annotation around the iteration, the phases inside it, one after
    # another and never nested.
    assert seen[:8] == [("enter", "loop"), ("enter", "loop.a"), ("exit", "loop.a"),
                        ("enter", "loop.b"), ("exit", "loop.b"), ("enter", "loop.a"),
                        ("exit", "loop.a"), ("exit", "loop")]
    assert len(seen) == 5 * 8
    assert ph.phase_n == {"a": 10, "b": 5, "c": 0}
    recs = ph.ring.snapshot()
    assert [r["iteration"] for r in recs] == [2, 3, 4] and ph.ring.dropped == 2
    for r in recs:
        assert set(r["phase_s"]) == {"a", "b"} and r["n"] == 7
        assert r["phase_s"]["a"] >= 0.003 and sum(r["phase_s"].values()) <= r["dur"]
        assert r["t"] <= time.monotonic()
    assert ph.phase_s["a"] >= 5 * 0.003 and ph.phase_s["c"] == 0.0
    with pytest.raises(KeyError):
        ph.begin("never-declared")
    # Without an annotation class the phases still time.
    bare = tracing.PhaseSpans("bare", ("x",), 2)
    bare.begin("x")
    bare.end()
    assert bare.ring.total == 1 and bare.phase_n == {"x": 1}


def _sleep_then_spin(ph, sleep_s, spin_s):
    ph.begin("sleeps")
    time.sleep(sleep_s)
    ph.to("spins")
    until = time.thread_time() + spin_s
    while time.thread_time() < until:
        pass
    ph.to("sleeps")  # a phase entered twice in one iteration adds up on both clocks
    time.sleep(sleep_s)
    ph.end()


def test_phase_spans_tell_a_phase_that_waits_from_one_that_works(cpu_tick):
    """Beside each phase's wall seconds the CPU seconds of the thread that
    drives the spans: a sleeping phase (as a dispatch that waits for room in
    the device's queue, or a fetch) has nearly none, a spinning one nearly all.
    `cpu_tick` is the CPU clock's step here: next to nothing on this sandbox,
    10 ms on a host that accounts by the tick, where a phase is made long
    enough to tell and may read a tick over its wall time each time it is entered."""
    ph = tracing.PhaseSpans("loop", ("sleeps", "spins", "unused"), 32)
    sleep_s, spin_s = max(0.03, 5 * cpu_tick), max(0.002, 3 * cpu_tick)
    before = time.thread_time()

    def spun_on_its_core(r):
        wall, cpu = r["phase_s"]["spins"], r["phase_cpu_s"]["spins"]
        return abs(wall - cpu) <= 0.2 * wall + cpu_tick

    # a spinning thread is taken off its core on a busy machine (the other test
    # workers): at least three iterations, and more until one kept its core
    for n in range(30):
        _sleep_then_spin(ph, sleep_s, spin_s)
        if n >= 2 and any(map(spun_on_its_core, ph.ring.snapshot())):
            break
    recs = ph.ring.snapshot()
    assert 3 <= len(recs) == ph.ring.total
    for r in recs:
        wall, cpu = r["phase_s"], r["phase_cpu_s"]
        assert list(cpu) == list(wall) == ["sleeps", "spins"]
        assert wall["sleeps"] >= 2 * sleep_s and cpu["sleeps"] <= wall["sleeps"] / 10 + 2 * cpu_tick
        assert cpu["spins"] >= spin_s
        assert all(0 <= cpu[p] <= wall[p] + 1e-3 + 2 * cpu_tick for p in wall)
        assert sum(cpu.values()) <= r["dur"] + 1e-3 + cpu_tick
    assert any(map(spun_on_its_core, recs))
    # over the iterations a tick charged to a sleep by chance does not add up
    assert sum(r["phase_cpu_s"]["sleeps"] for r in recs) <= sum(r["phase_s"]["sleeps"] for r in recs) / 10 + 2 * cpu_tick
    # cpu_t: the thread's CPU clock as the iteration began, so two records'
    # difference holds an iteration's CPU and what the loop did in between
    assert before <= recs[0]["cpu_t"] and [r["cpu_t"] for r in recs] == sorted(r["cpu_t"] for r in recs)
    for a, b in zip(recs, recs[1:]):
        assert b["cpu_t"] - a["cpu_t"] >= sum(a["phase_cpu_s"].values()) - 1e-6
    # the cumulative dicts hold wall seconds and entries, of every declared phase
    assert set(ph.phase_s) == set(ph.phase_n) == {"sleeps", "spins", "unused"}
    for phase in ph.phase_s:
        assert ph.phase_s[phase] == pytest.approx(sum(r["phase_s"].get(phase, 0.0) for r in recs))


def test_phase_spans_count_only_their_own_threads_cpu(cpu_tick):
    """Another thread that burns CPU meanwhile adds nothing to a sleeping
    phase: the clock is the driving thread's, not the process's (which would
    read the sleeps' whole wall time)."""
    stop = threading.Event()

    def burn():
        while not stop.is_set():
            pass

    other = threading.Thread(target=burn, daemon=True)
    other.start()
    try:
        ph = tracing.PhaseSpans("loop", ("sleeps", "spins"), 2)
        _sleep_then_spin(ph, max(0.05, 5 * cpu_tick), max(0.01, cpu_tick))
    finally:
        stop.set()
        other.join(10)
    rec = ph.ring.snapshot()[0]
    assert rec["phase_s"]["sleeps"] >= 0.1
    assert rec["phase_cpu_s"]["sleeps"] <= rec["phase_s"]["sleeps"] / 5 + 2 * cpu_tick


def test_ring_is_read_without_a_lock_while_its_thread_writes():
    import threading

    ring = tracing.Ring(64)
    stop = threading.Event()

    def write():
        i = 0
        while not stop.is_set():
            ring.push(i)
            i += 1

    t = threading.Thread(target=write)
    t.start()
    try:
        deadline = time.time() + 1.0
        while time.time() < deadline:
            snap = ring.snapshot()
            assert len(snap) <= 64 and snap == sorted(snap)
    finally:
        stop.set()
        t.join(10)
    assert ring.total > 64 and ring.dropped == ring.total - 64
    assert ring.snapshot() == list(range(ring.total - 64, ring.total))


def test_traced_llm_request_lays_its_phases_on_its_trace(serve_cluster):
    """A request sent with a trace context (`x-trace: 1`) reaches the engine's
    thread, where its contextvar is not set: the context rides the request's
    lifecycle record, the loop lays llm.queue / llm.prefill / llm.first_emit /
    llm.decode onto the trace as children of the replica's span, and
    autopsy's exec hop splits by them."""
    from ray_tpu.llm import build_llm_app
    from ray_tpu.obs import autopsy as obs_autopsy

    app = build_llm_app(
        model_config=dict(vocab_size=97, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
                          d_ff=128, max_seq_len=128, attention_impl="reference"),
        engine_config={"max_slots": 4, "max_seq": 128, "page_size": 16, "prefill_buckets": (16, 32)},
    )
    handle = serve.run(app, name="llm_traced", route_prefix="/llm_traced", timeout_s=300)
    port = serve.http_port()
    body = json.dumps({"tokens": [3, 1, 4, 1, 5], "max_tokens": 20, "stream": True}).encode()

    def post(headers):
        req = urllib.request.Request(f"http://127.0.0.1:{port}/llm_traced", data=body,
                                     headers=headers, method="POST")
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.status == 200
            frames = [json.loads(f[6:]) for f in resp.read().decode().split("\n\n")
                      if f.startswith("data: ") and f != "data: [DONE]"]
        return [t for f in frames for t in f["new_tokens"]]

    plain = post({})
    traced = post({"x-trace": "1"})
    assert len(plain) == 20 and traced == plain  # a trace context changes no token

    names = ("llm.queue", "llm.prefill", "llm.first_emit", "llm.decode")
    from ray_tpu.core import api

    core = api._require_worker()
    deadline = time.time() + 90
    events = []
    while time.time() < deadline:
        core._run(core._flush_task_events())
        for tr in core._run(core.controller.call("list_traces", {"q": "serve.request"})):
            evs = core._run(core.controller.call("get_trace", {"trace_id": tr["trace_id"]}))
            if {e.get("name") for e in evs} >= set(names) | {"serve.replica.llm"}:
                events = evs
        if events:
            break
        time.sleep(0.5)
    assert events, "the traced request's llm.* spans never reached the trace index"
    by_name = {e["name"]: e for e in events if e.get("kind") == "span"}
    replica = by_name["serve.replica.llm"]
    for name in names:
        span = by_name[name]
        assert span["trace_id"] == replica["trace_id"] and span["parent_id"] == replica["span_id"]
        assert span["dur"] >= 0 and span["attrs"]["slot"] in range(4)
        # on the spans' clock, inside the replica's span (a few ms of slack for
        # the one conversion from the engine's monotonic stamps)
        assert replica["ts"] - 0.05 <= span["ts"] <= replica["ts"] + replica["dur"] + 0.05
    starts = [by_name[n]["ts"] for n in names]
    assert starts == sorted(starts)
    # the host's share of the prefill, from the same start, inside it
    enqueue = by_name["llm.prefill.enqueue"]
    assert enqueue["parent_id"] == replica["span_id"] and enqueue["ts"] == pytest.approx(by_name["llm.prefill"]["ts"])
    assert 0 <= enqueue["dur"] <= by_name["llm.prefill"]["dur"]
    # Only the traced request left spans: the untraced one paid a ContextVar.get.
    assert sum(e.get("name") == "llm.decode" for e in events) == 1
    stats = handle.stats.remote().result(timeout=30)
    lives = [r for r in stats["trace"]["requests"] if r["prompt_len"] == 5]
    assert [r["trace"] is not None for r in lives] == [False, True]
    assert lives[1]["trace"][0] == replica["trace_id"]

    a = obs_autopsy.autopsy(events)
    exec_hop = next(h for h in a["hops"] if h["hop"] == "exec")
    parts = {p["part"]: p["dur_s"] for p in exec_hop["parts"]}
    assert list(parts) == ["queue", "prefill", "first_emit", "decode", "other"]
    assert sum(parts.values()) == pytest.approx(exec_hop["dur_s"], abs=1e-6)
    assert parts["decode"] > 0
    assert exec_hop["parts"][1]["enqueue_s"] == pytest.approx(enqueue["dur"])
    serve.delete("llm_traced")
