"""A traced serve run ends with a trace, or says how long it waited and how
large the trace was, however fast the program under it is: the traced part's
two limits (harness/replica.py `traced_part`), the replica's answer while
`stop_trace` runs (`bench_trace_result`), and the driver's wait
(harness/serve_cell.py `await_trace`); on fake clocks, fake counters and a
fake trace thread, and once on the real profiler of the CPU."""
import os
import re
import sys
import threading
import time
import types

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import serve_cell, xplane  # noqa: E402
from harness.context import Context  # noqa: E402
from harness.cellspec import load_metric  # noqa: E402
from harness.replica import BenchLLMServer, traced_part  # noqa: E402
from harness.serve_cell import TRACE_ASK_S, TRACE_S, TRACE_UNITS, await_trace  # noqa: E402


class FakeTime:
    """A clock that only `sleep` moves, and counters that grow with it."""

    def __init__(self, units_per_s: float = 0.0):
        self.now, self.rate, self.sleeps = 100.0, units_per_s, 0

    def clock(self) -> float:
        return self.now

    def sleep(self, s: float) -> None:
        self.now += s
        self.sleeps += 1

    def units(self) -> int:
        return int((self.now - 100.0) * self.rate)


# Layer passes in 6 s of the cells' traces (decode steps + prefilled requests,
# x layers x devices; chiprun logs of PR 29 and PR 31, PERF.md section 6).
ACCEPTED = {"internlm2-1.8b.chat": (499 + 15) * 24, "internlm2-1.8b.backlog": (319 + 84) * 24,
            "mistral-7b.backlog-tp4": (200 + 52) * 32 * 4}
PR31_TP4 = (246 + 68) * 32 * 4  # the four-chip trace that stop_trace wrote in 164.6 s


@pytest.mark.parametrize("cell", sorted(ACCEPTED))
def test_at_todays_rates_the_time_limit_ends_the_traced_part(cell):
    t = FakeTime(ACCEPTED[cell] / TRACE_S)
    out = traced_part(TRACE_S, TRACE_UNITS, t.units, t.clock, t.sleep)
    assert out["ended_by"] == "time" and out["traced_part_s"] == pytest.approx(TRACE_S, abs=1e-9)
    assert out["trace_units"] == pytest.approx(ACCEPTED[cell], abs=1) and out["trace_units"] < TRACE_UNITS


def test_a_faster_program_ends_it_by_volume_before_the_time_limit():
    t = FakeTime(PR31_TP4 / TRACE_S)
    out = traced_part(TRACE_S, TRACE_UNITS, t.units, t.clock, t.sleep)
    assert out["ended_by"] == "volume"
    assert out["traced_part_s"] == pytest.approx(TRACE_S * TRACE_UNITS / PR31_TP4, abs=0.06)  # 5.08 s, a poll late at most
    assert TRACE_UNITS <= out["trace_units"] < TRACE_UNITS * 1.01
    # the time limit doubled changes nothing: the volume still ends it, at the same place
    t2 = FakeTime(PR31_TP4 / TRACE_S)
    assert traced_part(2 * TRACE_S, TRACE_UNITS, t2.units, t2.clock, t2.sleep) == out


def test_the_bound_is_a_few_percent_over_the_four_chip_cells_six_seconds():
    assert 1.0 < TRACE_UNITS / ACCEPTED["mistral-7b.backlog-tp4"] <= 1.06
    assert TRACE_UNITS < PR31_TP4


def test_an_idle_replica_is_traced_for_the_time_limit_and_no_longer():
    t = FakeTime(0.0)
    out = traced_part(0.12, 10, t.units, t.clock, t.sleep)
    assert out == {"traced_part_s": pytest.approx(0.12), "trace_units": 0, "ended_by": "time"}
    assert t.sleeps == 3  # 0.05 + 0.05 + what is left: it never sleeps past the limit


# -- the driver's wait ---------------------------------------------------------

def _stop_trace_after(seconds: float, t: FakeTime, asked: list, record: dict):
    def ask(wait_s):
        asked.append(wait_s)
        if t.now - 100.0 + wait_s >= seconds:
            t.now = max(t.now, 100.0 + seconds)
            return dict(record)
        t.now += wait_s
        return {"pending": True}
    return ask


def test_a_stop_trace_that_outlasts_the_old_120_s_is_waited_for_and_its_result_used(tmp_path):
    t, asked = FakeTime(), []
    record = {"logdir": str(tmp_path), "stop_trace_s": 194.6, "ended_by": "time"}
    # PR 31's: stop_trace 164.6 s, the driver asking from 30 s after the traced part's end
    out = await_trace(_stop_trace_after(134.3, t, asked, record), 100.0 + 700.0, str(tmp_path), t.clock)
    assert out == dict(record, trace_wait_s=pytest.approx(134.3))
    assert len(asked) == 14 and max(asked) <= TRACE_ASK_S  # in slices: no call holds the replica for long


def test_a_stop_trace_that_never_returns_fails_with_seconds_and_bytes(tmp_path):
    os.makedirs(tmp_path / "plugins" / "profile" / "x")
    (tmp_path / "plugins" / "profile" / "x" / "host.xplane.pb").write_bytes(b"x" * 12345)
    t, asked = FakeTime(), []
    out = await_trace(_stop_trace_after(1e9, t, asked, {}), 100.0 + 395.0, str(tmp_path), t.clock)
    assert out["error"] == ("stop_trace has not returned (waited 395.0 s for it; "
                            "12345 bytes of trace on disk)")
    assert sum(asked) == pytest.approx(395.0) and asked[-1] == pytest.approx(5.0)  # the last slice ends at the deadline


def test_a_trace_thread_that_failed_is_an_error_with_seconds_and_bytes_too(tmp_path):
    t = FakeTime()
    out = await_trace(lambda s: {"error": "the trace thread failed: RuntimeError('boom')"},
                      100.0 + 50.0, str(tmp_path / "none"), t.clock)
    assert out["error"].startswith("the trace thread failed: RuntimeError('boom') (waited 0.0 s")
    assert "0 bytes of trace on disk" in out["error"]


def test_the_deadline_already_past_still_asks_once(tmp_path):
    t, asked = FakeTime(), []
    out = await_trace(_stop_trace_after(0.0, t, asked, {"logdir": "d"}), 50.0, str(tmp_path), t.clock)
    assert asked == [0.0] and out["logdir"] == "d"


# -- the replica's side, on a fake trace thread ---------------------------------

def _replica(layers=3, tp=2, **counters):
    srv = object.__new__(BenchLLMServer)  # the benchmark's methods without a model under them
    srv._b_reset()
    srv._b.update(counters)
    srv._b_trace, srv._b_annotate = None, True
    srv.engine = types.SimpleNamespace(cfg=types.SimpleNamespace(n_layers=layers),
                                       ec=types.SimpleNamespace(tensor_parallel=tp, prefix_cache=False))
    return srv


def test_a_unit_is_one_layer_pass_on_one_device():
    assert _replica(layers=32, tp=4, decode_steps=200, prefill_requests=52).bench_trace_units() == 32_256
    assert _replica(layers=24, tp=1, decode_steps=499, prefill_requests=15).bench_trace_units() == 12_336


def test_the_replica_answers_pending_until_its_trace_thread_is_done():
    srv = _replica()
    assert "error" in srv.bench_trace_result(0.01)  # no trace was started
    box = {"logdir": "somewhere", "done": threading.Event()}
    srv._b_trace = box
    t0 = time.monotonic()
    assert srv.bench_trace_result(0.05) == {"pending": True} and time.monotonic() - t0 < 2.0
    assert srv._b_annotate  # still tracing, for all the replica knows

    def fake_trace_thread():
        box.update(stop_trace_s=0.2, traced_part_s=5.1, ended_by="volume", trace_units=34_100,
                   counters_before={"decode_steps": 1}, counters_after={"decode_steps": 2})
        box["done"].set()

    timer = threading.Timer(0.2, fake_trace_thread)
    timer.start()
    out = await_trace(srv.bench_trace_result, time.monotonic() + 30.0, "nowhere")
    timer.join(timeout=5)
    assert out.pop("trace_wait_s") >= 0.1 and not srv._b_annotate
    assert out == {"logdir": "somewhere", "stop_trace_s": 0.2, "traced_part_s": 5.1, "ended_by": "volume",
                   "trace_units": 34_100, "counters_before": {"decode_steps": 1},
                   "counters_after": {"decode_steps": 2}}


def test_on_the_real_profiler_the_counters_end_the_part_and_window_s_is_the_shorter_part(tmp_path):
    """The replica's own trace thread on this machine's CPU, with counters a
    fake engine thread moves: the volume ends the part long before the time
    limit, `bench.window` in the written trace is that part, and the
    reduction takes `window_s` from it (a device plane is added by hand: a
    CPU trace has none)."""
    from jax.profiler import ProfileData

    srv = _replica(layers=2, tp=1)
    stop = threading.Event()

    def engine():
        while not stop.wait(0.01):
            srv._b["decode_steps"] += 1

    worker = threading.Thread(target=engine, daemon=True)
    worker.start()
    try:
        srv.bench_trace_start(time.monotonic(), 30.0, 60, str(tmp_path))  # 30 steps x 2 layers: ~0.3 s
        out = await_trace(srv.bench_trace_result, time.monotonic() + 120.0, str(tmp_path))
    finally:
        stop.set()
        worker.join(timeout=5)
    assert "error" not in out, out
    assert out["ended_by"] == "volume" and 60 <= out["trace_units"] <= 80 and out["traced_part_s"] < 5.0
    assert out["stop_trace_s"] > 0 and out["counters_after"]["decode_steps"] > out["counters_before"]["decode_steps"]
    path = next(os.path.join(d, f) for d, _s, files in os.walk(tmp_path) for f in files if f.endswith(".xplane.pb"))
    assert serve_cell.trace_bytes(str(tmp_path)) >= os.path.getsize(path) > 0
    plain = xplane.to_plain(ProfileData.from_file(path))
    (w0, dur), = [(s, d) for p in plain["planes"] for ln in p["lines"] for n, s, d in ln["events"]
                  if n == xplane.WINDOW]
    assert dur / 1e9 == pytest.approx(out["traced_part_s"], abs=0.05)
    plain["planes"].append({"name": "/device:TPU:0", "lines": [{"name": xplane.OPS_LINE, "events": [
        ["%fusion.1 = f32[8]{0} fusion(...)", w0 - 1e9, 1e9 + dur / 2],  # began before the part: clipped to it
        ["%fusion.2 = f32[8]{0} fusion(...)", w0 + dur, 1e9]]}]})  # after it: left out
    reduced = xplane.reduce(plain)
    assert reduced["window_s"] == pytest.approx(dur / 1e9) and reduced["busy_s"] == pytest.approx(dur / 2e9)


# -- no reader needs the traced part to be TRACE_S long --------------------------

def _traced(part_s: float) -> dict:
    k = part_s / 6.0  # a part k times as long holds k times the work: every reading must stay
    return {"window_s": 6.0 * k, "busy_s": 5.0 * k, "devices": 4, "collective_exposed_s": 0.012 * k,
            "module_s": {"jit__decode_impl": 3.6 * k, "jit__prefill_batch_impl": 1.5 * k},
            "module_runs": {"jit__decode_impl": 36 * k, "jit__prefill_batch_impl": 48 * k},
            "kernel": {"jit__decode_impl": {"seconds": 2.4 * k, "calls": 6400 * k}},
            "counters_before": {"decode_steps": 1000, "decode_context_tokens": 0, "slot_steps_active": 0},
            "counters_after": {"decode_steps": 1000 + 200 * k, "decode_context_tokens": 4_000_000 * k,
                               "slot_steps_active": 6000 * k}}


@pytest.mark.parametrize("metric,value", [
    ("decode_ms_per_step", 18.0), ("prefill_busy_share", 25.0), ("paged_attn_time_share", 48.0),
    ("collective_exposed_share", 0.2), ("paged_attn_roofline", None)])
def test_a_traced_reading_is_a_share_or_a_per_step_value(metric, value):
    import json

    with open(os.path.join(BENCH_DIR, "configs", "mistral-7b-v0.3.json")) as f:
        config = json.load(f)
    readings = []
    for part_s in (6.0, 4.5):
        ctx = Context({"kind": "serve", "seconds": 51.0, "config": config, "traffic": {},
                       "device": {"kind": "TPU v5 lite"}, "traced": _traced(part_s)}, 4)
        readings.append(load_metric(metric)(ctx))
    assert readings[0] == pytest.approx(readings[1], rel=1e-12) and readings[0] > 0
    if value is not None:
        assert readings[0] == pytest.approx(value)


def test_no_reader_names_the_time_limit():
    for d in ("metrics", "harness"):
        for name in sorted(os.listdir(os.path.join(BENCH_DIR, d))):
            if name.endswith(".py") and name != "serve_cell.py":
                with open(os.path.join(BENCH_DIR, d, name)) as f:
                    assert not re.search(r"\bTRACE_S\b", f.read()), f"{d}/{name}"
