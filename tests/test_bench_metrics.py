"""The benchmark's readers of the program's own record (PR 24), each on a
hand-written run record with known answers. `benchmarks/run.py --rehearse`
stops before the readers run and a real run needs the chip, so nothing else
executes them here."""
import copy
import os
import sys

import pytest

BENCH_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "benchmarks")
W0, W1 = 1000.0, 1051.0


@pytest.fixture(scope="module")
def bench():
    sys.path.insert(0, BENCH_DIR)
    try:
        from harness import cellspec, context

        yield cellspec, context
    finally:
        sys.path.remove(BENCH_DIR)


def _request(arrived, queue_ms, prefill_ms, emit_ms, wake_ms, decode_s=2.0):
    admitted = arrived + queue_ms / 1e3
    first_token = admitted + prefill_ms / 1e3
    first_emitted = first_token + emit_ms / 1e3
    return {"req_id": f"r{arrived}", "arrived": arrived, "admitted": admitted, "slot": 0,
            "prompt_len": 100, "prefix_hit_len": 0, "bucket": 128, "first_token": first_token,
            "first_emitted": first_emitted, "first_yielded": first_emitted + wake_ms / 1e3,
            "finished": first_emitted + decode_s, "n_out": 50, "finish_reason": "length",
            "trace": None}


def _step(t, **phase_ms):
    phase_s = {k: v / 1e3 for k, v in phase_ms.items()}
    return {"t": t, "dur": sum(phase_s.values()), "phase_s": phase_s, "waiting": 0,
            "n_admitted": 0, "n_prefill": 0, "block": 8, "active": 3}


def _record():
    """Three requests inside the window (queue waits 100/200/300 ms, prefill
    40/50/60, emit delays 400/410/420, wake-ups 1/2/3), one before it and one
    after it with other values; two steps inside (host 12 and 18 ms besides
    their fetches), one before; compiles: one before the window, none in it."""
    requests = [
        _request(W0 - 30, 999, 999, 999, 999),
        _request(W0 + 1, 100, 40, 400, 1),
        _request(W0 + 10, 200, 50, 410, 2),
        _request(W0 + 20, 300, 60, 420, 3),
        _request(W1 + 5, 777, 777, 777, 777),
    ]
    steps = [
        _step(W0 - 5, admit=90, decode_fetch=400),
        _step(W0 + 2, admit=2, prefill_dispatch=6, mirror_sync=3, emit=1, prefill_fetch=30, decode_fetch=400),
        _step(W0 + 3, admit=1, decode_dispatch=9, emit=4, retire_sync=4, decode_fetch=410),
    ]
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": requests, "requests_total": 5,
             "steps": steps, "steps_total": 3, "phase_s": {}, "phase_n": {},
             "dropped": {"requests": 0, "steps": 0},
             "compiles": [[W0 - 200.0, 3.5]], "compiles_total": 1}
    return {"kind": "serve", "seconds": W1 - W0, "config": {}, "traffic": {},
            "client": {"w0": W0, "w1": W1, "records": []}, "plan": {"loop": "open"},
            "stats": {"active_slots": 0, "waiting": 0, "trace": trace}}


KNOWN = {
    "engine_queue_wait_p50_ms": 200.0,
    "engine_prefill_p50_ms": 50.0,
    "first_emit_delay_p50_ms": 410.0,
    "stream_wake_p50_ms": 2.0,
    "engine_host_ms_per_step": 15.0,
    "engine_host_ms_per_step.backlog": 15.0,
    "window_compiles": 0.0,
    "window_compiles.backlog": 0.0,
}
# The ring each reader depends on: a drop there, younger than the window's
# start, must blank the metric.
RING_OF = {name: ("steps" if "host_ms" in name else "compiles" if "compiles" in name else "requests")
           for name in KNOWN}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_the_known_answer(bench, name):
    cellspec, context = bench
    value = cellspec.load_metric(name)(context.Context(_record(), 1))
    assert value == pytest.approx(KNOWN[name], abs=1e-6)


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_is_silent_without_the_programs_record(bench, name):
    """The parent of the PR that added stats()["trace"] has none: the reader
    returns None and does not raise, and the line leaves the metric out."""
    cellspec, context = bench
    for stats in ({"active_slots": 0, "waiting": 0}, None):
        record = _record()
        record["stats"] = stats
        assert cellspec.load_metric(name)(context.Context(record, 1)) is None


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_is_silent_after_a_drop_inside_the_window(bench, name):
    cellspec, context = bench
    record = _record()
    trace = record["stats"]["trace"]
    ring = RING_OF[name]
    if ring == "compiles":
        # the oldest stamp still held lies after the window's start, and one fell off
        trace["compiles"], trace["compiles_total"] = [[W0 + 7.0, 2.0]], 2
    else:
        trace[ring] = [r for r in trace[ring] if r.get("finished", r.get("t")) > W0 + 4]
        trace["dropped"][ring] = 1
    assert cellspec.load_metric(name)(context.Context(record, 1)) is None
    # The same drop with the oldest record still older than the window: nothing
    # that fell off can lie inside it, and the reader answers again.
    older = copy.deepcopy(_record())
    trace = older["stats"]["trace"]
    if ring == "compiles":
        trace["compiles_total"] = 2
    else:
        trace["dropped"][ring] = 1
    assert cellspec.load_metric(name)(context.Context(older, 1)) == pytest.approx(KNOWN[name], abs=1e-6)


def test_window_compiles_counts_a_compile_inside_the_window(bench):
    cellspec, context = bench
    record = _record()
    record["stats"]["trace"]["compiles"] += [[W0 + 3.0, 1.0], [W1 + 1.0, 1.0]]
    record["stats"]["trace"]["compiles_total"] = 3
    assert cellspec.load_metric("window_compiles")(context.Context(record, 1)) == 1.0


def test_every_new_metric_is_in_the_manifest_with_its_cells(bench):
    import json

    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    end_to_end = {m["name"]: m for m in manifest["end_to_end"]}
    chat, back = "internlm2-1.8b.chat", "internlm2-1.8b.backlog"
    for name in KNOWN:
        entry = by_name[name]
        if name.endswith(".backlog"):
            assert back in entry["workloads"], name  # later cells may join the list
        else:
            assert entry["workloads"] == [chat], name
        assert entry["source"] == ("program_counter" if "compiles" in name else "program_span")
        # a per-layer metric moves an end-to-end metric that its cells report
        assert set(entry["workloads"]) <= set(end_to_end[entry["moves"]]["workloads"]), name
