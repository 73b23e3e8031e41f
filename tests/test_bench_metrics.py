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


# -- the readers of what PR 41 added to the record ---------------------------
# The stepping thread's CPU clock beside each phase's wall seconds, a
# request's `prefill_enqueued`, set-up's two stamps, a step's `pages_reserved`.
# A table of their own: a plain name here may have more cells than `chat`, and
# `cells` are the ones a metric had when it was added: later cells may join.
CHAT = "internlm2-1.8b.chat"
BACKLOGS = ["internlm2-1.8b.backlog", "mistral-7b.backlog-tp4", "openpangu-718b-ep16.backlog-long-out",
            "laguna-s-2.1-ep8.backlog-long-ctx"]
HOST_AND_WAITS = {  # name: (answer, cells, layer, moves, source, unit, better)
    # the thread's CPU clock reads 20.000 / 20.013 / 20.030 s as the window's three steps begin
    "engine_host_cpu_ms_per_step": (15.0, [CHAT], "scheduler", "tpot_p90_ms", "program_span", "ms", "lower"),
    "engine_host_cpu_ms_per_step.backlog":
        (15.0, BACKLOGS, "scheduler", "serve_out_tokens_per_s", "program_span", "ms", "lower"),
    # wall less CPU outside the fetches: 2 + 2, 6 + 2 and 6 ms in the three steps
    "engine_dispatch_blocked_ms_per_step": (6.0, [CHAT], "scheduler", "tpot_p90_ms", "program_span", "ms", "lower"),
    "engine_dispatch_blocked_ms_per_step.backlog":
        (6.0, BACKLOGS, "scheduler", "serve_out_tokens_per_s", "program_span", "ms", "lower"),
    # enqueued 1 / 2 / 3 ms after admission, of prefills of 40 / 50 / 60 ms; the exact hit has no stamp
    "engine_prefill_host_p50_ms": (2.0, [CHAT], "scheduler", "ttft_p90_ms", "program_span", "ms", "lower"),
    "engine_prefill_device_p50_ms": (48.0, [CHAT], "scheduler", "ttft_p90_ms", "program_span", "ms", "lower"),
    # setup_s 77.25 ends at the window's start; the constructor began 70 s before that
    "setup_before_replica_s": (7.25, [CHAT] + BACKLOGS, "serve path", "setup_s", "program_span", "s", "lower"),
    "setup_weights_s": (11.75, [CHAT] + BACKLOGS, "serve path", "setup_s", "program_span", "s", "lower"),
    "setup_warmup_s": (33.5, [CHAT] + BACKLOGS, "serve path", "setup_s", "program_span", "s", "lower"),
    # the constructor returned 24 s before the window's start
    "setup_after_replica_s": (24.0, [CHAT] + BACKLOGS, "serve path", "setup_s", "program_span", "s", "lower"),
    # 96, 144 and 120 of 400 pages
    "kv_pages_reserved_share": (30.0, [CHAT], "scheduler", "ttft_p90_ms", "program_counter", "%", "lower"),
    "kv_pages_reserved_share.backlog":
        (30.0, BACKLOGS, "scheduler", "serve_out_tokens_per_s", "program_counter", "%", "higher"),
}
RING_READERS = sorted(n for n in HOST_AND_WAITS if not n.startswith("setup_"))


def _cpu_step(t, cpu_t, pages_reserved, **wall_and_cpu_ms):
    """A step record with both clocks: each phase as (wall ms, CPU ms)."""
    rec = _step(t, **{k: wall for k, (wall, _cpu) in wall_and_cpu_ms.items()})
    rec.update(cpu_t=cpu_t, pages_reserved=pages_reserved,
               phase_cpu_s={k: cpu / 1e3 for k, (_wall, cpu) in wall_and_cpu_ms.items()})
    return rec


def _record_with_both_clocks():
    record = _record()
    trace = record["stats"]["trace"]
    for r, enqueue_ms in zip(trace["requests"], (500, 1, 2, 3, 500)):
        r["prefill_enqueued"] = r["admitted"] + enqueue_ms / 1e3
    hit = _request(W0 + 25, 5, 9, 400, 1)  # an exact prefix hit: nothing was enqueued for it
    hit["prefill_enqueued"] = None
    trace["requests"].insert(4, hit)
    trace["steps"] = [
        _cpu_step(W0 - 5, 10.0, 100, admit=(90, 50), decode_fetch=(400, 1)),
        _cpu_step(W0 + 2, 20.0, 96, admit=(2, 2), prefill_dispatch=(6, 4), mirror_sync=(3, 1), emit=(1, 1),
                  prefill_fetch=(30, 0.5), decode_fetch=(400, 0.2)),
        _cpu_step(W0 + 3, 20.013, 144, admit=(1, 1), decode_dispatch=(9, 3), emit=(4, 4), retire_sync=(4, 2),
                  decode_fetch=(410, 0.1)),
        _cpu_step(W0 + 6, 20.030, 120, decode_dispatch=(10, 4), decode_fetch=(100, 0.1)),
        _cpu_step(W1 + 1, 30.0, 399, admit=(50, 1)),
    ]
    trace.update(steps_total=5, requests_total=6, pages_total=400)
    record["setup_s"] = 77.25
    record["stats"]["startup"] = {"init_began": W0 - 70.0, "init_ended": W0 - 24.0, "fetch_params_s": 0.25,
                                  "engine_init_s": 11.5, "warmup_s": 33.5, "programs": []}
    return record


@pytest.mark.parametrize("name", sorted(HOST_AND_WAITS))
def test_host_and_wait_reader_gives_the_hand_count(bench, name):
    cellspec, context = bench
    value = cellspec.load_metric(name)(context.Context(_record_with_both_clocks(), 1))
    assert value == pytest.approx(HOST_AND_WAITS[name][0], abs=1e-6)


@pytest.mark.parametrize("name", sorted(HOST_AND_WAITS))
def test_host_and_wait_reader_is_silent_on_the_parents_record(bench, name):
    """The parent's records have one clock, no `prefill_enqueued`, no
    `pages_reserved` or `pages_total` and three durations of start-up without a
    stamp: the reader returns None and does not raise, with or without stats."""
    cellspec, context = bench
    parents = _record()
    parents["setup_s"] = 77.25
    parents["stats"]["startup"] = {"fetch_params_s": 0.25, "engine_init_s": 11.5, "warmup_s": 33.5}
    bare = _record()
    bare["stats"] = None
    half = _record_with_both_clocks()  # a stamp of set-up's two is no account of set-up
    half["stats"]["trace"] = _record()["stats"]["trace"]
    del half["stats"]["startup"]["init_ended"]
    for record in (parents, _record(), bare, half):
        assert cellspec.load_metric(name)(context.Context(record, 1)) is None
    # one record of the window without the field is enough to blank a step reader
    mixed = _record_with_both_clocks()
    for key in ("cpu_t", "phase_cpu_s", "pages_reserved"):
        del mixed["stats"]["trace"]["steps"][2][key]
    if name in RING_READERS and "prefill" not in name:
        assert cellspec.load_metric(name)(context.Context(mixed, 1)) is None


@pytest.mark.parametrize("name", RING_READERS)
def test_host_and_wait_reader_is_silent_after_a_drop_inside_the_window(bench, name):
    cellspec, context = bench
    ring = "requests" if "prefill" in name else "steps"
    record = _record_with_both_clocks()
    trace = record["stats"]["trace"]
    trace[ring] = [r for r in trace[ring] if r.get("finished", r.get("t")) > W0 + 4]
    trace["dropped"][ring] = 1
    assert cellspec.load_metric(name)(context.Context(record, 1)) is None
    older = _record_with_both_clocks()  # the oldest record still held is older than the window
    older["stats"]["trace"]["dropped"][ring] = 1
    assert cellspec.load_metric(name)(context.Context(older, 1)) == pytest.approx(HOST_AND_WAITS[name][0], abs=1e-6)


def test_the_hosts_wall_time_is_its_cpu_time_plus_what_it_waited(bench):
    """engine_host_ms_per_step = the non-fetch phases' CPU + engine_dispatch_blocked_ms_per_step,
    by construction: (12 + 18 + 10) / 3 wall = (8 + 10 + 4) / 3 CPU + 6 blocked."""
    cellspec, context = bench
    ctx = context.Context(_record_with_both_clocks(), 1)
    wall = cellspec.load_metric("engine_host_ms_per_step")(ctx)
    blocked = cellspec.load_metric("engine_dispatch_blocked_ms_per_step")(ctx)
    assert wall == pytest.approx(40 / 3) and wall - blocked == pytest.approx(22 / 3)


def test_the_four_parts_of_set_up_add_up_to_it(bench):
    """Before the constructor's stamp, its weights and warm-up, after it: setup_s
    but for what lies between the stamps and outside the three durations (46 s
    between them here, 45.25 in the durations; milliseconds in the program)."""
    cellspec, context = bench
    ctx = context.Context(_record_with_both_clocks(), 1)
    parts = [cellspec.load_metric(f"setup_{part}_s")(ctx)
             for part in ("before_replica", "weights", "warmup", "after_replica")]
    assert sum(parts) == pytest.approx(77.25 - 0.75)


def test_one_step_in_the_window_gives_no_cpu_cycle(bench):
    cellspec, context = bench
    record = _record_with_both_clocks()
    record["stats"]["trace"]["steps"] = record["stats"]["trace"]["steps"][:2]
    assert cellspec.load_metric("engine_host_cpu_ms_per_step")(context.Context(record, 1)) is None
    assert cellspec.load_metric("engine_dispatch_blocked_ms_per_step")(context.Context(record, 1)) == pytest.approx(4.0)


@pytest.mark.parametrize("name", sorted(HOST_AND_WAITS))
def test_host_and_wait_metric_is_in_the_manifest_as_the_table_says(bench, name):
    import json

    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    _answer, cells, layer, moves, source, unit, better = HOST_AND_WAITS[name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": better, "source": source, "layer": layer, "moves": moves}
    assert set(cells) <= set(listed) and len(set(listed)) == len(listed)  # later cells may join the list
    moved = next(m for m in manifest["end_to_end"] if m["name"] == moves)
    assert set(listed) <= set(moved.get("workloads", listed)), "a cell that does not report what the metric moves"


# -- the reader of what PR 48 added to the step record ------------------------
@pytest.mark.parametrize("case,want", [
    ("counted", 25.0),  # 100 + 700 + 400 tokens in 256 + 768 + 2 x 288 rows; the step before the window is not read
    ("no prefill in the window", None),
    ("a record without the counters", None),  # the parent's: the line leaves the metric out
    ("no record", None),
    ("a drop inside the window", None),
])
def test_prefill_padding_share_reads_the_steps_own_counters(bench, case, want):
    cellspec, context = bench
    record = _record()
    trace = record["stats"]["trace"]
    counted = [(5000, 8192), (100 + 700, 256 + 768), (400, 2 * 288)]
    for s, (tokens, padded) in zip(trace["steps"], counted):
        s.update(prefill_tokens=tokens, prefill_padded=padded)
    if case == "no prefill in the window":
        for s in trace["steps"][1:]:
            s.update(prefill_tokens=0, prefill_padded=0)
    elif case == "a record without the counters":
        del trace["steps"][2]["prefill_padded"]
    elif case == "no record":
        record["stats"] = None
    elif case == "a drop inside the window":
        trace["steps"], trace["dropped"]["steps"] = trace["steps"][2:], 1
    value = cellspec.load_metric("prefill_padding_share")(context.Context(record, 1))
    assert value == (pytest.approx(want) if want is not None else None)


def test_prefill_padding_share_is_the_manifests_last_word_on_the_scheduler(bench):
    import json

    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == "prefill_padding_share")
    moved = next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")
    listed = entry.pop("workloads")
    assert entry == {"name": "prefill_padding_share", "unit": "%", "better": "lower", "source": "program_counter",
                     "layer": "scheduler", "moves": "serve_out_tokens_per_s"}
    assert listed[:6] == moved["workloads"][:6] and set(listed) <= set(moved["workloads"])  # later cells may join


# -- the readers of a start's stages (PR 57) ----------------------------------
# stats()["startup"] gained `ctor_began` and `stages`: what JAX traced, lowered
# and handed its backend before `init_began`, inside `engine_init` and inside
# `warmup`. One parametrised test a property, a case a metric.
SERVE_CELLS = [CHAT] + BACKLOGS + ["solar-open2-250b-ep8.backlog-long-ctx", "granite-4.0-h-micro.backlog-chat",
                                   "lfm2-24b-a2b.backlog-long-out", "gigachat3.5-432b-ep16.backlog-long-out"]
STAGE_READERS = {  # name: (answer, source, unit)
    "setup_warmup_trace_s": (12.5, "program_span", "s"),
    "setup_warmup_lower_s": (9.25, "program_span", "s"),
    "setup_warmup_backend_s": (4.0, "program_span", "s"),     # 21 cache reads and loads, 3 small compiles
    "setup_warmup_run_s": (7.75, "program_span", "s"),        # 33.5 less 12.5 + 9.25 + 4.0
    "setup_weights_jit_s": (0.5 + 0.25 + 2.0, "program_span", "s"),
    "setup_replica_backend_s": (6.5, "program_span", "s"),    # the constructor's first statement 76.5 s before the window
    "setup_cache_misses": (2.0 + 5.0 + 3.0, "program_counter", "count"),
    "setup_warmup_traces": (61.0, "program_counter", "count"),  # PR 58: kernel bodies traced, the calls the trace cache did not serve
}


def _stages(trace_s, lower_s, backend_s, miss_s, hits, misses, traces=0):
    return {"trace_s": trace_s, "lower_s": lower_s, "backend_s": backend_s, "miss_s": miss_s, "retrieval_s": 0.0,
            "traces": traces, "hits": hits, "misses": misses, "executables": hits + misses}


def _record_with_stages():
    record = _record_with_both_clocks()
    record["stats"]["startup"].update(
        ctor_began=W0 - 76.5,
        stages={"before": _stages(0.125, 0.0625, 0.25, 0.25, 0, 2, traces=2),
                "engine_init": _stages(0.5, 0.25, 2.0, 1.5, 4, 5, traces=11),
                "warmup": _stages(12.5, 9.25, 4.0, 0.5, 21, 3, traces=61)})
    return record


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_gives_the_hand_count(bench, name):
    cellspec, context = bench
    value = cellspec.load_metric(name)(context.Context(_record_with_stages(), 1))
    assert value == pytest.approx(STAGE_READERS[name][0], abs=1e-9)


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_reader_is_silent_on_the_parents_record(bench, name):
    """The parent's start-up record has the stamps and durations and neither
    `ctor_began` nor `stages`; an older one has no stamps; a run may have no
    stats at all: None each time, and nothing raised."""
    cellspec, context = bench
    bare = _record()
    bare["stats"] = None
    no_stamps = _record_with_stages()
    del no_stamps["stats"]["startup"]["init_ended"]
    half = _record_with_stages()
    del half["stats"]["startup"]["ctor_began"]
    for record in (_record_with_both_clocks(), _record(), bare, no_stamps, half):
        assert cellspec.load_metric(name)(context.Context(record, 1)) is None


def test_setup_warmup_traces_is_silent_on_a_record_whose_stages_count_no_traces(bench):
    """PR 57's record has `stages` without `traces` (the counter is PR 58's): the
    other stage readers read it, this one reads None and raises nothing."""
    cellspec, context = bench
    record = _record_with_stages()
    for part in record["stats"]["startup"]["stages"].values():
        del part["traces"]
    ctx = context.Context(record, 1)
    assert cellspec.load_metric("setup_warmup_traces")(ctx) is None
    assert cellspec.load_metric("setup_warmup_trace_s")(ctx) == pytest.approx(12.5)


def test_the_four_parts_of_warm_up_add_up_to_it(bench):
    cellspec, context = bench
    ctx = context.Context(_record_with_stages(), 1)
    parts = [cellspec.load_metric(f"setup_warmup_{part}_s")(ctx) for part in ("trace", "lower", "backend", "run")]
    assert sum(parts) == pytest.approx(cellspec.load_metric("setup_warmup_s")(ctx)) and parts[-1] >= 0


@pytest.mark.parametrize("name", sorted(STAGE_READERS))
def test_stage_metric_is_in_the_manifest_with_the_serve_cells(bench, name):
    import json

    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    _answer, source, unit = STAGE_READERS[name]
    listed = entry.pop("workloads")
    assert entry == {"name": name, "unit": unit, "better": "lower", "source": source, "layer": "serve path",
                     "moves": "setup_s"}
    # the cells of the four outside parts, which these lie inside; later serve cells may join
    outside = next(m for m in manifest["per_layer"] if m["name"] == "setup_warmup_s")["workloads"]
    assert listed[:9] == SERVE_CELLS == outside[:9] and len(set(listed)) == len(listed)
