"""Every reader that predates the program's own record (PR 24's have their
cases in tests/test_bench_metrics.py), each on a hand-written run record with
an answer worked out by hand. `run.py --rehearse` stops before the readers
run and a real run needs the chip, so nothing else executes them here."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import cellspec  # noqa: E402
from harness.context import Context  # noqa: E402

W0, W1 = 1000.0, 1051.0


def _config(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _request(due, late_ms, ttft_ms, tpot_ms, engine_ttft_ms, phase="window"):
    t_first = due + ttft_ms / 1e3
    t_last = t_first + 10 * tpot_ms / 1e3
    return {"phase": phase, "due": due, "sent": due + late_ms / 1e3, "status": 200, "error": None,
            "done": t_last, "n_out": 11, "out_len": 11, "t_first": t_first, "t_last": t_last,
            "engine_ttft_s": engine_ttft_ms / 1e3, "chunks": [[t_first, 1], [t_last, 10]]}


def _serve_record(loop):
    """Five requests due inside the window that finished (TTFT 100, 200, 300,
    400, 2000 ms; gaps 20, 30, 40, 50, 90 ms; the engine's own TTFT 60, 150,
    200, 250, 1900; sent 1, 2, 3, 4, 10 ms late), one that failed (sent 5 ms
    late), and one of the ramp, first token before the window, 7 of its tokens
    inside it."""
    ramp = _request(W0 - 3, 50, 700, 380, 600, phase="ramp")
    ramp["chunks"] = [[W0 - 1.0, 4], [W0 + 0.5, 7]]
    failed = dict(_request(W0 + 6, 5, 1, 1, 1), status=500, n_out=0, chunks=[], done=None)
    records = [ramp] + [_request(W0 + i, late, ttft, tpot, eng) for i, (late, ttft, tpot, eng) in enumerate(
        [(1, 100, 20, 60), (2, 200, 30, 150), (3, 300, 40, 200), (4, 400, 50, 250), (10, 2000, 90, 1900)], 1)]
    records.append(failed)
    traced = {"window_s": 6.0, "busy_s": 4.0, "devices": 1, "collective_exposed_s": 0.03,
              "module_s": {"jit__decode_impl": 2.0, "jit__prefill_batch_impl": 0.6},
              "module_runs": {"jit__decode_impl": 10, "jit__prefill_batch_impl": 3},
              "kernel": {"jit__decode_impl": {"seconds": 0.5, "calls": 240}},
              "counters_before": {"decode_steps": 100, "decode_context_tokens": 0, "slot_steps_active": 0},
              "counters_after": {"decode_steps": 120, "decode_context_tokens": 2_000_000, "slot_steps_active": 400}}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config("internlm2-1.8b"), "setup_s": 77.25,
            "traffic": {"limits": {"ttft_ms": 1500, "tpot_ms": 80}}, "plan": {"loop": loop},
            "client": {"w0": W0, "w1": W1, "records": records},
            "window": {"queue_wait_s": [0.01, 0.03, 0.02], "slot_steps_active": 300, "slot_steps_total": 400},
            "stats": {"prefix_cache": {"hits": 2, "partial_hits": 1, "misses": 7}},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


def _train_record():
    """510,000 trained tokens in 51 s, 0.51 s of them waiting for data; the
    traced steps held rows 0 and 1: documents of 4, 3 and 5 tokens (31 causal
    pairs, 9 targets); flash kernels 0.3 of 2.0 busy seconds."""
    traced = {"window_s": 2.2, "busy_s": 2.0, "devices": 1, "rows": [[0], [1]], "module_s": {"jit_train_step": 2.0},
              "module_runs": {"jit_train_step": 2}, "kernel": {"jit_train_step": {"seconds": 0.3, "calls": 12}}}
    worker = {"tokens": 510_000, "window_s": 51.0, "spans": {"data_wait": 0.51}, "device_kind": "TPU v5 lite",
              "traced": traced}
    return {"kind": "train", "seconds": 51.0, "config": _config("mistral-7b-v0.3-l2"), "traffic": {},
            "worker": worker, "doc_lens": [[4, 3], [5]], "setup_s": 24.5}


# One layer of internlm2-1.8b's decode attention (16 heads, 8 KV heads x 128,
# bf16) over the traced window's 10 steps: the counters' 20 steps held
# 2,000,000 context positions and 400 rows, so 10 hold 1,000,000 and 200.
PAGED_BYTES = 2 * 8 * 128 * 2 * 1_000_000 + 2 * 200 * 16 * 128 * 2
# Mistral-7B at 2 layers: 570,425,344 multiplied parameters; 12 positions.
TRAIN_FLOPS = 6.0 * 570_425_344 * 12 + 12.0 * 2 * 32 * 128 * 31
FLASH_BYTES = 6 * (12 * 32 * 128 * 2) + 6 * (12 * 8 * 128 * 2)

OPEN = {  # percentiles by linear interpolation at (n - 1) q / 100
    "generator_late_p99_ms": 5 + 0.95 * 5,                 # of 1, 2, 3, 4, 5, 10: the failed one counts
    "serve_added_ttft_p50_ms": 100.0,                      # of 40, 50, 100, 150, 100
    "ttft_p50_ms": 300.0, "ttft_p90_ms": 400 + 0.6 * 1600,
    "tpot_p50_ms": 40.0, "tpot_p90_ms": 50 + 0.6 * 40,
    "within_limits_share": 100.0 * 4 / 6,                  # the slow one and the failed one miss
    "serve_out_tokens_per_s": (5 * 11 + 7) / 51.0, "serve_out_tokens_per_s.open": (5 * 11 + 7) / 51.0,
    "sched_queue_wait_p50_ms": 20.0, "slot_occupancy": 75.0, "prefix_hit_share": 30.0,
    "decode_ms_per_step": 2.0 / (240 / 24) * 1e3, "prefill_busy_share": 10.0, "paged_attn_time_share": 12.5,
    "paged_attn_roofline": 100.0 * (24 * PAGED_BYTES / 819e9) / 0.5,  # bytes bind: 0.12 s against 0.001 of operations
    "setup_s": 77.25,
}
CLOSED = {  # every record counts; latency only of first tokens inside the window (the ramp's is before it)
    "ttft_p50_ms.backlog": 300.0, "tpot_p50_ms.backlog": 40.0, "slot_occupancy.backlog": 75.0,
    "decode_ms_per_step.backlog": 200.0, "prefill_busy_share.backlog": 10.0,
    "paged_attn_time_share.backlog": 12.5, "serve_out_tokens_per_s": (5 * 11 + 7) / 51.0,
}
TRAIN = {
    "train_tokens_per_s": 10_000.0, "data_wait_share": 1.0, "setup_s": 24.5,
    "train_mfu": 100.0 * (TRAIN_FLOPS / 9) * 10_000.0 / 197e12,
    "flash_time_share": 15.0,
    "flash_roofline": 100.0 * (2 * FLASH_BYTES / 819e9) / 0.3,  # bytes bind (1.8 us against 0.015 us)
}
CASES = ([("open", n, v) for n, v in OPEN.items()] + [("closed", n, v) for n, v in CLOSED.items()]
         + [("train", n, v) for n, v in TRAIN.items()])


@pytest.mark.parametrize("kind,name,known", CASES, ids=[f"{k}-{n}" for k, n, _ in CASES])
def test_reader_gives_the_hand_count(kind, name, known):
    record = _train_record() if kind == "train" else _serve_record(kind)
    assert cellspec.load_metric(name)(Context(record, 1)) == pytest.approx(known, rel=1e-9)


def test_collective_exposed_share_needs_more_than_one_device():
    record = _serve_record("closed")
    assert cellspec.load_metric("collective_exposed_share")(Context(record, 1)) is None
    record["traced"]["devices"] = 4
    assert cellspec.load_metric("collective_exposed_share")(Context(record, 4)) == pytest.approx(0.5)


def test_paged_attn_roofline_is_per_device_under_tensor_parallel():
    record = _serve_record("closed")
    record["config"]["engine"]["tensor_parallel"] = 4
    assert cellspec.load_metric("paged_attn_roofline")(Context(record, 4)) == pytest.approx(OPEN["paged_attn_roofline"] / 4)


@pytest.mark.parametrize("name", sorted(n for n in OPEN if n.startswith(("decode_ms", "prefill_busy", "paged_attn"))))
def test_trace_readers_are_silent_without_a_trace(name):
    record = _serve_record("open")
    record["traced"] = None
    assert cellspec.load_metric(name)(Context(record, 1)) is None


TWINS = [("engine_host_ms_per_step.backlog-tp4", "engine_host_ms_per_step"),  # their case is the last test of this file
         ("window_compiles.backlog-tp4", "window_compiles")]
ELSEWHERE_UNTIL_PR_35 = {  # the literal set this file listed: the rule below must still find each
    "engine_queue_wait_p50_ms", "engine_prefill_p50_ms", "first_emit_delay_p50_ms", "stream_wake_p50_ms",
    "engine_host_ms_per_step", "engine_host_ms_per_step.backlog", "window_compiles", "window_compiles.backlog",
    "engine_host_ms_per_step.backlog-tp4", "window_compiles.backlog-tp4"}


def _other_test_files() -> str:
    """The text of every benchmarks/tests/test_*.py but this one, and of
    tests/test_bench_metrics.py: a reader whose case lives in another file is
    named there in quotes, as a case's id or in a `load_metric` call."""
    import glob

    here = os.path.dirname(os.path.abspath(__file__))
    files = [f for f in glob.glob(os.path.join(here, "test_*.py")) if os.path.abspath(f) != os.path.abspath(__file__)]
    files.append(os.path.join(os.path.dirname(BENCH_DIR), "tests", "test_bench_metrics.py"))
    return "\n".join(open(f).read() for f in files if os.path.exists(f))


def _named_in(text: str, names) -> set:
    return {n for n in names if f'"{n}"' in text}


def test_every_reader_of_the_manifest_has_a_case_somewhere():
    """A reader is covered by a case of this file, or when its name is a
    string in another benchmarks/tests/test_*.py or in
    tests/test_bench_metrics.py: a new reader and its case arrive as two new
    files, and this one is not edited."""
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    here = set(OPEN) | set(CLOSED) | set(TRAIN) | {"collective_exposed_share"} | {twin for twin, _of in TWINS}
    elsewhere = _other_test_files()
    names = {m["name"] for m in manifest["end_to_end"] + manifest["per_layer"]}
    assert names - here - _named_in(elsewhere, names) == set()
    assert ELSEWHERE_UNTIL_PR_35 <= _named_in(elsewhere, ELSEWHERE_UNTIL_PR_35) | {twin for twin, _of in TWINS}


def test_the_rule_does_not_find_a_reader_that_has_no_case():
    assert _named_in(_other_test_files(), {"no_such_metric_p50_ms", "window_compiles"}) == {"window_compiles"}


@pytest.mark.parametrize("twin,of", TWINS)
def test_the_four_chip_cells_twins_read_what_their_originals_read(twin, of):
    steps = [{"t": W0 + 2, "dur": 0.03, "phase_s": {"admit": 0.012, "decode_fetch": 0.4}},
             {"t": W0 + 3, "dur": 0.03, "phase_s": {"emit": 0.018, "prefill_fetch": 0.1}}]
    record = _serve_record("closed")
    record["stats"]["trace"] = {"requests": [], "steps": steps, "dropped": {"requests": 0, "steps": 0},
                                "compiles": [[W0 - 9.0, 2.0], [W0 + 1.0, 0.05]], "compiles_total": 2}
    ctx = Context(record, 4)
    value = cellspec.load_metric(twin)(ctx)
    assert value == cellspec.load_metric(of)(ctx) == pytest.approx({"engine_host_ms_per_step": 15.0, "window_compiles": 1.0}[of])
