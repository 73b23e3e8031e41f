"""pages_per_grid_step.long-out, the counter that says how far the latent
kernel's page groups engage in the expert cell: on hand-written step records,
and its entry in the manifest by name."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import cellspec  # noqa: E402
from harness.context import Context  # noqa: E402

W0, W1 = 1000.0, 1051.0
NAME, CELL = "pages_per_grid_step.long-out", "openpangu-718b-ep16.backlog-long-out"


def _record(steps):
    return {"kind": "serve", "seconds": W1 - W0, "config": {}, "traffic": {}, "traced": None,
            "client": {"w0": W0, "w1": W1, "records": []},
            "stats": {"trace": {"requests": [], "steps": steps, "dropped": {"requests": 0, "steps": 0}}}}


def _step(t, block, live, grid=None):
    rec = {"t": t, "dur": 0.15, "phase_s": {"decode_fetch": 0.13}, "block": block, "live_pages": live}
    return rec if grid is None else dict(rec, grid_steps=grid)


@pytest.mark.parametrize("steps,want", [
    # 128 slots of 9 and 10 pages in two steps each, blocks of 8: (9,216 + 10,240) / 4,096; a block before the window
    # and a step without a block do not count
    ([_step(W0 - 2, 8, 99, 9), _step(W0 + 1, 8, 9216, 2048), _step(W0 + 2, 0, 0, 0), _step(W0 + 3, 8, 10240, 2048)], 4.75),
    # the walk a page a step, an empty slot's step counted as a page: 1.0 whatever the lengths
    ([_step(W0 + 1, 8, 9216 + 16, 9216 + 16), _step(W0 + 3, 8, 10240, 10240)], 1.0),
], ids=["page_groups", "a_page_a_step"])
def test_pages_over_grid_steps_of_the_blocks_that_started_in_the_window(steps, want):
    assert cellspec.load_metric(NAME)(Context(_record(steps), 1)) == pytest.approx(want, rel=1e-12)


def test_it_reads_nothing_where_there_is_nothing_to_read():
    """Step records without `grid_steps`, a window without a decode block, a
    run without the record: None, and no exception."""
    read = cellspec.load_metric(NAME)
    assert read(Context(_record([_step(W0 + 1, 8, 960), _step(W0 + 3, 8, 840)]), 1)) is None
    assert read(Context(_record([_step(W0 + 2, 0, 0, 0)]), 1)) is None
    assert read(Context(dict(_record([]), stats={}), 1)) is None


def test_the_manifest_has_it_for_the_expert_cell_alone_as_its_twins_are():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    twin = by_name["pages_per_grid_step.backlog"]
    assert by_name[NAME] == dict(twin, name=NAME, workloads=[CELL])
    assert (twin["unit"], twin["source"], twin["layer"], twin["moves"]) == (
        "pages/step", "program_counter", "kernels", "serve_out_tokens_per_s")
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == twin["moves"])["workloads"]
    assert NAME in {m["name"] for m in cellspec.load_cell(CELL)["per_layer"]}
    # the lists a test holds to their members stay as they were
    assert CELL not in twin["workloads"] and CELL not in by_name["pages_per_grid_step"]["workloads"]
