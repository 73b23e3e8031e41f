"""The readers that came with the paged kernel's page groups,
pages_per_grid_step and its twin for the cells above capacity, on
hand-written step records, and their entries in the manifest."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

from harness import cellspec  # noqa: E402
from harness.context import Context  # noqa: E402

W0, W1 = 1000.0, 1051.0
NAMES = ("pages_per_grid_step", "pages_per_grid_step.backlog")


def _record(steps):
    return {"kind": "serve", "seconds": W1 - W0, "config": {}, "traffic": {}, "traced": None,
            "client": {"w0": W0, "w1": W1, "records": []},
            "stats": {"trace": {"requests": [], "steps": steps, "dropped": {"requests": 0, "steps": 0}}}}


def _step(t, block, live, grid=None):
    rec = {"t": t, "dur": 0.07, "phase_s": {"decode_fetch": 0.02}, "block": block, "live_pages": live}
    return rec if grid is None else dict(rec, grid_steps=grid)


@pytest.mark.parametrize("name", NAMES)
def test_pages_over_grid_steps_of_the_blocks_that_started_in_the_window(name):
    """Two blocks in the window, 960 pages in 320 steps and 840 in 280 (a
    block before the window and a step without a block do not count):
    1,800 / 600 = 3 pages a grid step."""
    steps = [_step(W0 - 2, 8, 9999, 9), _step(W0 + 1, 8, 960, 320), _step(W0 + 2, 0, 0, 0), _step(W0 + 3, 8, 840, 280)]
    assert cellspec.load_metric(name)(Context(_record(steps), 1)) == pytest.approx(3.0, rel=1e-12)


@pytest.mark.parametrize("name", NAMES)
def test_it_reads_nothing_where_the_program_counts_no_grid_steps(name):
    """The parent's step records have no `grid_steps`; a window without a
    decode block has nothing to divide; a run without the record neither."""
    read = cellspec.load_metric(name)
    assert read(Context(_record([_step(W0 + 1, 8, 960), _step(W0 + 3, 8, 840)]), 1)) is None
    assert read(Context(_record([_step(W0 + 2, 0, 0, 0)]), 1)) is None
    assert read(Context(dict(_record([]), stats={}), 1)) is None


def test_the_manifest_lists_them_last_for_the_cells_whose_kernel_walks_page_groups():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        chat, backlog = json.load(f)["per_layer"][-2:]
    common = {"unit": "pages/step", "better": "higher", "source": "program_counter", "layer": "kernels"}
    assert chat == dict(common, name=NAMES[0], moves="tpot_p90_ms", workloads=["internlm2-1.8b.chat"])
    assert backlog == dict(common, name=NAMES[1], moves="serve_out_tokens_per_s",
                           workloads=["internlm2-1.8b.backlog", "mistral-7b.backlog-tp4",
                                      "laguna-s-2.1-ep8.backlog-long-ctx"])
