"""Each number `correct` is decided by is printed beside its limit: last in the
result line (`compared`) and as the last lines of standard error (run.py)."""
import json
import os
import sys

import numpy as np
import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

import run  # noqa: E402
from harness import refcheck  # noqa: E402


def _verdict(own_off, routing=None):
    ref = np.zeros((12, 4), np.float32)
    ref[:, 0] = 1.0
    return refcheck.judge(ref, ref + own_off, ref + 0.1, [0] * 12, routing)


@pytest.mark.parametrize("own_off,routing,passes", [(0.01, None, True), (0.05, None, False), (0.05, 16, True),
                                                    (0.12, 16, False)])
def test_a_serve_runs_numbers_are_within_their_limits_exactly_when_judge_passes(own_off, routing, passes):
    verdict = _verdict(own_off, routing)
    compared = run.serve_compared(verdict)
    assert set(compared) == {"worst_trail", "quietest_share_of_coarse", "noise_share_of_coarse"}
    assert all(value <= limit for value, limit in compared.values()) == verdict["ok"] == passes
    assert compared["noise_share_of_coarse"] == [verdict["noise_share_of_coarse"], refcheck.LIMITS["routed" if routing else "dense"][1]]
    assert compared["quietest_share_of_coarse"][1] == refcheck.LIMITS["routed" if routing else "dense"][0]


def test_a_served_token_that_trails_shows_in_its_own_number():
    ref = np.zeros((12, 4), np.float32)
    ref[:, 0] = 1.0
    verdict = refcheck.judge(ref, ref + 0.01, ref + 0.1, [0] * 11 + [1], None)
    value, limit = run.serve_compared(verdict)["worst_trail"]
    assert value == 1.0 and limit == pytest.approx(0.02) and verdict["refused_by"] == ["trail"]


def test_a_train_runs_numbers():
    worker = {"loss_program": 10.47, "loss_reference": 10.4725, "losses": [10.5] * 5 + [9.0] * 20 + [8.5] * 5}
    compared = run.train_compared(worker, 0.02)
    assert compared["loss_gap_to_reference"] == [pytest.approx(0.0025), 0.02]
    assert compared["loss_change_over_window"] == [pytest.approx(-2.0), 0.0]


def test_the_supervisors_last_lines_of_standard_error():
    line = json.dumps({"correct": True, "metrics": {}, "compared": {
        "worst_trail": {"value": 0.0, "limit": 0.15}, "window_compiles": {"value": 0, "limit": 0}}})
    assert run.compared_lines(line) == ["compared: worst_trail 0.0 (limit 0.15)", "compared: window_compiles 0 (limit 0)"]
    assert run.compared_lines(json.dumps({"correct": True})) == []  # a line of the parent's harness
