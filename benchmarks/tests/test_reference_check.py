"""The serve cells' comparison with the plain reference (harness/refcheck.py),
at toy widths on the CPU: it passes the program's own bf16 forward and refuses
a forward in fewer mantissa bits, at 2 and at 24 layers. The control is the
reference computed from weights kept in 3 mantissa bits (tests/control.py
says why that stands for the step below bfloat16); the same readings were
taken on the chip at the configurations' own sizes (PERF.md section 2).

The tolerance and its reason: the forward's error may be at most
refcheck.NOISE_LIMIT (0.4) of the error of the reference itself computed from
weights in 4 mantissa bits. On the chip the bf16 forward read 0.035-0.274 of
it over 97 draws of seven shapes, here 0.17-0.29 (sandbox CPU, toy width);
the control read 0.54-1.60 on the chip and reads 1.3-2.4 here, and the 4-bit reference put in the program's place
reads 1. A constant share of the logits' scale, the bound before (0.05),
passes the 4-bit forward at 2 layers (0.042-0.049 here) and refuses a sound
forward on weights that amplify rounding (0.059 on one seed of 32 layers).
"""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, HERE]

import control  # noqa: E402
from harness import cellspec, refcheck  # noqa: E402

SEEDS = (1, 2, 3, 4, 5, 6)


def _toy(layers):
    with open(os.path.join(BENCH_DIR, "configs", "internlm2-1.8b.json")) as f:
        model = json.load(f)
    cellspec.architecture(model).shrink(model)
    model["num_hidden_layers"] = layers
    return model


@pytest.fixture(scope="module")
def readings():
    return {layers: control.readings(_toy(layers), SEEDS) for layers in (2, 24)}


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("layers", (2, 24))
def test_the_programs_bf16_forward_passes(readings, layers, seed):
    verdict = readings[layers][SEEDS.index(seed)]["sound"]
    assert verdict["ok"], verdict


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("layers", (2, 24))
def test_a_forward_in_fewer_mantissa_bits_is_refused(readings, layers, seed):
    verdict = readings[layers][SEEDS.index(seed)]["control"]
    # its tokens are the reference's own, so only the own-noise bound can refuse it
    assert verdict["worst_trail"] <= 2 * verdict["bf16_logit_error"]
    assert not verdict["ok"] and verdict["noise_share_of_coarse"] > 2 * refcheck.NOISE_LIMIT, verdict


def test_the_checks_parts_each_refuse_alone():
    import numpy as np

    rng = np.random.default_rng(0)
    ref = rng.standard_normal((12, 512)).astype(np.float32)
    own, coarse = ref + 0.01, ref + 0.04
    best = ref.argmax(-1)
    assert refcheck.judge(ref, own, coarse, best)["ok"]
    # the yardstick itself in the program's place (the forward in 4 mantissa bits) reads 1
    as_coarse = refcheck.judge(ref, coarse, coarse, best)
    assert as_coarse["noise_share_of_coarse"] == 1.0 and not as_coarse["ok"]
    # a wrong token is refused whatever the noise
    assert not refcheck.judge(ref, own, coarse, ref.argmin(-1))["ok"]
    # a token inside twice the forward's own error of the best passes
    near = ref.copy()
    near[np.arange(12), best] -= 0.015  # the second best now leads by at most 0.015 somewhere
    assert refcheck.judge(near, near + 0.01, near + 0.04, best)["ok"]
    bad = ref.copy()
    bad[3, 7] = np.inf
    assert not refcheck.judge(bad, own, coarse, best)["ok"]
