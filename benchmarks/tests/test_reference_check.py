"""The serve cells' comparison with the plain reference (harness/refcheck.py),
at toy widths on the CPU: it passes the program's own bf16 forward and refuses
a forward in fewer mantissa bits, at 2 and at 24 layers. The control is the
reference computed from weights kept in 3 mantissa bits (tests/control.py
says why that stands for the step below bfloat16); the same readings were
taken on the chip at the configurations' own sizes (PERF.md section 2).

Two tests of the forward since PR 28: the quietest of the probe's positions,
which the 3-bit control fails and one odd position does not move, and every
position, which one wrong position fails; for an architecture that declares
routing (the fixture in selftest_data/) both are wide enough for near-ties
between experts resolved the other way.

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
ROUTED = os.path.join(os.pardir, "selftest_data", "routed_experts_olmoe")


def _toy(layers, config="internlm2-1.8b"):
    with open(os.path.join(BENCH_DIR, "configs", config + ".json")) as f:
        model = json.load(f)
    cellspec.architecture(model).shrink(model)
    model["num_hidden_layers"] = layers
    return model


@pytest.fixture(scope="module")
def readings():
    return {layers: control.readings(_toy(layers), SEEDS) for layers in (2, 24)}


@pytest.fixture(scope="module")
def routed_readings():
    return control.readings(_toy(2, ROUTED), SEEDS)


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
    assert "quietest_position" in verdict["refused_by"] and verdict["quietest_share_of_coarse"] > 2 * refcheck.NOISE_LIMIT


@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("layers", (2, 24))
def test_one_wrong_position_is_refused_by_the_every_position_test_alone(readings, layers, seed):
    sound, verdict = (readings[layers][SEEDS.index(seed)][k] for k in ("sound", "wrong_position"))
    assert verdict["refused_by"] == ["every_position"] and verdict["routing"] is None, verdict
    assert verdict["noise_share_of_coarse"] > 5 * verdict["position_limit"] == 5 * refcheck.NOISE_LIMIT
    # the quietest of twelve positions is another one at most
    assert verdict["quietest_share_of_coarse"] < 1.2 * sound["quietest_share_of_coarse"] < refcheck.NOISE_LIMIT


@pytest.mark.parametrize("seed", SEEDS)
def test_routed_fixture_sound_passes_and_both_controls_are_refused(routed_readings, seed):
    row = routed_readings[SEEDS.index(seed)]
    assert row["sound"]["ok"] and row["sound"]["routing"] == 2, row["sound"]
    assert (row["sound"]["quietest_limit"], row["sound"]["position_limit"]) == refcheck.LIMITS["routed"]
    assert "quietest_position" in row["control"]["refused_by"], row["control"]
    assert row["wrong_position"]["refused_by"] == ["every_position"], row["wrong_position"]


@pytest.mark.parametrize("seed", (1, 2, 3))
def test_a_planted_near_tie_flip_passes_only_with_the_routing_declaration(seed):
    """One probe position whose last kept and first dropped expert change
    places in the first layer (the fixture reference's `flip`), added to the
    program's own bf16 error: accepted for an architecture that declares
    routing; the same arrays without the declaration are refused, as every
    architecture's were before PR 28."""
    import jax
    import numpy as np

    from harness import schedule

    model = _toy(2, ROUTED)
    init, sound, _control, reference, coarse = control.make_forwards(model)
    params = init(jax.random.PRNGKey(seed))
    toks = np.asarray([schedule.prompt_tokens(seed, 10 ** 6, control.PROMPT + control.SERVED, model["vocab_size"])],
                      np.int32)
    ref, own = np.asarray(reference(params, toks)), np.asarray(sound(params, toks), np.float32)
    at = 5
    with jax.default_matmul_precision("highest"):
        flipped = cellspec.architecture(model).logits(params, toks, model, flip=(0, control.PROMPT - 1 + at))
    planted = own + np.asarray(flipped)[0, control.PROMPT - 1:control.PROMPT - 1 + control.SERVED] - ref
    yard, served = coarse(params, toks), ref.argmax(-1)
    declared = refcheck.judge(ref, planted, yard, served, cellspec.routing(model))
    assert declared["ok"] and int(np.argmax(declared["position_noise"])) == at, declared
    assert refcheck.NOISE_LIMIT < declared["noise_share_of_coarse"] < refcheck.LIMITS["routed"][1]
    undeclared = refcheck.judge(ref, planted, yard, served)
    assert undeclared["refused_by"] == ["every_position"], undeclared
    assert {k: v for k, v in undeclared.items() if k in OLD_KEYS - {"ok"}} == \
        {k: v for k, v in declared.items() if k in OLD_KEYS - {"ok"}}


OLD_KEYS = {"bf16_logit_error", "worst_trail", "logit_scale", "coarse_logit_error", "noise_share_of_coarse",
            "noise_limit", "position_noise", "tokens", "ok"}


def _hand_made():
    """The arrays on which the parent commit's judge printed FROZEN below."""
    import numpy as np

    rng = np.random.default_rng(28)
    ref = rng.standard_normal((12, 512)).astype(np.float32)
    own = ref + 0.01 * rng.standard_normal((12, 512)).astype(np.float32)
    coarse = ref + 0.04 * rng.standard_normal((12, 512)).astype(np.float32)
    served = ref.argmax(-1)
    served[4] = int(np.argsort(ref[4])[-2])
    return ref, own, coarse, served


FROZEN = {"bf16_logit_error": 0.03703415393829346, "worst_trail": 0.017649173736572266,
          "logit_scale": 3.851085901260376, "coarse_logit_error": 0.1531032919883728,
          "noise_share_of_coarse": 0.24188999111205237, "noise_limit": 0.4,
          "position_noise": [0.037, 0.0259, 0.0348, 0.0303, 0.0316, 0.0333, 0.0305, 0.0252, 0.0305, 0.0297,
                             0.0339, 0.032], "tokens": 12, "ok": True}


@pytest.mark.parametrize("key", sorted(OLD_KEYS))
def test_the_records_old_keys_are_the_parents(key):
    """harness/refcheck.judge at commit 586e00e (PR 26) on _hand_made()."""
    assert set(FROZEN) == OLD_KEYS
    assert refcheck.judge(*_hand_made())[key] == FROZEN[key]


@pytest.mark.parametrize("routing", (None, 8))
@pytest.mark.parametrize("factor", (0.3, 0.39, 0.41, 1.0, 1.5, 1.7))
def test_the_every_position_test_is_the_parents_expression_for_a_dense_architecture(factor, routing):
    """One position alone at `factor` of the coarse reference's largest
    error: what the parent's one test, max |own - ref| <= 0.4 max |coarse -
    ref|, decided is what the every-position test decides without a routing
    declaration; with one the multiple is LIMITS["routed"]'s."""
    import numpy as np

    ref, own, coarse, served = _hand_made()
    served = ref.argmax(-1)
    own = own.copy()
    own[7, 100] = ref[7, 100] + factor * np.abs(coarse - ref).max()
    verdict = refcheck.judge(ref, own, coarse, served, routing)
    parents = bool(np.abs(own - ref).max() <= 0.4 * np.abs(coarse - ref).max())
    passes = parents if routing is None else factor < refcheck.LIMITS["routed"][1]
    assert verdict["ok"] == passes and verdict["refused_by"] == ([] if passes else ["every_position"])
    assert (verdict["noise_share_of_coarse"] <= 0.4) == parents


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
    # every position at 1.2 x the coarse error: refused with and without the declaration
    for routing in (None, 8):
        assert "quietest_position" in refcheck.judge(ref, ref + 1.2 * (coarse - ref), coarse, best, routing)["refused_by"]
