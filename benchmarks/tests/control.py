"""The reference check's readings, for any configuration file: what the
program's own forward reads against the plain float32 reference (sound), and
what two controls read, put in the program's place. `control`: the reference
computed from weights kept in 3 mantissa bits, which is what fp8 e4m3 holds,
the step below bfloat16 that would tempt a later PR; it moves every position,
and the quietest-position test has to refuse it. `wrong_position`: the sound
forward with one position's logits taken from the next position, a position
computed from the wrong context; it leaves the other positions alone, and
the every-position test has to refuse it. All as
harness/refcheck.judge reads them: shares of the coarse reference's error
(weights in 4 bits). The rounding is of the parameter tree, so it needs
nothing of an architecture's file but its reference.

On the chip, at a configuration's own size (harness/refcheck.py's limits were
set from these readings; PERF.md section 2):

    python3 benchmarks/tests/control.py --config internlm2-1.8b --seeds 12
    python3 benchmarks/tests/control.py --config ../selftest_data/routed_experts_olmoe --layers 8

(the second is the routed fixture at OLMoE's widths: --config is a name
relative to benchmarks/configs). A routed model's PR reads both controls so,
on its own configuration, before it adds its cell.

builds no engine and serves nothing: weights from each seed, made on the
device(s) in one jitted call with the engine's sharding, one sequence of the
probe's length. Tier-1-sized cases of the same are in test_reference_check.py.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)
if os.path.dirname(BENCH_DIR) not in sys.path:
    sys.path.insert(0, os.path.dirname(BENCH_DIR))

PROMPT, SERVED = 96, 12  # the probe's lengths (harness/serve_cell.py PROBE)
CONTROL_MANTISSA_BITS = 3
FORWARDS = ("sound", "control", "wrong_position")


def make_forwards(model: dict):
    """(init, sound, control, reference, coarse): jitted functions of the
    parameter tree and a [1, S] token array, for one configuration. Under
    tensor_parallel > 1 the weights are made sharded as LLMEngine makes them."""
    import jax

    from harness import refcheck
    from harness.cellspec import architecture
    from ray_tpu.models.transformer import TransformerConfig, forward, init_params

    arch = architecture(model)
    cfg = TransformerConfig(**arch.transformer_kwargs(model))
    cfg = dataclasses.replace(cfg, attention_impl="reference")
    tp = int((model.get("engine") or {}).get("tensor_parallel", 1))
    shardings = None
    if tp > 1:
        from ray_tpu.models.transformer import param_logical_axes
        from ray_tpu.parallel.mesh import MeshSpec
        from ray_tpu.parallel.sharding import ShardingStrategy, logical_sharding

        mesh = MeshSpec(tensor=tp).build(jax.devices()[:tp])
        shardings = logical_sharding(mesh, ShardingStrategy.tp(), param_logical_axes(cfg))
    n = slice(PROMPT - 1, PROMPT - 1 + SERVED)
    init = jax.jit(lambda key: init_params(key, cfg), out_shardings=shardings)
    sound = jax.jit(lambda p, t: forward(p, t, cfg)[0][0, n])

    def plain(p, t):
        with jax.default_matmul_precision("highest"):
            return arch.logits(p, t, model)[0, n]

    reference = jax.jit(plain)
    # rounded where the reference reads a layer's slice, as the replica's check
    # computes its yardstick: no rounded copy of a stacked weight is held
    control = jax.jit(lambda p, t: plain(refcheck.read_coarsely(p, CONTROL_MANTISSA_BITS), t))
    coarse = jax.jit(lambda p, t: plain(refcheck.read_coarsely(p), t))
    return init, sound, control, reference, coarse


def readings(model: dict, seeds) -> list:
    """For each seed the check's verdict on the program's forward and on the
    two controls, judged as the replica judges (harness/refcheck.judge, with
    the architecture's routing declaration), the reference's own greedy
    tokens as the served ones."""
    import jax
    import numpy as np

    from harness import cellspec, refcheck, schedule

    init, sound, control, reference, coarse = make_forwards(model)
    routing = cellspec.routing(model)

    def rms(a):
        return float(np.sqrt(np.mean(np.square(a, dtype=np.float64))))

    out = []
    for seed in seeds:
        params = init(jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
        toks = np.asarray([schedule.prompt_tokens(seed, 10 ** 6, PROMPT + SERVED, model["vocab_size"])], np.int32)
        ref = np.asarray(reference(params, toks), np.float32)
        served, yard = ref.argmax(-1), np.asarray(coarse(params, toks), np.float32)
        own = np.asarray(sound(params, toks), np.float32)
        moved = own.copy()
        at = int(seed) % SERVED
        moved[at] = own[(at + 1) % SERVED]
        row = {"seed": int(seed)}
        for name, logits in zip(FORWARDS, (own, control(params, toks), moved)):
            row[name] = refcheck.judge(ref, logits, yard, served, routing)
            # no test of judge's: read beside them (PERF.md section 7)
            row[name]["rms_share_of_coarse"] = rms(np.asarray(logits, np.float32) - ref) / rms(yard - ref)
        out.append(row)
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True, help="a file of benchmarks/configs, without .json")
    ap.add_argument("--seeds", type=int, default=12, help="how many (1000003 * i + 17, i = 1..)")
    ap.add_argument("--layers", type=int, help="another depth than the file's (to read how the error grows)")
    ap.add_argument("--tensor-parallel", type=int, help="another degree than the file's")
    args = ap.parse_args()
    import jax

    with open(os.path.join(BENCH_DIR, "configs", args.config + ".json")) as f:
        model = json.load(f)
    if args.layers:
        model["num_hidden_layers"] = args.layers
    if args.tensor_parallel:
        model.setdefault("engine", {})["tensor_parallel"] = args.tensor_parallel
    tp = int((model.get("engine") or {}).get("tensor_parallel", 1))
    if jax.default_backend() != "tpu" or len(jax.devices()) < tp:
        raise SystemExit(f"control: needs {tp} TPU chip(s); JAX sees {jax.devices()}")
    rows = readings(model, [1000003 * i + 17 for i in range(1, args.seeds + 1)])
    for r in rows:
        print(json.dumps(r), flush=True)
    def span(which, key):
        values = [r[which][key] for r in rows]
        return [min(values), max(values)]

    print(json.dumps({
        "config": args.config, "layers": model["num_hidden_layers"], "tensor_parallel": tp,
        "device": jax.devices()[0].device_kind, "seeds": len(rows), "routing": rows[0]["sound"]["routing"],
        # [least, most] over the seeds, of each forward by each test's statistic
        "quietest_share_of_coarse": {w: span(w, "quietest_share_of_coarse") for w in FORWARDS},
        "noise_share_of_coarse": {w: span(w, "noise_share_of_coarse") for w in FORWARDS},
        "rms_share_of_coarse": {w: span(w, "rms_share_of_coarse") for w in FORWARDS},
        "quietest_limit": rows[0]["sound"]["quietest_limit"], "position_limit": rows[0]["sound"]["position_limit"],
        "sound_all_ok": all(r["sound"]["ok"] for r in rows),
        "control_all_refused_at_the_quietest_position": all(
            "quietest_position" in r["control"]["refused_by"] for r in rows),
        "wrong_position_all_refused_at_every_position_alone": all(
            r["wrong_position"]["refused_by"] == ["every_position"] for r in rows)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
