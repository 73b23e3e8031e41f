"""Set-up of a sharded replica (harness/replica.py bench_warm_groups): after
it, no prefill group of any size compiles anything, in either state the
device mirrors can be in. A forced four-device CPU mesh stands for the four
chips; each case is a process of its own, because the device count is fixed
when JAX starts."""
import json
import os
import subprocess
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

SCRIPT = r"""
import json, sys
sys.path[:0] = [sys.argv[1], sys.argv[2]]
tp = int(sys.argv[3])
from harness import schedule
from harness.replica import BenchLLMServer
from harness.serve_cell import warm_group_rounds

model = dict(vocab_size=512, d_model=128, n_layers=2, n_heads=4, n_kv_heads=4, d_ff=256, max_seq_len=256,
             rope_theta=10000.0, attention_impl="auto", param_dtype="bfloat16")
engine = dict(kv_layout="paged", page_size=32, max_slots=32, max_seq=128, prefill_buckets=[32, 64],
              decode_block=4, tensor_parallel=tp, prefix_cache=True)
server = BenchLLMServer(model, engine, warmup_buckets=(32,))
server.generate(schedule.prompt_tokens(9, 0, 16, 512), max_tokens=3)  # the cell's probe: a first request's own compiles
out = []
for seed in range(3):
    out.append(server.bench_warm_groups(warm_group_rounds(engine, 32, seed, 512, server.engine.k_buckets)))
server._stop = True
print("RESULT " + json.dumps(out))
"""


def _run(tp: int) -> list:
    env = dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS="--xla_force_host_platform_device_count=4")
    proc = subprocess.run([sys.executable, "-c", SCRIPT, BENCH_DIR, ROOT, str(tp)], env=env, cwd=ROOT,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(next(ln for ln in proc.stdout.splitlines() if ln.startswith("RESULT "))[7:])


@pytest.fixture(scope="module")
def sharded():
    return _run(4)


def test_the_rounds_hold_every_group_size_in_both_states():
    sys.path.insert(0, BENCH_DIR)
    from harness.serve_cell import warm_group_rounds

    rounds = warm_group_rounds({"max_slots": 32}, 128, 7, 1000, [8, 4, 2, 1])
    assert [(s, len(p)) for s, _k, p in rounds] == [
        (s, n) for s in ("retired", "decoded") for n in (8, 4, 2, 1, 15, 16)]
    assert all(len(t) == 64 for _s, k, p in rounds for t in [k] + p)
    small = warm_group_rounds({"max_slots": 4}, 32, 7, 1000, [8, 4, 2, 1])  # --rehearse
    assert [len(p) for _s, _k, p in small] == [2, 1, 3] * 2


def test_the_engines_own_warm_up_leaves_the_sharded_updates_cold(sharded):
    """Why the step exists: under a mesh the first group of each size after a
    decode block compiles (8, 4, 2 and 1 rows, two mirrors each)."""
    first = sharded[0]
    assert first["rounds"] == 12 and first["requests"] == 2 * (8 + 4 + 2 + 1 + 15 + 16) and sum(first["compiles"]) >= 8, sharded


@pytest.mark.parametrize("again", (1, 2))
def test_after_it_no_group_size_compiles_again(sharded, again):
    assert sharded[again]["compiles"] == [0] * sharded[again]["rounds"], sharded


def test_on_one_device_there_is_nothing_to_warm():
    """serve_cell skips the step there; if it ran, it would compile nothing:
    one-chip cells are as they were."""
    assert [sum(r["compiles"]) for r in _run(1)] == [0, 0, 0]
