"""What `mellum2-12b-ep4.pretrain-8k`'s spread over seeds is made of (PERF.md section 6, PR 59, third session):
the cell's own step in `harness/train_cell.py`'s order (two warm steps on the first batch, then the batches in
order), under `make_train_step`'s own optimizer (AdamW, a constant 3e-4 from step 0) or under the same AdamW
with a linear warm-up over `--warmup` steps to that 3e-4 (Llama 2, section 2.2: peak 3e-4, 2000 warm-up steps),
every other value the cell's. By seed: the pairs on the held experts and the live tiles by step (from
`make_train_step`'s metrics, which the harness does not relay), the trained tokens over the steps' seconds and
the loss's first and last. A step's seconds follow the live tiles (0.31 us a pair on a v5e), so seeds whose
routing is thrown about read apart; with the routing level they read what their rows and tokens differ by.

    chiprun --timeout 1500 -- python3 benchmarks/checks/held_load_mellum2.py --seeds 11,2158000311,77 \\
        --steps 94 --warmup 0,2000        # about a minute a seed and schedule on a v5e, two minutes to compile
    python3 benchmarks/checks/held_load_mellum2.py --toy --steps 3    # the CPU, toy widths: runs, shows nothing

Read on a v5e (PR 59's builder, six seeds, 94 steps): constant 3e-4: 52-180 k pairs a step where 131,072 are
expected, sets of six of the cell itself spread 0.0035-0.0101; warm-up 2000: 116-146 k pairs, 28,714.3-28,788.3
tokens/s over five seeds, spread 0.0023 (a sixth met a stalled step of 4.2 s). Judges nothing: exit code 0."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "benchmarks")]

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402
from harness import cellspec, schedule  # noqa: E402

from ray_tpu.models.transformer import TransformerConfig, make_train_step  # noqa: E402

ap = argparse.ArgumentParser()
ap.add_argument("--seeds", default="0", help="comma-separated; each draws the weights and orders the rows")
ap.add_argument("--steps", type=int, default=94, help="steps after the two warm ones (a window of 51 s holds 94)")
ap.add_argument("--warmup", default="0,2000", help="comma-separated; 0: make_train_step's own constant rate")
ap.add_argument("--every", type=int, default=6, help="a reading of the counters every so many steps")
ap.add_argument("--toy", action="store_true", help="the rehearsal's widths, for the CPU")
args = ap.parse_args()

spec = cellspec.load_cell("mellum2-12b-ep4.pretrain-8k")
if args.toy:
    spec = cellspec.shrink_for_rehearsal(spec)
config, traffic = spec["config"], spec["traffic"]
kw = cellspec.transformer_kwargs(config)
kw["max_seq_len"] = int(traffic["seq_len"])
trn = dict(config["train"])
rows = int(trn.pop("batch_rows"))
cfg = TransformerConfig(**kw, **trn)
COLS = ("tokens", "segment_ids", "positions", "mask")

for warmup in (int(w) for w in args.warmup.split(",")):
    optimizer = optax.adamw(optax.linear_schedule(0.0, 3e-4, warmup), weight_decay=0.01) if warmup else None
    init, step, _ = make_train_step(cfg, optimizer)
    init, step = jax.jit(init), jax.jit(step, donate_argnums=(0,))
    for seed in (int(s) for s in args.seeds.split(",")):
        arrays = schedule.train_arrays(traffic, seed, config["vocab_size"])
        trained = schedule.trained_tokens_per_row(arrays["doc_lens"])
        n = len(arrays["doc_lens"]) // rows
        at = lambda i: slice((i % n) * rows, (i % n) * rows + rows)  # noqa: E731
        state = init(jax.random.PRNGKey(seed % (2 ** 31 - 1)))
        for _ in range(2):
            state, m = step(state, {c: jnp.asarray(arrays[c][at(0)]) for c in COLS})
            float(m["loss"])
        readings, seconds, losses, tokens = [], [], [], 0
        for i in range(1, args.steps + 1):
            batch = {c: jnp.asarray(arrays[c][at(i)]) for c in COLS}
            t0 = time.perf_counter()
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
            seconds.append(time.perf_counter() - t0)
            tokens += int(trained[at(i)].sum())
            if (i - 1) % args.every == 0 or i == args.steps:
                readings.append([i, int(m["expert_pairs"]), int(m["expert_live_tiles"]), round(float(m["balance_loss"]), 3)])
        pairs = [r[1] for r in readings]
        print(json.dumps({
            "warmup_steps": warmup, "seed": seed, "tokens_per_s": round(tokens / sum(seconds), 1),
            "step_s": {"mean": round(float(np.mean(seconds)), 5), "max": round(max(seconds), 4)},
            "held_pairs": {"expected": rows * int(traffic["seq_len"]) * cfg.expert_top_k * cfg.experts_held
                           // cfg.n_experts * cfg.n_layers, "least": min(pairs), "most": max(pairs)},
            "loss_first_last": [round(float(np.mean(losses[:5])), 4), round(float(np.mean(losses[-5:])), 4)],
            "step_pairs_tiles_balance": readings}), flush=True)
        del state
