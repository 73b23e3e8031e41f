"""A train cell: JaxTrainer(...).fit() with the input pipeline running
(ray_tpu.data shard -> prefetch_to_device -> make_train_step). The driver
never touches JAX: the one train worker holds the chips. Everything measured
is measured inside the worker, where the steps are."""
from __future__ import annotations

import contextlib
import json
import os
import time

from harness.cellspec import transformer_kwargs

TRACE_STEPS = 6  # the traced part of a --trace 1 window
REFERENCE_TOKENS = 1024  # the reference's sequence: the first row, cut to this
# bf16 activations against the float32 reference, on a mean over ~1000
# targets of a loss near ln(vocab) ~ 10.4: the two agree to ~1e-3; a forward
# in a lower precision than bf16, or a wrong mask, is off by far more.
LOSS_TOL = 0.02
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def train_fn(cfg: dict) -> None:
    """Runs in the train worker."""
    import jax
    import numpy as np
    from jax.profiler import TraceAnnotation

    from harness import xplane
    from harness.cellspec import architecture
    from ray_tpu import train
    from ray_tpu.accel.device import device_report, enable_compile_cache
    from ray_tpu.data import prefetch_to_device
    from ray_tpu.models import TransformerConfig, make_train_step
    from ray_tpu.models.transformer import cross_entropy_loss
    from ray_tpu.parallel import MeshSpec, ShardingStrategy, logical_sharding
    from ray_tpu.parallel.sharding import use_strategy

    enable_compile_cache()
    report = device_report()
    if not cfg["rehearse"] and (report["platform"] != "tpu" or report["local_device_count"] < cfg["chips"]):
        raise RuntimeError(f"train worker sees {report}; the cell needs {cfg['chips']} TPU chip(s)")
    compiles = [0]
    jax.monitoring.register_event_duration_secs_listener(
        lambda name, _s, **_k: compiles.__setitem__(0, compiles[0] + (name == COMPILE_EVENT)))
    reference = architecture(cfg["config"])
    tcfg = TransformerConfig(**cfg["model"], **cfg["train_config"])
    rows, seconds, trace = cfg["batch_rows"], cfg["seconds"], cfg["trace"]
    cols = ("tokens", "segment_ids", "positions", "mask")

    mesh = MeshSpec(data=-1).build()
    strategy = ShardingStrategy.dp() if report["device_count"] > 1 else ShardingStrategy.none()
    init_state, train_step, state_axes = make_train_step(tcfg)
    spans = {"data_wait": 0.0, "dispatch": 0.0, "fence": 0.0}

    @contextlib.contextmanager
    def span(name):
        t = time.perf_counter()
        with TraceAnnotation("bench.train." + name) if trace else contextlib.nullcontext():
            yield
        spans[name] += time.perf_counter() - t

    with use_strategy(strategy), mesh:
        # Weights and optimizer state made on the device, in one jitted call
        # from the seed, already in the layout the step takes.
        axes = state_axes(jax.eval_shape(init_state, jax.random.PRNGKey(0)))
        state_sh = logical_sharding(mesh, strategy, axes)
        state = jax.jit(init_state, out_shardings=state_sh)(jax.random.PRNGKey(cfg["seed"] % (2 ** 31 - 1)))
        batch_sh = strategy.sharding(mesh, ("batch", "seq"))
        step = jax.jit(train_step, in_shardings=(state_sh, {c: batch_sh for c in cols}),
                       out_shardings=(state_sh, None), donate_argnums=(0,))
        shard = train.get_dataset_shard("train")

        def host_batches():
            while True:  # epochs: the shard streams again when it runs out
                for b in shard.iter_batches(batch_size=rows, drop_last=True):
                    yield {c: np.asarray(b[c], np.int32) for c in cols} | {"row": np.asarray(b["row"])}

        def device_batches():
            pending = []

            def strip(b):
                pending.append(b["row"])
                return {c: b[c] for c in cols}

            for dev in prefetch_to_device(host_batches(), size=2, sharding={c: batch_sh for c in cols},
                                          transform=strip):
                yield dev, pending.pop(0)

        batches = device_batches()
        first, _ = next(batches)
        warm_losses, losses, fenced_at = [], [], []
        for _ in range(cfg["warm_steps"]):  # the first compiles
            state, m = step(state, first)
            warm_losses.append(float(jax.block_until_ready(m["loss"])))
        warm_compiles = compiles[0]
        trained_of = np.asarray(cfg["trained_tokens_per_row"])
        # ---- the window: steps back to back, one in flight -----------------
        tokens_done, steps_done, in_flight, rows_seen = 0, 0, None, []
        tracing, traced, trace_tokens, trace_rows = False, not trace, 0, []
        for k in spans:
            spans[k] = 0.0
        t0 = time.perf_counter()
        train.report({"event": "window_start", "at": time.time(), "monotonic": time.monotonic()})
        while True:
            if not traced and time.perf_counter() - t0 >= 0.4 * seconds:
                traced = True
                jax.block_until_ready(state["step"])
                xplane.start(jax, cfg["trace_dir"])
                window_note = TraceAnnotation("bench.window")
                window_note.__enter__()
                tracing, t_trace0 = True, time.perf_counter()
            with span("data_wait"):
                batch, row_ids = next(batches)
            with span("dispatch"):
                state, m = step(state, batch)
            with span("fence"):
                if in_flight is not None:
                    losses.append(float(jax.block_until_ready(in_flight)))
                    fenced_at.append(time.perf_counter() - t0)
            in_flight = m["loss"]
            steps_done += 1
            tokens_done += int(trained_of[row_ids].sum())
            rows_seen.append(row_ids)
            if tracing:
                trace_tokens += int(trained_of[row_ids].sum())
                trace_rows.append([int(r) for r in row_ids])
                if len(trace_rows) == TRACE_STEPS:
                    jax.block_until_ready(m["loss"])
                    window_note.__exit__(None, None, None)
                    jax.profiler.stop_trace()
                    tracing, t_trace1 = False, time.perf_counter()
            if time.perf_counter() - t0 >= seconds:
                break
        losses.append(float(jax.block_until_ready(in_flight)))
        t1 = time.perf_counter()
        if tracing:
            window_note.__exit__(None, None, None)
            jax.profiler.stop_trace()
            t_trace1 = time.perf_counter()
        window_compiles = compiles[0] - warm_compiles
        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)

        # ---- correctness, outside the window: one sequence against the
        # plain float32 reference, at the weights as they now are ------------
        n_ref = min(REFERENCE_TOKENS, first["tokens"].shape[1] - 1) + 1
        one = {c: first[c][:1, :n_ref] for c in cols}
        own = float(jax.jit(lambda p, b: cross_entropy_loss(p, b, tcfg))(state["params"], one))
        with jax.default_matmul_precision("highest"):
            ref = float(jax.jit(lambda p, b: reference.packed_loss(p, b, cfg["config"]))(state["params"], one))

    out = {
        "event": "done", **report, "window_s": t1 - t0, "steps": steps_done, "tokens": tokens_done,
        "losses": losses, "warm_losses": warm_losses, "fenced_at": fenced_at, "spans": spans, "window_compiles": window_compiles,
        "warm_compiles": warm_compiles, "memory_peak_bytes": peak,
        "bytes_limit": [s.get("bytes_limit") for s in stats],
        "loss_program": own, "loss_reference": ref, "reference_tokens": n_ref - 1,
        "rows_seen": [[int(r) for r in ids] for ids in rows_seen[:4]],
    }
    if trace:
        traced = xplane.reduce_logdir(cfg["trace_dir"])
        traced.update(host_window_s=t_trace1 - t_trace0, tokens=trace_tokens, rows=trace_rows)
        out["traced"] = traced
    train.report(out)


def run(spec: dict, seed: int, seconds: float, trace: bool, rehearse: bool, t_start: float,
        workdir: str, say) -> dict:
    import numpy as np

    import ray_tpu as rt
    from ray_tpu import data, train
    from ray_tpu.accel.device import backend_initialized
    from ray_tpu.data import block as B

    from harness import schedule

    config, traffic, chips = spec["config"], spec["traffic"], spec["chips"]
    trn = dict(config["train"])
    rows = int(trn.pop("batch_rows")) * chips
    arrays = schedule.train_arrays(traffic, seed, config["vocab_size"])
    n_rows = len(arrays["doc_lens"])
    trained = schedule.trained_tokens_per_row(arrays["doc_lens"])
    say(f"offered: {json.dumps({'rows': n_rows, 'row_tokens': int(arrays['tokens'].shape[1]), 'trained_tokens_per_epoch': int(trained.sum()), 'documents': sum(len(d) for d in arrays['doc_lens']), 'batch_rows': rows})}")

    tpu = 0 if rehearse else chips
    rt.init(num_cpus=4, resources={"TPU": tpu} if tpu else None)
    try:
        # Blocks of one batch each, in the object store; the worker streams
        # its shard of them (ray_tpu.data) and prefetches onto the device.
        cols = {c: arrays[c] for c in ("tokens", "segment_ids", "positions", "mask")}
        cols["row"] = np.arange(n_rows, dtype=np.int64)
        blocks = [B.block_from_batch({c: v[i:i + rows] for c, v in cols.items()})
                  for i in range(0, n_rows - rows + 1, rows)]
        ds = data.from_blocks(blocks)
        model = transformer_kwargs(config)
        model["max_seq_len"] = int(traffic["seq_len"])
        loop_cfg = {
            "model": model, "train_config": trn, "config": config, "batch_rows": rows,
            "seconds": seconds, "trace": trace, "rehearse": rehearse, "chips": chips,
            "seed": seed, "warm_steps": int(traffic.get("warm_steps", 2)),
            "trained_tokens_per_row": trained.tolist(),
            "trace_dir": os.path.join(workdir, "trace"),
        }
        trainer = train.JaxTrainer(
            train_fn, train_loop_config=loop_cfg,
            scaling_config=train.ScalingConfig(
                num_workers=1, use_tpu=not rehearse,
                resources_per_worker={"TPU": tpu} if tpu else {"CPU": 1}),
            run_config=train.RunConfig(name="bench", storage_path=os.path.join(workdir, "train")),
            datasets={"train": ds},
        )
        result = trainer.fit()
        if result.error:
            raise SystemExit(f"benchmark: train worker failed:\n{result.error}")
        driver_touched_jax = backend_initialized()
    finally:
        rt.shutdown()
    history = result.metrics_history
    start = next(h for h in history if h.get("event") == "window_start")
    done = next(h for h in history if h.get("event") == "done")
    say(f"worker: {json.dumps({k: done[k] for k in ('steps', 'tokens', 'window_s', 'spans', 'window_compiles', 'warm_compiles', 'loss_program', 'loss_reference', 'rows_seen')})}")
    say(f"losses: warm-up {done['warm_losses']}, window first {done['losses'][:3]} last {done['losses'][-3:]}")
    gaps = [round(b - a, 4) for a, b in zip(done["fenced_at"], done["fenced_at"][1:])]
    say(f"seconds between fences: {gaps}")
    return {"kind": "train", "worker": done, "setup_s": start["at"] - t_start,
            "driver_touched_jax": driver_touched_jax, "seconds": seconds, "traffic": traffic,
            "config": config, "doc_lens": arrays["doc_lens"], "batch_rows": rows}
