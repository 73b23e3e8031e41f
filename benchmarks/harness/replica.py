"""The serve replica under test, with the benchmark's counters around it.

`BenchLLMServer` IS ray_tpu.llm.LLMServer (serve.run(build_llm_app(...))
deploys it with the application's own options); it adds read-only methods
the harness calls over the deployment handle, and wraps three calls of its
engine from here, because the program has no spans at those seams yet
(ROADMAP D9): engine.step, the decode program's dispatch, the prefill
program's dispatch. The wrappers count (a few dict updates per step, always
on, so every run can print what it did); under `--trace 1` they also write
jax.profiler.TraceAnnotations, so that a device idle gap can be laid to what
the host was doing. Only this process can trace the chip it holds.
"""
from __future__ import annotations

import os
import threading
import time

from ray_tpu.llm.deployment import LLMServer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class BenchLLMServer(LLMServer):
    def __init__(self, *args, **kwargs):
        t0 = time.monotonic()
        super().__init__(*args, **kwargs)
        self._b_init_s = time.monotonic() - t0
        self._b_annotate = False
        self._b_lock = threading.Lock()
        self._b_trace = None
        self._b_reset()
        import jax.monitoring

        def on_event(name, _secs, **_kw):
            if name == COMPILE_EVENT:
                self._b["compiles"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_event)
        eng = self.engine
        step, decode, prefill_of = eng.step, eng._decode_jit, eng._prefill
        from jax.profiler import TraceAnnotation

        def wrapped_step():
            b = self._b
            before = {s.req_id for s in eng.slots if s is not None}
            waiting = len(eng.waiting)
            t = time.perf_counter()
            if self._b_annotate:
                with TraceAnnotation("bench.engine.step"):
                    events = step()
            else:
                events = step()
            b["steps"] += 1
            b["step_s"] += time.perf_counter() - t
            b["steps_with_waiting"] += bool(waiting)
            for s in eng.slots:
                # Admission is the first thing a step does: a request that
                # got a slot in this step waited from its arrival until t.
                if s is not None and s.req_id not in before and s.req_id not in b["_seen"]:
                    b["_seen"].add(s.req_id)
                    b["queue_wait_s"].append(t - s.arrived_at)
            b["_last_step_end"] = time.perf_counter()
            return events

        def wrapped_decode(*a, **kw):
            b = self._b
            n = a[6] if eng.paged else a[5]  # n_steps, the static argument
            lens = eng.lengths
            active = [i for i, s in enumerate(eng.slots) if s is not None and i not in eng._prefilling]
            ctx = int(sum(int(lens[i]) for i in active))
            b["decode_blocks"] += 1
            b["decode_steps"] += n
            b["slot_steps_active"] += n * len(active)
            b["slot_steps_total"] += n * eng.ec.max_slots
            # step j of the block attends to len+1+j positions in each slot
            b["decode_context_tokens"] += n * ctx + len(active) * n * (n + 1) // 2
            if self._b_annotate:
                with TraceAnnotation("bench.decode.dispatch"):
                    return decode(*a, **kw)
            return decode(*a, **kw)

        def wrapped_prefill_of(bucket, k):
            fn = prefill_of(bucket, k)

            def call(*a, **kw):
                b = self._b
                b["prefill_calls"] += 1
                b["prefill_requests"] += k
                b["prefill_padded_tokens"] += bucket * k
                if self._b_annotate:
                    with TraceAnnotation("bench.prefill.dispatch"):
                        return fn(*a, **kw)
                return fn(*a, **kw)

            return call

        eng.step, eng._decode_jit, eng._prefill = wrapped_step, wrapped_decode, wrapped_prefill_of

    def _b_reset(self):
        self._b = {
            "steps": 0, "step_s": 0.0, "steps_with_waiting": 0, "queue_wait_s": [], "_seen": set(),
            "decode_blocks": 0, "decode_steps": 0, "slot_steps_active": 0, "slot_steps_total": 0,
            "decode_context_tokens": 0, "prefill_calls": 0, "prefill_requests": 0,
            "prefill_padded_tokens": 0, "compiles": 0, "_last_step_end": None,
        }

    # -- what the harness calls over the handle ----------------------------
    def bench_counters(self, reset: bool = False) -> dict:
        out = {k: v for k, v in self._b.items() if not k.startswith("_")}
        out["queue_wait_s"] = list(out["queue_wait_s"])
        out["at"] = time.monotonic()
        out["prefix_cache"] = self.engine.prefix_cache_stats if self.engine.ec.prefix_cache else None
        if reset:
            self._b_reset()
        return out

    def bench_device(self) -> dict:
        import jax

        stats = [d.memory_stats() or {} for d in jax.local_devices()]
        rep = self.device_report()
        return {
            "platform": rep["platform"], "kind": rep["device_kind"], "count": rep["device_count"],
            "memory_peak_bytes": max((s.get("peak_bytes_in_use", 0) for s in stats), default=0),
            "bytes_in_use": [s.get("bytes_in_use") for s in stats],
            "bytes_limit": [s.get("bytes_limit") for s in stats],
            "warmup_s": rep["warmup_s"], "init_s": self._b_init_s, "mosaic": rep["mosaic"],
            "compile_cache_dir": rep["compile_cache_dir"], "pid": os.getpid(),
            "buckets": list(self.engine.buckets), "block_sizes": list(self.engine.block_sizes),
            "total_pages": self.engine.ec.total_pages, "max_slots": self.engine.ec.max_slots,
        }

    def bench_trace_start(self, start_at: float, duration_s: float, logdir: str) -> bool:
        """Trace the device from start_at (CLOCK_MONOTONIC) for duration_s, in
        a thread of this process; returns at once."""
        self._b_annotate = True
        box = {"logdir": logdir, "done": threading.Event()}
        self._b_trace = box

        def run():
            import jax
            from jax.profiler import TraceAnnotation

            from harness import xplane

            time.sleep(max(0.0, start_at - time.monotonic()))
            box["counters_before"] = self.bench_counters()
            xplane.start(jax, logdir)
            with TraceAnnotation("bench.window"):
                time.sleep(duration_s)
            jax.profiler.stop_trace()
            box["counters_after"] = self.bench_counters()
            box["done"].set()

        threading.Thread(target=run, name="bench-trace", daemon=True).start()
        return True

    def bench_trace_result(self) -> dict:
        """The reduced trace (parsed here, after the window, where the file is)."""
        from harness import xplane

        box = self._b_trace
        if box is None or not box["done"].wait(timeout=120):
            return {"error": "no finished trace"}
        self._b_annotate = False
        summary = xplane.reduce_logdir(box["logdir"])
        summary["counters_before"], summary["counters_after"] = box["counters_before"], box["counters_after"]
        return summary

    def bench_reference_check(self, prompt: list, served: list, model: dict) -> dict:
        """Were the tokens the served path returned for `prompt` (greedy) the
        plain float32 reference's choices, up to bf16's rounding? Teacher-
        forced: the reference's logits at every generated position; bf16's
        error there is taken from the program's own forward in bf16. A served
        token may trail the reference's best logit by twice that error."""
        import dataclasses

        import jax
        import jax.numpy as jnp
        import numpy as np

        from harness import reference
        from ray_tpu.models.transformer import forward

        eng = self.engine
        P, n = len(prompt), len(served)
        toks = jnp.asarray([list(prompt) + list(served)], jnp.int32)
        with jax.default_matmul_precision("highest"):
            ref = jax.jit(lambda p, t: reference.logits(p, t, model)[0, P - 1: P - 1 + n])(eng.params, toks)
        ref = np.asarray(ref, np.float32)
        cfg = dataclasses.replace(eng.cfg, attention_impl="reference")
        own = jax.jit(lambda p, t: forward(p, t, cfg)[0][0, P - 1: P - 1 + n])(eng.params, toks)
        noise = float(np.abs(np.asarray(own, np.float32) - ref).max())
        chosen = ref[np.arange(n), np.asarray(served)]
        trail = float((ref.max(-1) - chosen).max())
        scale = float(np.abs(ref).max())
        return {"bf16_logit_error": noise, "worst_trail": trail, "logit_scale": scale,
                "tokens": n, "ok": bool(np.isfinite(ref).all() and trail <= 2 * noise
                                        and noise <= 0.05 * max(scale, 1.0))}
