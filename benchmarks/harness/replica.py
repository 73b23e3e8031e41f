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

import inspect
import os
import threading
import time

from ray_tpu.llm.deployment import LLMServer

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
TRACE_POLL_S = 0.05  # how often the traced part looks at the counters


def traced_part(limit_s: float, limit_units: int, units, clock=time.monotonic, sleep=time.sleep) -> dict:
    """Keep the trace open for limit_s seconds, or until `units()` (a count
    of the device work dispatched so far, read from the replica's counters)
    has grown by limit_units, whichever comes first: a trace's size, and the
    time stop_trace takes to write it, follow what it records and not the
    seconds it was open, so a faster program fills it sooner."""
    t0, u0, ended_by = clock(), units(), "time"
    while True:
        left = limit_s - (clock() - t0)
        if left <= 0:
            break
        if units() - u0 >= limit_units:
            ended_by = "volume"
            break
        sleep(min(TRACE_POLL_S, left))
    return {"traced_part_s": clock() - t0, "trace_units": units() - u0, "ended_by": ended_by}


def block_length_reader(decode_impl):
    """(args, kwargs) of a call of the decode program -> `n_steps`, its static
    block length, found by its name in the program's own signature (looked at
    once): a program whose cache is not two pools has it at another position."""
    at = list(inspect.signature(decode_impl).parameters).index("n_steps")
    return lambda args, kwargs: kwargs["n_steps"] if "n_steps" in kwargs else args[at]


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
        n_steps_of = block_length_reader(eng._decode_impl)
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
            n = n_steps_of(a, kw)
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
            "k_buckets": list(self.engine.k_buckets),
            "total_pages": self.engine.ec.total_pages, "max_slots": self.engine.ec.max_slots,
        }

    def bench_warm_groups(self, rounds: list, timeout_s: float = 600.0) -> dict:
        """Set-up for a sharded replica: admit prefill groups of every size the
        engine forms (k_buckets), in both states its device mirrors can be in.
        The engine's own warm-up compiles each size's mirror update against
        the mirrors as it made them, on one device; under a mesh a decode block
        returns them laid over it, an update's own result is laid out a third
        way, and the first update of a size against a layout it has not met
        compiles: inside the window, if the ramp happened not to bring the two
        together (PERF.md section 7).

        `rounds` is a list of (state, keeper, prompts). Each round ends when
        every request of it has retired, which leaves the mirrors as the
        host rewrote them ("retired"): the next round's prompts, all of one
        bucket and queued in one go, are admitted in the next step and split
        into groups by the engine. Under "decoded" a keeper request goes first and the
        prompts follow once its first token is out: they meet the mirrors as
        the keeper's decode block left them. These are requests like any
        other, through the server's own queue. Returns how many compilations
        each round caused."""
        eng = self.engine
        deadline = time.monotonic() + timeout_s

        def wait_for(done):
            while not done():
                if time.monotonic() > deadline:
                    raise TimeoutError("bench_warm_groups: the engine did not finish the warm-up requests")
                self._cond.wait(timeout=0.05)

        def add(tokens, max_tokens):
            rid = self._new_rid()
            self._life[rid] = eng.add_request(rid, tokens, max_tokens)
            return rid

        compiles, live = [], []

        def drain():
            wait_for(lambda: all(r in self._done for r in live))
            for r in live:
                del self._done[r]
            live.clear()

        with self._cond:
            for state, keeper, prompts in rounds:
                before = self._b["compiles"]
                if state == "decoded":
                    live.append(add(keeper, 3 * eng.ec.decode_block))
                    life = self._life[live[0]]
                    self._cond.notify_all()
                    wait_for(lambda: life["first_emitted"] is not None)
                live += [add(p, 2) for p in prompts]
                self._cond.notify_all()
                drain()
                compiles.append(self._b["compiles"] - before)
        return {"rounds": len(rounds), "requests": sum(len(p) for _s, _k, p in rounds), "compiles": compiles}

    def bench_trace_units(self) -> int:
        """What a trace's size follows: the layer passes dispatched so far, one
        for each decode step or prefilled request, layer and device (every
        pass is the decoder block's operations once more on each device, and
        the trace holds an event for every operation executed)."""
        b, eng = self._b, self.engine
        return (b["decode_steps"] + b["prefill_requests"]) * eng.cfg.n_layers * eng.ec.tensor_parallel

    def bench_trace_start(self, start_at: float, limit_s: float, limit_units: int, logdir: str) -> bool:
        """Trace the device from start_at (CLOCK_MONOTONIC) until limit_s
        seconds or limit_units of device work have passed (`traced_part`), in
        a thread of this process; returns at once."""
        self._b_annotate = True
        box = {"logdir": logdir, "done": threading.Event()}
        self._b_trace = box

        def run():
            import jax
            from jax.profiler import TraceAnnotation

            from harness import xplane

            try:
                time.sleep(max(0.0, start_at - time.monotonic()))
                box["counters_before"] = self.bench_counters()
                xplane.start(jax, logdir)
                with TraceAnnotation("bench.window"):
                    box.update(traced_part(limit_s, limit_units, self.bench_trace_units))
                box["counters_after"] = self.bench_counters()
                t = time.monotonic()
                jax.profiler.stop_trace()
                box["stop_trace_s"] = time.monotonic() - t
            except Exception as e:  # the thread's end has to be seen: the driver waits for it
                box["error"] = f"the trace thread failed: {e!r}"
            box["done"].set()

        threading.Thread(target=run, name="bench-trace", daemon=True).start()
        return True

    def bench_trace_result(self, wait_s: float) -> dict:
        """Where the finished trace lies, the counters at the traced part's
        two ends, how that part ended and what stop_trace cost; or, after
        wait_s seconds without stop_trace having returned, `pending`: the
        driver asks again for as long as the run's deadline leaves
        (serve_cell.await_trace), so that no call holds a thread of this
        replica for long. The cell's driver reduces the trace: parsing a trace
        of four devices holds this process's interpreter for longer than the
        serve controller waits for a heartbeat, and it kills the replica."""
        box = self._b_trace
        if box is None:
            return {"error": "no trace was started"}
        if not box["done"].wait(timeout=wait_s):
            return {"pending": True}
        self._b_annotate = False
        return {k: v for k, v in box.items() if k != "done"}

    def bench_reference_check(self, prompt: list, served: list, model: dict) -> dict:
        """`reference_check` on the engine's own weights: {"verdict":
        refcheck.judge's dict, "temp_bytes": each of the three programs'
        temporaries as compiled}."""
        verdict, temp_bytes = reference_check(self.engine.params, self.engine.cfg, model, prompt, served)
        return {"verdict": verdict, "temp_bytes": temp_bytes}


def reference_check(params, cfg, model: dict, prompt: list, served: list) -> tuple:
    """Were the tokens the served path returned for `prompt` (greedy) the
    plain float32 reference's choices, up to bf16's rounding, and is the
    program's own forward as close to the reference as bf16 allows?
    Teacher-forced: the reference's logits at every generated position,
    the program's own forward in bf16 there, and the reference from
    coarse weights as the yardstick (harness/refcheck.py `judge`).

    The weights lie on the device once. The yardstick is one jitted function
    of the engine's own parameters that runs the reference on
    `refcheck.read_coarsely(params)`: a layer's slice of a stacked weight is
    rounded where the reference reads it, and no coarse copy of the tree is
    ever an argument, a result or (as the TPU compiler made of rounding the
    whole tree inside the same jit) a temporary. Each of the three programs
    is compiled ahead and its temporaries are returned beside the verdict:
    (judge's dict, {"reference", "coarse", "own"} -> temp_size_in_bytes, or
    None where the backend does not say)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from harness import refcheck
    from harness.cellspec import architecture, routing
    from ray_tpu.models.transformer import forward

    reference = architecture(model)
    P, n = len(prompt), len(served)
    toks = jnp.asarray([list(prompt) + list(served)], jnp.int32)
    probe = slice(P - 1, P - 1 + n)
    own_cfg = dataclasses.replace(cfg, attention_impl="reference")
    logits, temp_bytes = {}, {}

    def run(name, fn):
        compiled = jax.jit(fn).lower(params, toks).compile()
        temp_bytes[name] = getattr(compiled.memory_analysis(), "temp_size_in_bytes", None)
        logits[name] = compiled(params, toks)

    with jax.default_matmul_precision("highest"):
        run("reference", lambda p, t: reference.logits(p, t, model)[0, probe])
        run("coarse", lambda p, t: reference.logits(refcheck.read_coarsely(p), t, model)[0, probe])
    run("own", lambda p, t: forward(p, t, own_cfg)[0][0, probe])
    verdict = refcheck.judge(logits["reference"], logits["own"], logits["coarse"], served, routing(model))
    return verdict, temp_bytes
