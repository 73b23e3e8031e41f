"""A serve cell: serve.run(build_llm_app(...)), load over streaming HTTP from a
process of its own, numbers taken at the client. This driver never touches
JAX: the replica holds the chip."""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
import time

from harness import schedule
from harness.cellspec import BENCH_DIR, transformer_kwargs

APP, ROUTE = "bench", "/llm"
# The traced part of a --trace 1 window ends at the first of two limits
# (replica.traced_part). TRACE_S, the time: what every cell has been traced
# for since PR 23. TRACE_UNITS, the volume, in layer passes
# (replica.bench_trace_units): 4-5% over the 32,256-32,640 that
# mistral-7b.backlog-tp4, the largest, records in 6 s on PR 30's program (a
# trace of 65-66 MB, which stop_trace takes 135 s to write: 4 ms a pass on
# four chips, 3.4-3.9 on one); the one-chip cells record 9,300-12,400. A
# faster program reaches it sooner and is traced for less than TRACE_S.
TRACE_S = 6.0
TRACE_UNITS = 34_000
TRACE_ASK_S = 10.0  # one call of bench_trace_result waits this long for stop_trace
# What a run still does once it has its trace, before run.py's deadline. On
# four chips: reducing the trace 12-15 s, the reference check 7-8 s (32 s
# where it compiles), the shutdown 8-17 s (chip runs, PR 32); over twice their sum.
AFTER_TRACE_S = 150.0
PROBE = {"prompt_len": 96, "out_len": 12}


def _warmup_buckets(traffic: dict, engine: dict) -> list:
    """Only the prefill buckets this traffic's prompts can reach."""
    longest = int(traffic["prompt_len"].get("max", traffic["prompt_len"].get("value", 0)))
    longest += int((traffic.get("prefix") or {}).get("shared_len", 0))
    shortest = int(traffic["prompt_len"].get("min", traffic["prompt_len"].get("value", 1)))
    buckets = sorted(engine["prefill_buckets"])
    top = next((b for b in buckets if b >= longest), buckets[-1])
    bottom = next(b for b in buckets if b >= min(shortest, top))
    return [b for b in buckets if bottom <= b <= top]


def warm_group_rounds(engine: dict, bucket: int, seed: int, vocab: int, k_buckets: list) -> list:
    """The rounds of replica.bench_warm_groups for this engine: each group
    size alone, every size in one step (8 + 4 + 2 + 1: the later groups meet
    what the first one's update left), and the largest twice; each in both
    states of the mirrors. Prompts of half the cell's first warmed bucket,
    their tokens from the seed."""
    room = engine["max_slots"] - 1  # a keeper holds one slot
    sizes = sorted((k for k in k_buckets if k <= room), reverse=True)
    counts = sizes + [n for n in (sum(sizes), 2 * sizes[0]) if n <= room and n not in sizes]
    rounds, idx = [], 2 * 10 ** 6
    for state in ("retired", "decoded"):
        for n in counts:
            tokens = [schedule.prompt_tokens(seed, idx + j, bucket // 2, vocab) for j in range(n + 1)]
            rounds.append((state, tokens[0], tokens[1:]))
            idx += n + 1
    return rounds


def trace_bytes(logdir: str) -> int:
    return sum(os.path.getsize(os.path.join(d, f)) for d, _dirs, files in os.walk(logdir) for f in files)


def await_trace(ask, give_up_at: float, logdir: str, clock=time.monotonic) -> dict:
    """The finished trace's record, for which the replica's `stop_trace` is
    waited for until `give_up_at` (on `clock`): in calls of `ask(seconds)`
    that each wait at most TRACE_ASK_S in the replica. How long `stop_trace`
    takes follows the trace's size (about 4 ms a layer pass, 2 s a megabyte), so
    the wait follows what the run has left, not a constant. A trace that is
    not there by then is an `error` that says how long it was waited for and
    how many bytes of it are on disk."""
    t0 = clock()
    while True:
        out = ask(max(0.0, min(TRACE_ASK_S, give_up_at - clock())))
        if not out.get("pending"):
            break
        if clock() >= give_up_at:
            out = {"error": "stop_trace has not returned"}
            break
    out["trace_wait_s"] = clock() - t0
    if "error" in out:
        out["error"] += f" (waited {out['trace_wait_s']:.1f} s for it; {trace_bytes(logdir)} bytes of trace on disk)"
    return out


def _post(port: int, tokens: list, max_tokens: int) -> tuple[int, list]:
    import http.client

    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=600)
    try:
        conn.request("POST", ROUTE, json.dumps(
            {"tokens": tokens, "max_tokens": max_tokens, "stream": True, "ignore_eos": True}))
        resp = conn.getresponse()
        body = resp.read().decode()
    finally:
        conn.close()
    out = []
    for frame in body.split("\n\n"):
        if frame.startswith("data: ") and frame != "data: [DONE]":
            out += json.loads(frame[6:]).get("new_tokens", [])
    return resp.status, out


def run(spec: dict, seed: int, seconds: float, trace: bool, rehearse: bool, t_start: float,
        workdir: str, say, deadline: float) -> dict:
    """`deadline`: when run.py ends the run whatever its state, on time.time()."""
    import ray_tpu as rt
    from ray_tpu import serve
    from ray_tpu.accel.device import backend_initialized
    from ray_tpu.llm import build_llm_app

    from harness.replica import BenchLLMServer

    config, traffic, chips = spec["config"], spec["traffic"], spec["chips"]
    engine = dict(config["engine"])
    engine["seed"] = int(seed) % (2 ** 31 - 1)  # weights from the seed, on the device
    serve_opts = dict(config.get("serve") or {})
    model_kwargs = transformer_kwargs(config)
    plan = schedule.serve_plan(traffic, seed, seconds, engine["max_slots"])
    phase = "window" if plan["loop"] == "open" else "stream"
    say(f"offered: {json.dumps(schedule.plan_totals(plan, phase))} in phase {phase!r}"
        + (f" at {traffic['rate_rps']} requests/s" if plan["loop"] == "open"
           else f" to {plan['concurrency']} clients"))

    tpu = 0 if rehearse else chips
    rt.init(num_cpus=8, resources={"TPU": tpu} if tpu else None)
    try:
        serve.start()
        app = build_llm_app(
            model_config=model_kwargs, engine_config=engine,
            warmup_buckets=tuple(_warmup_buckets(traffic, engine)),
            ray_actor_options={"resources": {"TPU": float(tpu)}},
            **serve_opts,
        )
        # The application as build_llm_app made it, its class replaced by the
        # subclass that adds the benchmark's read-only methods and counters.
        app.deployment = dataclasses.replace(app.deployment, func_or_class=BenchLLMServer)
        app.deployment.config.startup_timeout_s = 1100.0
        t_run = time.time()
        serve.run(app, name=APP, route_prefix=ROUTE, timeout_s=1100)
        ready_s = time.time() - t_run
        replica = serve.get_deployment_handle("llm", APP)
        call = lambda m, *a, t=600: getattr(replica, m).remote(*a).result(timeout=t)  # noqa: E731
        dev0 = call("bench_device")
        port = serve.http_port()
        # One probe alone through the proxy: proves the path before load, and
        # its tokens are held to the reference after the window.
        probe_prompt = schedule.prompt_tokens(seed, 10 ** 6, PROBE["prompt_len"] if not rehearse else 12,
                                              config["vocab_size"])
        status, probe_out = _post(port, probe_prompt, PROBE["out_len"])
        if status != 200 or len(probe_out) != PROBE["out_len"]:
            raise SystemExit(f"benchmark: probe request failed: status {status}, {len(probe_out)} tokens")
        warm_groups = None
        if int(engine.get("tensor_parallel", 1)) > 1:
            # Under a mesh; on one device there is nothing to warm and the
            # replica is left as it was.
            warm_groups = call("bench_warm_groups", warm_group_rounds(
                engine, _warmup_buckets(traffic, engine)[0], seed, config["vocab_size"], dev0["k_buckets"]))
        call("bench_counters", True)

        start_at = time.monotonic() + 1.0
        # Process start to the first measured request: the ramp that brings
        # the server to a steady state is set-up, not window.
        setup_s = time.time() + 1.0 + plan["ramp_s"] - t_start
        job = {"plan": plan, "port": port, "route": ROUTE, "vocab": config["vocab_size"],
               "start_at": start_at, "drain_s": float(traffic.get("drain_s", 60.0)),
               "max_seq": engine["max_seq"]}
        job_path = os.path.join(workdir, "loadgen_job.json")
        with open(job_path, "w") as f:
            json.dump(job, f)
        gen = subprocess.Popen([sys.executable, os.path.join(BENCH_DIR, "harness", "loadgen.py"), job_path])
        trace_dir, trace_s = os.path.join(workdir, "trace"), min(TRACE_S, seconds)
        if trace:
            t_trace = start_at + plan["ramp_s"] + max(0.0, (seconds - TRACE_S) * 0.4)
            call("bench_trace_start", t_trace, trace_s, TRACE_UNITS, trace_dir)
        w0 = start_at + plan["ramp_s"]
        # Counters at the window's two ends (the replica answers between steps).
        time.sleep(max(0.0, w0 - time.monotonic()))
        c0 = call("bench_counters")
        time.sleep(max(0.0, w0 + seconds - time.monotonic()))
        c1 = call("bench_counters")
        rc = gen.wait(timeout=seconds + plan["ramp_s"] + job["drain_s"] + 120)
        if rc != 0:
            raise SystemExit(f"benchmark: the load generator exited with code {rc}")
        with open(job_path + ".out") as f:
            client = json.load(f)
        device = call("bench_device")
        stats = call("stats")
        traced = None
        if trace:
            from harness import xplane

            give_up_at = time.monotonic() + (deadline - AFTER_TRACE_S - time.time())
            traced = await_trace(lambda s: call("bench_trace_result", s, t=s + 60), give_up_at, trace_dir)
            if "error" in traced:
                raise SystemExit(f"benchmark: the traced run gave no trace: {traced['error']}")
            say(f"trace: open for {traced['traced_part_s']:.2f} s, ended by {traced['ended_by']} at "
                f"{traced['trace_units']} layer passes (limits {trace_s} s, {TRACE_UNITS}); "
                f"stop_trace took {traced['stop_trace_s']:.1f} s, of which the run waited "
                f"{traced['trace_wait_s']:.1f} s after the load's end")
            t_reduce = time.monotonic()
            traced.update(xplane.reduce_logdir(traced.pop("logdir")))  # parses a file: no backend, no chip
            traced["reduce_s"] = time.monotonic() - t_reduce
        t_check = time.monotonic()
        checked = call("bench_reference_check", probe_prompt, probe_out, config, t=900)
        check_s = time.monotonic() - t_check
        check = checked.pop("verdict")
        # `device` above was read before the check, as the run's peak has to be
        peak_after_check = call("bench_device")["memory_peak_bytes"]
        driver_touched_jax = backend_initialized()
    finally:
        serve.shutdown()
        rt.shutdown()

    window = {k: c1[k] - c0[k] for k in c0 if isinstance(c0[k], (int, float)) and k != "at"}
    window["queue_wait_s"] = c1["queue_wait_s"][len(c0["queue_wait_s"]):]
    window["seconds"] = c1["at"] - c0["at"]
    say(f"replica in the window: {json.dumps({k: v for k, v in window.items() if k != 'queue_wait_s'})}")
    say(f"replica: ready in {ready_s:.1f} s (engine init + warm-up {dev0['init_s']:.1f} s, of which "
        f"warm-up {dev0['warmup_s']:.1f} s); buckets warmed {_warmup_buckets(traffic, engine)}; "
        f"groups warmed before the ramp {json.dumps(warm_groups)}; reference check in {check_s:.1f} s, "
        f"the device's peak after it {peak_after_check} bytes ({device['memory_peak_bytes']} before it, "
        f"{max(b or 0 for b in device['bytes_in_use'])} in use then), its programs' temporaries "
        f"{json.dumps(checked['temp_bytes'])} {json.dumps(check)}")
    return {"kind": "serve", "plan": plan, "client": client, "window": window, "device": device,
            "traced": traced, "check": check, "setup_s": setup_s, "stats": stats,
            "driver_touched_jax": driver_touched_jax, "seconds": seconds, "traffic": traffic,
            "config": config, "engine": engine}
