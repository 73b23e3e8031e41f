"""One cell of the benchmark, one run, one JSON line.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 benchmarks/run.py --workload <cell> --rehearse   toy sizes on the CPU: counts, no metric
    python3 benchmarks/run.py --selftest                     the yardstick's own checks, on the CPU

The process you start is a supervisor that never imports jax or ray_tpu: it
runs the cell in a child that leads a session of its own, and when the child
is done, or the supervisor is told to end, it kills that session and reaps
every process of it (the supervisor is their subreaper: a process can be
reaped only when its last thread has gone, and a killed chip holder lets go
of the chip seconds after its leader dies). Should the supervisor itself be
killed, the child sees its lifeline close and kills the session. The child
drives the program's normal entry points and never touches JAX either; the
chip belongs to the serve replica or the train worker.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import ctypes  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import select  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
RESULT_TAG = "BENCH_RESULT "
DEADLINE_S = 1150  # a first run compiles; the driver allows it 1200 s


def say(msg: str) -> None:
    print(msg, flush=True)


# ---------------------------------------------------------------------------
# The child: run the cell, reduce it to the metrics of the line
# ---------------------------------------------------------------------------

def _clean_name(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_./-]+", "_", name).strip("_")


def child(args) -> None:
    sys.path.insert(0, BENCH_DIR)
    from harness import cellspec
    from harness.context import Context

    spec = cellspec.load_cell(args.workload)
    if args.rehearse:
        spec = cellspec.shrink_for_rehearsal(spec)
    else:
        # Fail at once where there is no chip to hold: a replica that cannot
        # come up would keep serve.run waiting for its whole start-up budget.
        # Counting the kernel's device files claims nothing (the process that
        # holds the chip checks again, through JAX, what it really sees).
        from ray_tpu.accel.tpu import chip_device_files

        if len(chip_device_files()) < spec["chips"]:
            raise SystemExit(f"benchmark: the cell needs {spec['chips']} TPU chip(s); this host "
                             f"exposes {len(chip_device_files())}. No result.")
    for item in args.set:
        key, value = item.split("=", 1)
        spec["traffic"][key] = json.loads(value)
        say(f"traffic override (a sweep, not the cell as committed): {key} = {value}")
    workdir = os.path.join(BENCH_DIR, ".work", _clean_name(args.workload))
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    more = {}
    if spec["traffic"]["kind"] == "serve":
        from harness import serve_cell as cell

        # A serve cell's trace is written by a thread of the replica while the
        # driver waits; how long it may wait follows from when this run ends.
        more["deadline"] = args.t_start + DEADLINE_S
    elif spec["traffic"]["kind"] == "train":
        from harness import train_cell as cell
    else:
        raise SystemExit(f"benchmark: unknown traffic kind {spec['traffic']['kind']!r}")
    result = cell.run(spec, args.seed, args.seconds, bool(args.trace), args.rehearse,
                      args.t_start, workdir, say, **more)
    ctx = Context(result, spec["chips"])

    if result["kind"] == "serve":
        dev = result["device"]
        attempted = len(ctx.measured)
        failed = attempted - len(ctx.finished)
        done = ctx.finished
        say("done: " + json.dumps({
            "requests": len(done), "prompt_tokens": sum(x["prompt_len"] for x in done),
            "output_tokens": sum(x["n_out"] for x in done),
            "decode_steps": result["window"]["decode_steps"],
            "prefills": result["window"]["prefill_calls"],
            "prefill_requests": result["window"]["prefill_requests"]}))
        if failed:
            why = {}
            for x in ctx.measured:
                if not ctx.ok(x):
                    key = f"status {x.get('status')}, {x.get('error')}, {x['n_out']}/{x['out_len']} tokens"
                    why[key] = why.get(key, 0) + 1
            say(f"failed requests: {json.dumps(why)}")
        compiles = result["window"]["compiles"]
        check = result["check"]
        problems = [] if check["ok"] else [f"reference check failed: {check}"]
        compared = serve_compared(check)
        if result["client"].get("stream_exhausted"):
            problems.append("the closed loop ran out of requests")
        device = {"platform": dev["platform"], "kind": dev["kind"], "count": dev["count"],
                  "memory_peak_bytes": dev["memory_peak_bytes"]}
    else:
        w = result["worker"]
        attempted, failed = w["steps"], 0
        compiles = w["window_compiles"]
        losses = w["losses"]
        problems = []
        if not all(x == x and abs(x) != float("inf") for x in losses):
            problems.append(f"loss not finite: {losses[:5]}")
        elif not sum(losses[-5:]) / 5 < sum(losses[:5]) / 5:
            problems.append(f"loss did not fall over the window: {losses[:5]} -> {losses[-5:]}")
        from harness.train_cell import LOSS_TOL

        if not abs(w["loss_program"] - w["loss_reference"]) <= LOSS_TOL:
            problems.append(f"loss {w['loss_program']} vs reference {w['loss_reference']} "
                            f"(tolerance {LOSS_TOL}, {w['reference_tokens']} tokens)")
        compared = train_compared(w, LOSS_TOL)
        device = {"platform": w["platform"], "kind": w["device_kind"], "count": w["device_count"],
                  "memory_peak_bytes": w["memory_peak_bytes"]}
    compared["window_compiles"] = [compiles, 0]
    if compiles:
        problems.append(f"{compiles} compilation(s) inside the window")
    if result["driver_touched_jax"]:
        problems.append("the benchmark's driver initialised a JAX backend")
    for p in problems:
        say(f"NOT CORRECT: {p}")

    if args.rehearse:
        say(f"rehearsal complete on {device['platform']!r}: control flow only, "
            f"attempted {attempted}, failed {failed}, problems {len(problems)}; no result")
        if problems or failed:
            raise SystemExit(1)
        return
    if device["platform"] != "tpu" or device["count"] != spec["chips"]:
        raise SystemExit(f"benchmark: the cell needs {spec['chips']} TPU chip(s); the process that "
                         f"holds the device reports {device}")
    metrics = {}
    for m in spec["per_layer"] if args.trace else spec["end_to_end"]:
        value = cellspec.load_metric(m["name"])(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": not problems, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}
    if args.trace:
        if not ctx.traced:
            raise SystemExit(f"benchmark: the traced run gave no trace: "
                             f"{result.get('traced') or result['worker'].get('traced')}")
        device["busy_s"], device["window_s"] = ctx.traced["busy_s"], ctx.traced["window_s"]
        line["breakdown"] = {
            k: [[_clean_name(n), s] for n, s in ctx.traced[k]] for k in ("device_ops", "idle_gaps")}
        say("traced: " + json.dumps({k: ctx.traced[k] for k in
                                     ("module_s", "module_runs", "kernel", "kernels", "line_names",
                                      "collective_exposed_s", "xplane_bytes", "stop_trace_s", "traced_part_s",
                                      "ended_by", "trace_units", "trace_wait_s", "reduce_s", "op_samples")
                                     if k in ctx.traced}))
    # last in the line: each number `correct` was decided by, beside its limit
    line["compared"] = {name: {"value": value if value == value and abs(value) != float("inf") else None,
                               "limit": limit} for name, (value, limit) in compared.items()}
    say(RESULT_TAG + json.dumps(line))


def serve_compared(check: dict) -> dict:
    """harness/refcheck.py `judge`'s three comparisons as name -> [number, its
    limit]: each has to be at most its limit (the fourth test is that the
    reference's logits are finite)."""
    return {"worst_trail": [check["worst_trail"], 2 * check["bf16_logit_error"]],
            "quietest_share_of_coarse": [check["quietest_share_of_coarse"], check["quietest_limit"]],
            "noise_share_of_coarse": [check["noise_share_of_coarse"], check["position_limit"]]}


def train_compared(worker: dict, loss_tol: float) -> dict:
    """The train cells' comparisons: the program's loss against the plain
    reference's on one sequence, at most the tolerance apart; the mean of the
    window's last five losses less that of its first five, below 0."""
    losses = worker["losses"]
    return {"loss_gap_to_reference": [abs(worker["loss_program"] - worker["loss_reference"]), loss_tol],
            "loss_change_over_window": [sum(losses[-5:]) / 5 - sum(losses[:5]) / 5, 0.0]}


def compared_lines(result_line: str) -> list:
    """What the supervisor prints as the last lines of standard error: the
    result line's `compared`, a number and its limit a line."""
    return [f"compared: {name} {c['value']} (limit {c['limit']})"
            for name, c in json.loads(result_line).get("compared", {}).items()]


# ---------------------------------------------------------------------------
# The supervisor
# ---------------------------------------------------------------------------

SWEEP_S = 120  # how long the sweep waits for a killed session to be gone
END_SIGNALS = (signal.SIGTERM, signal.SIGINT, signal.SIGHUP, signal.SIGALRM)


class _ToldToEnd(Exception):
    pass


def _session_pids(sid: int) -> list[int]:
    """Every process of a session with a thread still alive (a killed
    process's leader turns zombie while its other threads are still closing
    the files they share, the chip's among them). A thread that ends between
    the listing and the look is passed over alone: the process's other
    threads are still looked at."""
    out = []
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            if os.getsid(int(name)) != sid:
                continue
            tids = os.listdir(f"/proc/{name}/task")
        except OSError:
            continue
        for tid in tids:
            try:
                with open(f"/proc/{name}/task/{tid}/stat") as f:
                    state = f.read().rsplit(")", 1)[1].split()[0]
            except (OSError, IndexError):
                continue
            if state not in "ZX":
                out.append(int(name))
                break
    return sorted(out)


def _kill_session(sid: int, but: int = 0) -> None:
    for pid in _session_pids(sid):
        if pid != but:
            try:
                os.kill(pid, signal.SIGKILL)
            except ProcessLookupError:
                pass


def _lifeline(fd: int) -> None:
    """In the child: the read end of a pipe whose write end only the
    supervisor holds. It closes when the supervisor is gone, however it went;
    then this session, which nobody is left to sweep, ends itself."""
    def watch():
        try:
            while os.read(fd, 1):
                pass
        except OSError:
            pass
        _kill_session(os.getsid(0), but=os.getpid())
        os.kill(os.getpid(), signal.SIGKILL)

    threading.Thread(target=watch, name="lifeline", daemon=True).start()


def _adopt_orphans() -> bool:
    """Make this process the subreaper of its descendants (Linux's
    PR_SET_CHILD_SUBREAPER): a worker whose parent has died becomes this
    process's child, so that the sweep can reap it, which the kernel allows
    only when its last thread has gone, and leaves no zombie behind."""
    try:
        return ctypes.CDLL(None, use_errno=True).prctl(36, 1, 0, 0, 0) == 0
    except (OSError, AttributeError):
        return False


def _reap_all(deadline: float) -> bool:
    """Reap every child this process has, adopted ones too, until none is
    left (True) or the deadline has passed."""
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return True
        if pid == 0:
            if time.monotonic() > deadline:
                return False
            time.sleep(0.05)


def run_swept(cmd: list, env: dict, deadline_s: float, on_line) -> dict:
    """Run `cmd` as the leader of a session of its own, hand its output to
    `on_line` line by line, and leave nothing of that session behind, on
    every way out: the command's own end, its deadline, or a signal that
    tells this process to end (`signal` in the result, and no line is handed
    on after it)."""
    adopted = _adopt_orphans()
    life_r, life_w = os.pipe()
    proc = subprocess.Popen(cmd + ["--lifeline", str(life_r)], cwd=ROOT, env=env, stdout=subprocess.PIPE,
                            start_new_session=True, pass_fds=(life_r,))
    os.close(life_r)
    told, old, rc, sweeping = [], {}, None, False

    def end(signum, _frame):
        told.append(signum)
        if len(told) == 1 and not sweeping:  # once, and never into the sweep
            raise _ToldToEnd()

    try:
        for s in END_SIGNALS:
            old[s] = signal.signal(s, end)
        signal.alarm(int(deadline_s))
        fd, buf = proc.stdout.fileno(), b""
        while True:
            if select.select([fd], [], [], 0.5)[0]:
                chunk = os.read(fd, 1 << 16)
                if not chunk:
                    break
                *lines, buf = (buf + chunk).split(b"\n")
                for line in lines:
                    on_line(line.decode(errors="replace") + "\n")
            elif proc.poll() is not None:
                break  # the leader has ended; whoever still holds its pipe is for the sweep
        if buf:
            on_line(buf.decode(errors="replace") + "\n")
        rc = proc.wait()
    except _ToldToEnd:
        pass
    finally:
        sweeping = True
        signal.alarm(0)
        t0 = time.monotonic()
        stragglers = _session_pids(proc.pid)
        _kill_session(proc.pid)
        if rc is None:
            rc = proc.wait()
        reaped = _reap_all(t0 + SWEEP_S) if adopted else False
        while _session_pids(proc.pid) and time.monotonic() - t0 < SWEEP_S:
            time.sleep(0.1)
        left = _session_pids(proc.pid)
        waited = time.monotonic() - t0
        os.close(life_w)
        for s, h in old.items():
            signal.signal(s, h)
    return {"rc": rc, "signal": told[0] if told and rc != 0 else None, "stragglers": stragglers, "left": left,
            "waited_s": waited, "reaped_all": reaped}


def supervise(args, argv: list) -> int:
    if not os.path.isdir(os.path.join(ROOT, "ray_tpu")) or not os.path.exists(
            os.path.join(ROOT, "BENCHMARK.json")):
        say("benchmark: this checkout holds no system to measure (no ray_tpu/ beside benchmarks/)")
        return 2
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(p for p in (BENCH_DIR, ROOT, env.get("PYTHONPATH")) if p)
    env["PYTHONUNBUFFERED"] = "1"
    if args.rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    cmd = [sys.executable, os.path.abspath(__file__), "--child", "--t-start", repr(T_START)] + argv
    result = []

    def on_line(line: str) -> None:
        if line.startswith(RESULT_TAG):
            result.append(line[len(RESULT_TAG):].strip())
        else:
            sys.stdout.write(line)
            sys.stdout.flush()

    end = run_swept(cmd, env, DEADLINE_S, on_line)
    rc, left = end["rc"], end["left"]
    say(f"processes the run left for the sweep: {len(end['stragglers'])}; after it: {len(left)} "
        f"(waited {end['waited_s']:.1f} s; every one reaped: {end['reaped_all']}); "
        f"the run took {time.time() - T_START:.1f} s")
    if end["signal"] is not None:
        say(f"benchmark: no result (ended by signal {end['signal']}, processes left {left})")
        return 128 + end["signal"]
    if rc != 0 or left or (not result and not args.rehearse):
        say(f"benchmark: no result (exit code {rc}, processes left {left})")
        return rc or 1
    if result:
        # the numbers compared, as the last lines of standard error too
        print("\n".join(compared_lines(result[-1])), file=sys.stderr, flush=True)
        say(result[-1])
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--selftest", action="store_true")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=VALUE",
                    help="override a key of the traffic file for this run (a sweep, not a measurement)")
    ap.add_argument("--child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--t-start", type=float, default=T_START, help=argparse.SUPPRESS)
    ap.add_argument("--lifeline", type=int, default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.selftest:
        sys.path.insert(0, BENCH_DIR)
        from harness import selftest

        return selftest.main()
    if not args.workload:
        ap.error("--workload is required")
    if args.seconds is None:
        if not args.rehearse:
            ap.error("--seconds is required")
        args.seconds = 4.0
    if args.child:
        if args.lifeline is not None:
            _lifeline(args.lifeline)
        child(args)
        return 0
    argv = sys.argv[1:]
    if "--seconds" not in argv:
        argv += ["--seconds", str(args.seconds)]
    return supervise(args, argv)


if __name__ == "__main__":
    sys.exit(main())
