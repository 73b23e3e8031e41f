"""From a profiler trace to numbers: device busy time, idle gaps laid to what
the host was doing, time by operation, by program and by kernel (a program's
Mosaic calls summed, and each under its own name).

The reduction works on a plain form of the trace (`to_plain`), so that it can
be checked on a small recorded one (selftest_data/, `run.py --selftest`):

    {"planes": [{"name": str, "lines": [{"name": str,
                 "events": [[name, start_ns, duration_ns], ...]}]}]}

What it reads of a TPU trace: planes "/device:TPU:<n>" with a line
"XLA Ops" (one event per executed HLO operation) and a line "XLA Modules"
(one event per executed program, named "jit_<fn>(<fingerprint>)"); host
planes whose lines are threads, where jax.profiler.TraceAnnotations appear
under the names they were given: the benchmark's own ("bench.window" brackets
the traced window) and the program's step phases ("llm.step.<phase>",
ray_tpu.util.tracing.PhaseSpans).
"""
from __future__ import annotations

import bisect
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW = "bench.window"
NOTE_FAMILIES = ("bench.", "llm.step.")  # annotations an idle gap can be laid to
OP_NAME = re.compile(r'op_name="([^"]*)"')
COLLECTIVE = re.compile(r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all", re.I)
KERNEL = re.compile(r"mosaic|tpu_custom_call", re.I)


def start(jax, logdir: str) -> None:
    """Start a device trace with the host's Python tracer off (it slows the
    very host loop whose gaps the trace is there to show)."""
    os.makedirs(logdir, exist_ok=True)
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
    except (AttributeError, TypeError):
        jax.profiler.start_trace(logdir)


def to_plain(profile_data) -> dict:
    planes = []
    for plane in profile_data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, float(ev.start_ns), float(ev.duration_ns)] for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def reduce_logdir(logdir: str) -> dict:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return {"error": f"no .xplane.pb under {logdir}"}
    out = reduce(to_plain(ProfileData.from_file(paths[-1])))
    out["xplane_bytes"] = os.path.getsize(paths[-1])
    return out


def _union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _clip(intervals, lo, hi) -> list:
    return [[max(s, lo), min(e, hi)] for s, e in intervals if e > lo and s < hi]


def _subtract(a: list, b: list) -> list:
    """a minus b, both merged and sorted."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append([cur, b[k][0]])
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append([cur, e])
    return out


def _op_name(text: str) -> str:
    """An event of the ops line is named by its HLO instruction's whole text
    ("%fusion.1 = bf16[..] fusion(..), ..."): keep the instruction's name."""
    return text.split(" = ", 1)[0].lstrip("%").strip()[:80]


def _self_times(ops: list) -> list:
    """(name, start, end, self_ns): an operation that contains others (a
    while loop and the operations of its body, which the ops line nests)
    keeps only the time none of its children covers."""
    out, stack = [], []
    for n, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][2] <= s:
            stack.pop()
        rec = [n, s, e, e - s]
        if stack:
            stack[-1][3] -= min(e, stack[-1][2]) - s
        stack.append(rec)
        out.append(rec)
    return out


def _module_name(name: str) -> str:
    return re.sub(r"\(\d+\)$", "", name).strip()


def reduce(trace: dict) -> dict:
    devices = [p for p in trace["planes"] if re.match(r"/device:TPU:\d+$", p["name"])]
    hosts = [p for p in trace["planes"] if p["name"].startswith("/host:")]
    if not devices:
        return {"error": "no /device:TPU:<n> plane in the trace",
                "planes": [p["name"] for p in trace["planes"]]}
    # Host annotations of the benchmark, on the trace's clock.
    notes = sorted(
        (s, s + d, name) for p in hosts for ln in p["lines"] for name, s, d in ln["events"]
        if name.startswith(NOTE_FAMILIES))
    windows = [(s, e) for s, e, name in notes if name == WINDOW]
    all_ops = [(s, s + d) for p in devices for ln in p["lines"] if ln["name"] == OPS_LINE
               for _n, s, d in ln["events"]]
    if not all_ops:
        return {"error": f"no {OPS_LINE!r} events on any device plane",
                "lines": sorted({ln["name"] for p in devices for ln in p["lines"]})}
    lo, hi = windows[0] if windows else (min(s for s, _ in all_ops), max(e for _, e in all_ops))
    window_s = (hi - lo) / 1e9

    busy, exposed, per_op, per_module, module_runs, kernel, kernels, samples = [], [], {}, {}, {}, {}, {}, {}
    first_gaps = None
    kinds: dict[str, tuple] = {}

    def kind(text):
        """(is a collective, is a kernel). Looked up once for each distinct
        instruction text: a window holds a million events of a few hundred."""
        k = kinds.get(text)
        if k is None:
            k = kinds[text] = (bool(COLLECTIVE.search(text)), bool(KERNEL.search(text)))
        return k

    for p in devices:
        lines = {ln["name"]: ln["events"] for ln in p["lines"]}
        ops = [(n, s, s + d) for n, s, d in lines.get(OPS_LINE, []) if s + d > lo and s < hi]
        mods = sorted((s, s + d, _module_name(n)) for n, s, d in lines.get(MODULES_LINE, []))
        starts = [m[0] for m in mods]

        def module_of(t):
            i = bisect.bisect_right(starts, t) - 1
            return mods[i][2] if i >= 0 and t < mods[i][1] else "no_module"

        b = _clip(_union([[s, e] for _n, s, e in ops]), lo, hi)
        busy.append(_length(b) / 1e9)
        coll = _clip(_union([[s, e] for n, s, e in ops if kind(n)[0]]), lo, hi)
        comp = _clip(_union([[s, e] for n, s, e in ops if not kind(n)[0]]), lo, hi)
        exposed.append(_length(_subtract(coll, comp)) / 1e9)
        for n, s, e, self_ns in _self_times(ops):
            m = module_of(s)
            dur = max(0.0, self_ns) / 1e9
            key = f"{m}/{_op_name(n)}"
            per_op[key] = per_op.get(key, 0.0) + dur
            if key not in samples:
                # the text's head, and the named scope from its metadata (which
                # lies past any cut worth printing)
                scope = OP_NAME.search(n)
                samples[key] = (n[:240], scope.group(1) if scope else None)
            if kind(n)[1]:
                # once for the program, once under the call's own name
                for k in (kernel.setdefault(m, {"seconds": 0.0, "calls": 0}),
                          kernels.setdefault(m, {}).setdefault(_op_name(n), {"seconds": 0.0, "calls": 0})):
                    k["seconds"] += dur
                    k["calls"] += 1
        for s, e, m in mods:
            if e > lo and s < hi:
                per_module[m] = per_module.get(m, 0.0) + (min(e, hi) - max(s, lo)) / 1e9
                module_runs[m] = module_runs.get(m, 0) + 1
        if first_gaps is None:
            first_gaps = _subtract([[lo, hi]], b)

    # Idle gaps of the first device, each laid to the innermost annotation of
    # either family that covers its start (a bench.<x> as "inside_<x>", a
    # phase of the program's step under its own name), else to "between_steps".
    spans = [(s, e, n) for s, e, n in notes if n != WINDOW]
    gaps: dict[str, float] = {}
    for s, e in first_gaps:
        inner = [(e2 - s2, n) for s2, e2, n in spans if s2 <= s < e2]
        name = min(inner)[1].replace("bench.", "inside_") if inner else "between_steps"
        gaps[name] = gaps.get(name, 0.0) + (e - s) / 1e9
    n_dev = len(devices)
    top = lambda d: sorted(([k, v / n_dev] for k, v in d.items()), key=lambda kv: -kv[1])  # noqa: E731
    a_device = lambda k: {"seconds": k["seconds"] / n_dev, "calls": k["calls"] / n_dev}  # noqa: E731
    return {
        "window_s": window_s, "busy_s": sum(busy) / n_dev, "devices": n_dev,
        "collective_exposed_s": sum(exposed) / n_dev,
        "device_ops": top(per_op)[:10],
        "idle_gaps": sorted(([k, v] for k, v in gaps.items()), key=lambda kv: -kv[1])[:10],
        "module_s": {k: v / n_dev for k, v in per_module.items()},
        "module_runs": {k: v / n_dev for k, v in module_runs.items()},
        # every Mosaic call of a program summed, and each by the name its
        # instruction carries, which is all of a trace event that names it: a
        # pallas_call's `name=` where it has one, else the scope around the call
        # (`paged_attn.6`, `flash_attn.7`; `shard_map.141` under a mesh)
        "kernel": {m: a_device(k) for m, k in kernel.items()},
        "kernels": {m: {name: a_device(k) for name, k in by_name.items()} for m, by_name in kernels.items()},
        "line_names": sorted({ln["name"] for p in devices for ln in p["lines"]}),
        "op_samples": [[k, v, *samples[k]] for k, v in top(per_op)[:40]],
    }
