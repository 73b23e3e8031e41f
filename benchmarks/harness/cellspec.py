"""Find a cell's files by the names BENCHMARK.json gives, and turn them into
what the program's entry points take. Everything that belongs to one
configuration, traffic mix, cell or metric is a file of its own; this module
is the only place that knows where they are."""
from __future__ import annotations

import copy
import functools
import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    manifest = _load(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next((w for w in manifest["workloads"] if w["name"] == name), None)
    if cell is None:
        raise SystemExit(f"benchmark: no workload {name!r} in BENCHMARK.json; it has "
                         f"{[w['name'] for w in manifest['workloads']]}")
    config = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    spec = {
        "name": name, "chips": int(cell["chips"]), "config_name": cell["config"],
        "traffic_name": cell["traffic"],
        "config": _load(os.path.join(ROOT, config["file"])),
        "traffic": _load(os.path.join(BENCH_DIR, "traffic", cell["traffic"] + ".json")),
    }
    # A cell's own file is optional: overrides of the configuration's engine
    # or train group that belong to this pairing alone.
    own = os.path.join(BENCH_DIR, "workloads", name + ".json")
    if os.path.exists(own):
        for group, over in (_load(own).get("overrides") or {}).items():
            spec["config"].setdefault(group, {}).update(over)

    def metrics(kind):
        return [m for m in manifest[kind] if "workloads" not in m or name in m["workloads"]]

    spec["end_to_end"], spec["per_layer"] = metrics("end_to_end"), metrics("per_layer")
    return spec


def load_metric(name: str):
    """The reader of one metric: benchmarks/metrics/<name>.py, `read(ctx)`."""
    path = os.path.join(BENCH_DIR, "metrics", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: metric {name!r} has no reader at {path}")
    mod_spec = importlib.util.spec_from_file_location("bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod.read


@functools.lru_cache(maxsize=None)
def load_architecture(name: str):
    """An architecture's module: benchmarks/architectures/<name>.py, with
    `logits`, `packed_loss`, `transformer_kwargs`, `shrink`, `attention_dims`,
    `param_counts` and optionally `routing` and `decode_kernels` (README, "An
    architecture"). The self-test's fixture reaches its own directory with a
    relative name."""
    path = os.path.join(BENCH_DIR, "architectures", name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: architecture {name!r} has no file at {path}")
    mod_spec = importlib.util.spec_from_file_location(
        "bench_architecture_" + "".join(c if c.isalnum() else "_" for c in name), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def architecture(config: dict):
    """The module a configuration file names under "architecture"; a file
    without the key describes a dense decoder."""
    return load_architecture(config.get("architecture", "dense"))


def transformer_kwargs(config: dict) -> dict:
    return architecture(config).transformer_kwargs(config)


def routing(config: dict):
    """How many top-k choices among experts a token meets on its way through
    the model, as the architecture's file declares it; None for a file that
    declares no discrete choice (the serve check then holds every position as
    tightly as the quietest one: harness/refcheck.py `judge`)."""
    declared = getattr(architecture(config), "routing", None)
    return declared(config) if declared else None


def decode_kernels(config: dict):
    """The Mosaic calls of one decode step, as the architecture's file
    declares them: {a fragment of the kernel's name in the trace: its calls
    a step}, the first entry the one decode steps are counted from
    (harness/context.py `traced_decode_steps`); None for a file that declares
    none, whose decode program calls one kernel once a layer."""
    declared = getattr(architecture(config), "decode_kernels", None)
    return declared(config) if declared else None


def shrink_for_rehearsal(spec: dict) -> dict:
    """Toy sizes for a CPU run of the same control flow. Its output is
    counts only; nothing it prints is a measurement."""
    spec = copy.deepcopy(spec)
    architecture(spec["config"]).shrink(spec["config"])
    eng = spec["config"].get("engine")
    if eng:
        eng.update(max_slots=4, max_seq=256, page_size=32, total_pages=48,
                   prefill_buckets=[32, 64, 128, 256], tensor_parallel=min(eng.get("tensor_parallel", 1), 2))
    trn = spec["config"].get("train")
    if trn:
        trn.update(batch_rows=2, attention_block_q=0, attention_block_k=0, ce_chunk=0)
    t = spec["traffic"]

    def small(dist, cap):
        dist = dict(dist)
        for k in ("median", "min", "max", "value"):
            if k in dist:
                dist[k] = max(2, min(int(dist[k]) // 8, cap))
        return dist

    if t["kind"] == "serve":
        t["prompt_len"], t["output_len"] = small(t["prompt_len"], 96), small(t["output_len"], 24)
        if t.get("turns"):
            t["turns"]["add_len"] = small(t["turns"]["add_len"], 16)
            t["turns"]["think_s"] = 0.1
        if t.get("prefix", {}).get("shared_len"):
            t["prefix"]["shared_len"] = 32
        t.update(ramp_s=1.0, cooldown_s=0.5)
        if t["loop"] == "closed":
            t.update(multiset=16, cycles=64)
    else:
        t.update(seq_len=128, documents=64, doc_len=small(t["doc_len"], 128), warm_steps=1)
    return spec
