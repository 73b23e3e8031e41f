"""The readers the train cell of a model with held experts and window layers
brought (train_mfu.ep-train, expert_train_roofline), each on a hand-written
run record with the answer worked out by hand and silent where there is
nothing to read; the accepted data_wait_share in the new cell; the
architecture file's counts against hand counts of its configuration
(mellum2-12b-a2.5b-ep4-l4) and against the program's tree; the manifest's new
entries, last in their lists."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))  # transformer_kwargs asks the program what it can hold

from harness import cellspec  # noqa: E402
from harness.context import Context  # noqa: E402

CELL = "mellum2-12b-ep4.pretrain-8k"
CONFIG = "mellum2-12b-a2.5b-ep4-l4"
# a layer: attention 2 x 2304 x 32 x 128 + 2 x 2304 x 4 x 128, router 2304 x 64, one expert 3 x 2304 x 896
ATTN, ROUTER, EXPERT = 21_233_664, 147_456, 6_193_152
A_TOKEN = ATTN + ROUTER + 8 * 16 * EXPERT // 64  # what a token multiplies in a layer: 2 of its 8 experts expected here
MATMUL = 4 * A_TOKEN + 2304 * 24_576


def _config():
    with open(os.path.join(BENCH_DIR, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def _record():
    """1,620,000 trained tokens in 54 s, 0.27 s of them waiting for data. Rows 0 and 1 hold documents of 3000 +
    1000 and of 2000 tokens; the two traced steps held row 0 and row 1. The grouped matmul's kernels took 0.05 +
    0.02 + 0.03 s, the flash kernels 0.04 s, of 0.5 busy seconds; two more instructions carry a kernel's name
    and are no kernel's."""
    kernels = {"expert_gmm.11": {"seconds": 0.05, "calls": 48}, "expert_gmm_dx.12": {"seconds": 0.02, "calls": 24},
               "expert_tgmm.13": {"seconds": 0.03, "calls": 24}, "flash_attn_fwd_win.3": {"seconds": 0.01, "calls": 12},
               "flash_attn_dkv_win.4": {"seconds": 0.02, "calls": 6}, "flash_attn_fwd.5": {"seconds": 0.01, "calls": 4}}
    traced = {"window_s": 0.6, "busy_s": 0.5, "devices": 1, "rows": [[0], [1]],
              "module_s": {"jit_train_step": 0.5}, "module_runs": {"jit_train_step": 2},
              "kernel": {"jit_train_step": {"seconds": 0.14, "calls": 118}}, "kernels": {"jit_train_step": kernels}}
    worker = {"tokens": 1_620_000, "window_s": 54.0, "spans": {"data_wait": 0.27}, "device_kind": "TPU v5 lite",
              "traced": traced}
    return {"kind": "train", "seconds": 54.0, "config": _config(), "traffic": {}, "worker": worker,
            "doc_lens": [[3000, 1000], [2000]], "setup_s": 80.0}


def _pairs(l, window=0):
    """Causal pairs of a document of l tokens; inside a window of 1024 a band."""
    return l * (l + 1) // 2 if not window or l <= window else window * (window + 1) // 2 + (l - window) * window


# attention over the record's three documents: a full layer's causal pairs, a sliding layer's band (x 3 layers)
FULL_PAIRS = _pairs(3000) + _pairs(1000) + _pairs(2000)
BAND_PAIRS = _pairs(3000, 1024) + _pairs(1000, 1024) + _pairs(2000, 1024)
TRAIN_FLOPS = 6.0 * MATMUL * 6000 + 12.0 * 32 * 128 * (FULL_PAIRS + 3 * BAND_PAIRS)
# the traced steps' 6000 document tokens x 8 x 16 / 64 = 12,000 pairs expected on the held experts, a layer:
# nine products of 2 x 2304 x 896 operations a pair; the bytes (16 experts' three matrices three times, a pair's
# 3 x 2304 + 3 x 896 values three times, bf16) come to 0.003 s of the chip's 819 GB/s against 0.0068 s of compute
EXPERT_FLOPS = 4 * 9 * 2.0 * 2304 * 896 * 12_000
EXPERT_BYTES = 4 * 3 * (16 * EXPERT + 12_000 * (3 * 2304 + 3 * 896)) * 2
KNOWN = {
    "train_mfu.ep-train": 100.0 * (TRAIN_FLOPS / 5997) * (1_620_000 / 54.0) / 197e12,
    "expert_train_roofline": 100.0 * (EXPERT_FLOPS / 197e12) / 0.10,
    "data_wait_share": 0.5,
    "train_tokens_per_s": 30_000.0,
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_reader_gives_the_hand_count(name):
    assert EXPERT_FLOPS / 197e12 > EXPERT_BYTES / 819e9  # compute binds
    assert cellspec.load_metric(name)(Context(_record(), 1)) == pytest.approx(KNOWN[name], rel=1e-9)


def test_the_trace_reader_is_silent_without_a_trace_or_without_the_kernels():
    record = _record()
    record["worker"]["traced"]["kernels"]["jit_train_step"] = {"flash_attn_fwd.5": {"seconds": 0.01, "calls": 4}}
    assert cellspec.load_metric("expert_train_roofline")(Context(record, 1)) is None
    record["worker"]["traced"] = None
    assert cellspec.load_metric("expert_train_roofline")(Context(record, 1)) is None


def test_the_new_readers_are_silent_on_an_architecture_without_the_counts():
    record = _record()
    with open(os.path.join(BENCH_DIR, "configs", "mistral-7b-v0.3-l2.json")) as f:
        record["config"] = json.load(f)
    assert cellspec.load_metric("train_mfu.ep-train")(Context(record, 1)) is None
    assert cellspec.load_metric("expert_train_roofline")(Context(record, 1)) is None


def test_the_architectures_counts_are_the_hand_counts_and_the_programs_tree():
    import jax

    from ray_tpu.models.transformer import TransformerConfig, init_params

    config = _config()
    arch = cellspec.architecture(config)
    counts = arch.param_counts(config)
    layer = ATTN + ROUTER + 16 * EXPERT + 2 * 2304
    assert layer == 120_476_160
    assert counts["total"] == 4 * layer + 2 * 2304 * 24_576 + 2304 == 595_153_152
    assert counts["matmul"] == MATMUL == 191_692_800 and counts["per_layer_matmul"] == A_TOKEN
    assert counts["resident_matmul"] == 4 * (ATTN + ROUTER + 16 * EXPERT) + 2304 * 24_576
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), TransformerConfig(**arch.transformer_kwargs(config))))
    assert sum(a.size for a in jax.tree.leaves(shapes)) == 595_153_152
    assert arch.attention_dims(config) == (4, 32, 4, 128) and arch.routing(config) == 4
    docs = [3000, 1000, 2000]
    assert arch.live_pairs(docs) == FULL_PAIRS and arch.live_pairs(docs, 1024) == BAND_PAIRS
    assert arch.train_needs(config, docs)["flops"] == pytest.approx(TRAIN_FLOPS, rel=1e-12)
    sliding = arch.flash_train_needs(config, docs, "sliding_attention")
    full = arch.flash_train_needs(config, docs, "full_attention")
    assert sliding["flops"] == 12.0 * 32 * 128 * BAND_PAIRS and full["flops"] == 12.0 * 32 * 128 * FULL_PAIRS
    assert sliding["bytes"] == full["bytes"] == 6 * 6000 * 32 * 128 * 2 + 6 * 6000 * 4 * 128 * 2
    needs = arch.expert_train_needs(config, 6000)
    assert needs["flops"] * 4 == pytest.approx(EXPERT_FLOPS) and needs["bytes"] * 4 == pytest.approx(EXPERT_BYTES)


def test_the_configuration_states_its_source_its_cut_and_its_deployment():
    config = _config()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Mellum2-12B-A2.5B-Instruct")
    assert config["source"] == row["source_url"]
    differs = sorted(k for k, v in row["config"].items() if config.get(k) != v)
    assert differs == sorted(config["reduced"]) == sorted(["layer_types", "mlp_layer_types", "num_experts",
                                                           "num_hidden_layers", "vocab_size"])
    assert config["layer_types"] == row["config"]["layer_types"][:4] and config["router_experts"] == 64
    assert config["published"]["num_experts"] == 64 and "4 that share each layer" in config["deployment"]
    assert config["train"]["batch_rows"] == 2


def test_the_manifest_lists_the_new_entries_last():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert manifest["configs"][-1]["name"] == CONFIG and manifest["configs"][-1]["file"].endswith(CONFIG + ".json")
    cell = manifest["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (CELL, CONFIG, "pretrain-packed-8k", 1)
    assert [m["name"] for m in manifest["per_layer"][-2:]] == ["train_mfu.ep-train", "expert_train_roofline"]
    assert all(m["workloads"] == [CELL] and m["moves"] == "train_tokens_per_s" for m in manifest["per_layer"][-2:])
    by_name = {m["name"]: m for m in manifest["end_to_end"] + manifest["per_layer"]}
    assert by_name["train_tokens_per_s"]["workloads"][-1] == CELL and by_name["data_wait_share"]["workloads"][-1] == CELL
    assert len(manifest["per_layer"]) <= 128  # the contract's room: six more readers the issue named wait for it
    spec = cellspec.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"train_tokens_per_s", "setup_s"}
    assert {m["name"] for m in spec["per_layer"]} == {"data_wait_share", "train_mfu.ep-train", "expert_train_roofline"}


def test_the_cell_is_the_issues_traffic_and_the_sources_hyperparameters():
    """ISSUE 59's parameters, letter for letter: 1024 documents of median 2048 in rows of 8192; no rate in the
    configuration (make_train_step's own optimizer) and the Qwen-MoE family's balance coefficient."""
    spec = cellspec.load_cell(CELL)
    t = spec["traffic"]
    assert (t["seq_len"], t["packing"], t["documents"], t["warm_steps"]) == (8192, "greedy", 1024, 2)
    assert t["doc_len"] == {"dist": "lognormal", "median": 2048, "sigma": 1.0, "min": 64, "max": 8192}
    assert spec["config"]["router_aux_loss_coef"] == 0.001 and "learning_rate" not in spec["config"]["train"]


def test_the_whole_row_comparison_runs_at_toy_widths():
    """benchmarks/checks/whole_row_mellum2.py, the comparison the cell's check cannot make, stays runnable: here it
    judges nothing (toy widths on the CPU), on the chip it holds loss and gradients by leaf to its LIMITS."""
    import subprocess

    script = os.path.join(BENCH_DIR, "checks", "whole_row_mellum2.py")
    done = subprocess.run([sys.executable, script, "--toy", "--seeds", "3", "--controls", "pairs"],
                          capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    assert lines[-1]["judged"] is False and lines[-1]["controls_refused"] == {"pairs": True}
    sound = next(line for line in lines if line.get("control") == "none")
    assert set(sound["read"]) == {"loss_gap", "sliding.wq", "sliding.wo", "full.wq", "full.wo", "sliding.router",
                                  "full.router", "expert.w_gate", "expert.w_up", "expert.w_down", "lm_head", "embed"}


def test_the_held_load_by_step_runs_at_toy_widths():
    """benchmarks/checks/held_load_mellum2.py, the cell's step under make_train_step's own rate and under a warm-up
    to it, stays runnable: one line a seed and schedule, the pairs on the held experts beside the expected count."""
    import subprocess

    script = os.path.join(BENCH_DIR, "checks", "held_load_mellum2.py")
    done = subprocess.run([sys.executable, script, "--toy", "--seeds", "3", "--steps", "2", "--every", "1"],
                          capture_output=True, text=True, timeout=600, env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert done.returncode == 0, done.stderr[-2000:]
    lines = [json.loads(line) for line in done.stdout.splitlines() if line.startswith("{")]
    assert [line["warmup_steps"] for line in lines] == [0, 2000]
    for line in lines:
        held = line["held_pairs"]
        assert 0.5 * held["expected"] < held["least"] <= held["most"] < 1.5 * held["expected"]
        assert [r[0] for r in line["step_pairs_tiles_balance"]] == [1, 2]
