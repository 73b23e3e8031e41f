"""The readers this architecture brought (linear_attn_step_time_share,
linear_attn_chunk_time_share, linear_attn_step_roofline,
linear_attn_chunk_roofline, state_rows_per_step, and the three expert readers
under this cell's names: expert_gmm_time_share.hybrid,
expert_gmm_roofline.hybrid, expert_pairs_per_held_expert.hybrid), each on a
hand-written run record with the answer worked out by hand, and the
architecture file's counts against the hand counts of its configuration
(solar-open2-250b-ep8)."""
import json
import os
import sys

import pytest

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, os.path.dirname(BENCH_DIR))  # transformer_kwargs asks the program what it can hold

from harness import cellspec  # noqa: E402
from harness.context import Context  # noqa: E402

W0, W1 = 1000.0, 1051.0
CELL = "solar-open2-250b-ep8.backlog-long-ctx"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "solar-open2-250b-ep8.json")) as f:
        return json.load(f)


def _step(t, block, rows=None, experts=(0, 0)):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19}, "block": block, "live_pages": 0,
           "expert_pairs": experts[0], "expert_tiles": experts[1]}
    if rows is not None:
        rec["state_rows"] = rows
    return rec


def _record(with_counter=True):
    """100 traced decode steps (the paged kernel's 100 calls at 1 a step):
    0.6 s in the delta layers' 300 one-token calls, 0.2 s in the paged
    kernel's, 1.2 s in the grouped matmul's 1,200 of 6.0 s busy; 30 prefilled
    requests inside the trace, 90 chunked calls of 1.5 s in all; around the
    trace the replica dispatched 40 requests of 163,840 padded tokens. In the
    window two decode blocks of 8 steps: 120 and 124 states rewritten a step;
    4,096 and 3,712 pairs on held experts in 1,200 and 1,168 live tiles; one
    step without a block, one block before the window."""
    w = (lambda n: n) if with_counter else (lambda n: None)
    steps = [_step(W0 - 4, 8, w(999)),
             _step(W0 + 1, 8, w(8 * 120), (4096, 1200)),
             _step(W0 + 2, 0, w(0)),
             _step(W0 + 3, 8, w(8 * 124), (3712, 1168))]
    traced = {
        "window_s": 6.5, "busy_s": 6.0, "devices": 1,
        "module_s": {"jit__decode_impl": 3.0, "jit__prefill_batch_impl": 3.0},
        "module_runs": {"jit__decode_impl": 13, "jit__prefill_batch_impl": 30},
        "kernel": {"jit__decode_impl": {"seconds": 2.0, "calls": 1600}},
        "kernels": {
            "jit__decode_impl": {
                "paged_attn.5": {"seconds": 0.2, "calls": 100},
                "kda_step.6": {"seconds": 0.2, "calls": 100}, "kda_step.7": {"seconds": 0.2, "calls": 100},
                "kda_step.8": {"seconds": 0.2, "calls": 100},
                "expert_gmm.11": {"seconds": 0.4, "calls": 400}, "expert_gmm.12": {"seconds": 0.4, "calls": 400},
                "expert_gmm.13": {"seconds": 0.4, "calls": 400}},
            "jit__prefill_batch_impl": {
                "kda_chunk.3": {"seconds": 0.5, "calls": 30}, "kda_chunk.4": {"seconds": 0.5, "calls": 30},
                "kda_chunk.5": {"seconds": 0.5, "calls": 30}, "expert_gmm.4": {"seconds": 0.2, "calls": 96}}},
        "counters_before": {"decode_steps": 1000, "prefill_requests": 100, "prefill_padded_tokens": 400_000},
        "counters_after": {"decode_steps": 1200, "prefill_requests": 140, "prefill_padded_tokens": 563_840},
    }
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": [], "requests_total": 0, "steps": steps,
             "steps_total": 4, "phase_s": {}, "phase_n": {}, "dropped": {"requests": 0, "steps": 0}}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config(), "traffic": {}, "plan": {"loop": "closed"},
            "client": {"w0": W0, "w1": W1, "records": []}, "stats": {"trace": trace},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


# Worked out by hand. One-token rule: (960 + 992) / 16 = 122 rows a step; 100 steps x 3
# layers = 36,600 rows; a row reads and writes 64 x 128 x 128 float32 (8,388,608 bytes) and
# its q, k, v, g (4 x 64 x 128 x 4), beta (64 x 4) and o (64 x 128 x 4): 8,552,704 bytes.
STEP_BYTES = 36_600 * (2 * 64 * 128 * 128 * 4 + 4 * 64 * 128 * 4 + 64 * 4 + 64 * 128 * 4)
STEP_FLOPS = 36_600 * 64 * 7 * 128 * 128
# Chunked rule: 163,840 / 40 = 4,096 padded tokens a request, 90 calls: 368,640 token-layers;
# a token and head 4 x 64 x 128 + 3 x 128 x 128 = 81,920 multiply-adds and 4 x 128 x 2 + 128 x 4
# + 4 = 1,540 bytes; 64 heads.
CHUNK_FLOPS = 2 * 81_920 * 64 * 368_640
CHUNK_BYTES = 368_640 * 64 * 1_540
assert STEP_BYTES / 819e9 > STEP_FLOPS / 197e12 and CHUNK_BYTES / 819e9 > CHUNK_FLOPS / 197e12  # both by bandwidth
# Grouped matmul: (4,096 + 3,712) / (16 steps x 4 layers) = 122 pairs a layer of a step in
# (1,200 + 1,168) / 64 = 37 live tiles, 400 layers of steps traced; a tile reads its expert,
# 3 x 4,096 x 1,280 = 15,728,640 parameters.
GMM_BYTES = 15_728_640 * 2 * 37 * 400 + 122 * 400 * (3 * 4096 + 3 * 1280) * 2
KNOWN = {
    "linear_attn_step_time_share": 100 * 0.6 / 6.0,
    "linear_attn_chunk_time_share": 100 * 1.5 / 6.0,
    "linear_attn_step_roofline": 100 * (STEP_BYTES / 819e9) / 0.6,  # 63.7
    "linear_attn_chunk_roofline": 100 * (CHUNK_BYTES / 819e9) / 1.5,  # 2.96
    "state_rows_per_step": 122.0,
    "expert_gmm_time_share.hybrid": 100 * 1.2 / 6.0,
    "expert_gmm_roofline.hybrid": 100 * (GMM_BYTES / 819e9) / 1.2,  # 47.4
    "expert_pairs_per_held_expert.hybrid": 122 / 40,
}
NEW = tuple(KNOWN)
FROM_COUNTERS = ("state_rows_per_step", "expert_pairs_per_held_expert.hybrid")


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < (100 if name != "state_rows_per_step" else 128)


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the state counter (the parent, or a model without delta layers) blanks the
    two that read it, and raises nothing; a trace without the kernels' names,
    or without the two kernels, blanks the traced ones."""
    read = cellspec.load_metric(name)
    untraced = read(Context(dict(_record(), traced=None), 1))
    assert untraced == (pytest.approx(KNOWN[name]) if name in FROM_COUNTERS else None)
    if name in ("state_rows_per_step", "linear_attn_step_roofline"):
        assert read(Context(_record(with_counter=False), 1)) is None
    no_kernel = _record()
    del no_kernel["traced"]["kernels"]  # a trace reduced before kernels were told apart
    if name not in FROM_COUNTERS:
        assert read(Context(no_kernel, 1)) is None
    parent = _record(with_counter=False)  # a program with neither kernel
    for program in parent["traced"]["kernels"].values():
        for kernel in [k for k in program if k.startswith("kda_")]:
            del program[kernel]
    if name.startswith("linear_attn") or name == "state_rows_per_step":
        assert read(Context(parent, 1)) is None
    no_prefill = _record()
    no_prefill["traced"]["counters_after"] = dict(no_prefill["traced"]["counters_before"])
    if name == "linear_attn_chunk_roofline":
        assert read(Context(no_prefill, 1)) is None


def test_every_new_metric_is_in_the_manifest_for_the_new_cell():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
        assert by_name[name]["layer"] == ("scheduler" if name == "state_rows_per_step" else "kernels")
        assert by_name[name]["source"] == ("program_counter" if name in FROM_COUNTERS else "device_trace")
    # the accepted expert metrics keep their one cell each (test_pangu_metrics.py, test_laguna_metrics.py)
    for name in ("expert_gmm_time_share", "expert_gmm_roofline", "expert_pairs_per_held_expert"):
        assert CELL not in by_name[name]["workloads"] and CELL not in by_name[name + ".long-ctx"]["workloads"]
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("solar-open2-250b-ep8", "backlog-long-ctx", 1)
    assert manifest["workloads"][-1] == cell and manifest["configs"][-1]["name"] == "solar-open2-250b-ep8"
    assert next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")["workloads"][-1] == CELL
    spec = cellspec.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_out_tokens_per_s", "setup_s"}
    assert set(NEW) <= {m["name"] for m in spec["per_layer"]}


def test_decode_steps_are_counted_from_the_softmax_layers_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"paged_attn": 1, "kda_step": 3, "expert_gmm": 12}
    assert ctx.traced_decode_steps() == 100
    assert ctx.kernel_of("_decode_impl", "kda_step") == {"seconds": pytest.approx(0.6), "calls": 300}
    assert ctx.kernel_of("_prefill_batch_impl", "kda_chunk") == {"seconds": pytest.approx(1.5), "calls": 90}


def test_the_counts_match_the_hand_counts():
    """The issue's table: a softmax layer's mixer 109,051,904; a delta layer's
    137,625,600 multiplied and 106,688 beside (taps, dt_bias, a_log, the head
    norm); router, shared expert, an expert 1,310,720 / 15,728,640 /
    15,728,640; a layer's router, shared expert and 40 held experts
    646,184,960; embedding + head at 24,576 rows 201,326,592; this chip
    3,308,352,064 parameters, 6,616,704,128 bytes in bfloat16."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    gqa = 2 * 4096 * 64 * 128 + 2 * 4096 * 8 * 128 + 4096 * 64 * 128
    low_rank = 4096 * 128 + 128 * 64 * 128
    kda = 4 * 4096 * 64 * 128 + 2 * low_rank + 4096 * 64
    small = 4 * 3 * 64 * 128 + 64 * 128 + 64 + 128
    assert (gqa, low_rank, kda, small) == (109_051_904, 1_572_864, 137_625_600, 106_688)
    ffn = 4096 * 320 + 3 * 4096 * 1280 + 40 * 3 * 4096 * 1280
    assert ffn == 646_184_960
    assert counts["embedding"] + counts["lm_head"] == 201_326_592
    everything = gqa + 3 * kda + 4 * ffn + 201_326_592
    assert counts["resident_matmul"] == everything - 24576 * 4096  # the embedding multiplies nothing
    # a token multiplies 8 x 40 / 320 = 1 expert a layer
    assert counts["matmul"] == counts["resident_matmul"] - 4 * 39 * 15_728_640
    assert counts["total"] == everything + 3 * small + 4 * 2 * 4096 + 4096 == 3_308_352_064
    assert arch.routing(model) == 4 and arch.attention_dims(model) == (4, 64, 8, 128)
    assert arch.kda_step_needs(model, rows=1.0) == {"flops": 64 * 7.0 * 128 * 128, "bytes": 8_552_704.0}
    assert arch.kda_chunk_needs(model, padded_tokens=1.0) == {"flops": 2.0 * 81_920 * 64, "bytes": 64 * 1_540.0}


def test_the_configuration_keeps_the_published_widths():
    model = _config()
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["head_dim"], kw["n_kv_heads"], kw["n_heads"]) == (4096, 128, 8, 64)
    gqa, *kda = kw["layer_pattern"]
    assert len(set(kda)) == 1 and len(kda) == 3
    assert (gqa.name, gqa.n_heads, gqa.window, gqa.rope_share, gqa.mixer) == ("gqa", 64, 0, 0.0, "attention")
    assert (kda[0].name, kda[0].n_heads, kda[0].mixer, kda[0].conv_size, kda[0].low_rank, kda[0].beta_scale) == (
        "kda", 64, "delta", 4, 128, 2.0)
    assert (kw["expert_d_ff"], kw["n_experts"], kw["expert_top_k"], kw["experts_held"]) == (1280, 320, 8, 40)
    assert (kw["n_layers"], kw["vocab_size"], kw["n_shared_experts"]) == (4, 24576, 1)
    assert kw["routed_scaling"] == 1.0 and kw["norm_eps"] == 1e-5 and kw["attn_gate"] == "elementwise"
    assert kw["router_score"] == "sigmoid" and "n_dense_layers" not in kw
    assert set(model["reduced"]) == set(model["published"]) == set(model["cut"])
    eng = model["engine"]
    assert (eng["total_pages"], eng["max_seq"], eng["page_size"], eng["decode_block"]) == (6144, 9344, 128, 8)
    assert eng["max_slots"] in (96, 128) and eng["prefill_buckets"] == [512, 1024, 2048, 4096, 8192]


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The keys of the published config the file was started from: every top
    level number is the published one unless `reduced` names it, and the
    nested group is copied whole."""
    model = _config()
    published = {"partial_rotary_factor": 1, "hidden_size": 4096, "num_hidden_layers": 48, "num_attention_heads": 64,
                 "head_dim": 128, "num_key_value_heads": 8, "vocab_size": 196608, "intermediate_size": 10240,
                 "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05, "rope_theta": 10000,
                 "max_position_embeddings": 1048576, "first_k_dense_replace": 0, "gqa_interval": 3,
                 "n_routed_experts": 320, "n_shared_experts": 1, "routed_scaling_factor": 1, "num_experts_per_tok": 8}
    for key, value in published.items():
        assert (model[key] == value) != (key in model["reduced"]), key
    assert model["published"] == {k: published[k] for k in model["reduced"]}
    assert model["linear_attn_config"] == {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
                                           "num_kv_heads": None}
    assert (model["model_type"], model["use_rope"], model["use_gqa_gate"], model["kda_use_full_proj"],
            model["kda_allow_neg_eigval"], model["norm_topk_prob"], model["tie_word_embeddings"]) == (
        "solar_open2", False, True, False, True, True, False)
    assert model["gqa_layers"] == [0] and model["router_experts"] == published["n_routed_experts"]
