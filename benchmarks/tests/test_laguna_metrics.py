"""The readers this architecture brought (window_attn_time_share,
full_attn_time_share, window_attn_roofline, full_attn_roofline,
window_walk_share, and the three expert readers under this cell's names:
expert_gmm_time_share.long-ctx, expert_gmm_roofline.long-ctx,
expert_pairs_per_held_expert.long-ctx), each on a hand-written run record
with the answer worked out by hand, and the architecture file's counts
against the hand counts of its configuration (laguna-s-2.1-ep8)."""
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
CELL = "laguna-s-2.1-ep8.backlog-long-ctx"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "laguna-s-2.1-ep8.json")) as f:
        return json.load(f)


def _step(t, block, live=0, window=None, experts=(0, 0)):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19}, "block": block, "live_pages": live,
           "expert_pairs": experts[0], "expert_tiles": experts[1]}
    if window is not None:
        rec.update(window_pages=window[0], window_tokens=window[1])
    return rec


def _record(with_counter=True):
    """100 traced decode steps (the paged kernel's 100 + 200 calls over its 3
    a step): 0.9 s in the full layers' calls, 0.6 s in the window layers' 600,
    1.5 s in the grouped matmul's 2,400 of 6.0 s busy; around the trace 200
    steps attended 150,000 positions in 64 active slots each. In the window
    two decode blocks of 8 steps on 64 slots: a full layer walked 30 and 34
    pages a slot, a window layer 5 and 5, attending 500 and 512 positions a
    slot; 1,280 and 1,344 pairs on held experts in 1,000 and 1,040 live tiles;
    one step without a block, one block before the window."""
    w = (lambda *n: n) if with_counter else (lambda *n: None)
    steps = [_step(W0 - 4, 8, 9999, w(999, 99999)),
             _step(W0 + 1, 8, 8 * 64 * 30, w(8 * 64 * 5, 8 * 64 * 500), (1280, 1000)),
             _step(W0 + 2, 0, 0, w(0, 0)),
             _step(W0 + 3, 8, 8 * 64 * 34, w(8 * 64 * 5, 8 * 64 * 512), (1344, 1040))]
    traced = {
        "window_s": 6.5, "busy_s": 6.0, "devices": 1,
        "module_s": {"jit__decode_impl": 4.0, "jit__prefill_batch_impl": 2.0},
        "module_runs": {"jit__decode_impl": 13, "jit__prefill_batch_impl": 9},
        "kernel": {"jit__decode_impl": {"seconds": 3.0, "calls": 3300}},
        "kernels": {"jit__decode_impl": {
            "paged_attn.5": {"seconds": 0.3, "calls": 100}, "paged_attn.9": {"seconds": 0.6, "calls": 200},
            "window_attn.6": {"seconds": 0.2, "calls": 200}, "window_attn.7": {"seconds": 0.2, "calls": 200},
            "window_attn.8": {"seconds": 0.2, "calls": 200},
            "expert_gmm.11": {"seconds": 0.5, "calls": 800}, "expert_gmm.12": {"seconds": 0.5, "calls": 800},
            "expert_gmm.13": {"seconds": 0.5, "calls": 800}},
            "jit__prefill_batch_impl": {"expert_gmm.4": {"seconds": 0.2, "calls": 96}}},
        "counters_before": {"decode_steps": 1000, "decode_context_tokens": 0, "slot_steps_active": 0},
        "counters_after": {"decode_steps": 1200, "decode_context_tokens": 200 * 150_000,
                           "slot_steps_active": 200 * 64},
    }
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": [], "requests_total": 0, "steps": steps,
             "steps_total": 4, "phase_s": {}, "phase_n": {}, "dropped": {"requests": 0, "steps": 0}}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config(), "traffic": {}, "plan": {"loop": "closed"},
            "client": {"w0": W0, "w1": W1, "records": []}, "stats": {"trace": trace},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


# Worked out by hand. Full layers, 100 steps x 3 layers: 15,000,000 positions and
# 6,400 rows a layer; 4,096 bytes a position + 2 x 48 heads x 128 x 2 a row;
# operations 4 x 48 x 128 a position.
FULL_BYTES = 3 * (4096 * 15_000_000 + 6_400 * 2 * 48 * 128 * 2)  # 184,791,859,200
FULL_FLOPS = 3 * 4 * 48 * 128 * 15_000_000
# Window layers: (256,000 + 262,144) / 16 = 32,384 positions a step and layer, 3,238,400
# in 100 steps; 6 layers; a row's 72 queries read and 72 outputs written.
WINDOW_BYTES = 6 * (4096 * 3_238_400 + 6_400 * 2 * 72 * 128 * 2)  # 81,002,496,000
WINDOW_FLOPS = 6 * 4 * 72 * 128 * 3_238_400
assert FULL_BYTES / 819e9 > FULL_FLOPS / 197e12 and WINDOW_BYTES / 819e9 > WINDOW_FLOPS / 197e12
# Grouped matmul: (1,280 + 1,344) / (16 steps x 8 layers) = 20.5 pairs a routed layer of a
# step in (1,000 + 1,040) / 128 = 15.9375 live tiles, 800 routed layers of steps traced; a
# tile reads its expert, 3 x 3,072 x 1,024 = 9,437,184 parameters.
GMM_BYTES = 9_437_184 * 2 * 15.9375 * 800 + 20.5 * 800 * (3 * 3072 + 3 * 1024) * 2
KNOWN = {
    "window_attn_time_share": 100 * 0.6 / 6.0,
    "full_attn_time_share": 100 * 0.9 / 6.0,
    "full_attn_roofline": 100 * (FULL_BYTES / 819e9) / 0.9,  # 25.07
    "window_attn_roofline": 100 * (WINDOW_BYTES / 819e9) / 0.6,  # 16.48
    "window_walk_share": 100 * (2560 + 2560) / (15360 + 17408),  # 15.625
    "expert_gmm_time_share.long-ctx": 100 * 1.5 / 6.0,
    "expert_gmm_roofline.long-ctx": 100 * (GMM_BYTES / 819e9) / 1.5,  # 19.6
    "expert_pairs_per_held_expert.long-ctx": 20.5 / 32,
}
NEW = tuple(KNOWN)
FROM_COUNTERS = ("window_walk_share", "expert_pairs_per_held_expert.long-ctx")


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < 100


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the window counters (the parent, or a model without a window) blanks the
    two that read them, and raises nothing."""
    read = cellspec.load_metric(name)
    untraced = read(Context(dict(_record(), traced=None), 1))
    assert untraced == (pytest.approx(KNOWN[name]) if name in FROM_COUNTERS else None)
    if name in ("window_walk_share", "window_attn_roofline"):
        assert read(Context(_record(with_counter=False), 1)) is None
    if name.startswith("expert_"):  # a program whose step records lack the expert counters
        bare = _record()
        for step in bare["stats"]["trace"]["steps"]:
            del step["expert_pairs"], step["expert_tiles"]
        if "time_share" not in name:
            assert read(Context(bare, 1)) is None
    no_kernel = _record()
    del no_kernel["traced"]["kernels"]  # a trace reduced before kernels were told apart
    if name not in FROM_COUNTERS:
        assert read(Context(no_kernel, 1)) is None


def test_every_new_metric_is_in_the_manifest_for_the_new_cell():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
        assert by_name[name]["layer"] == ("scheduler" if name == "window_walk_share" else "kernels")
    joined = ["ttft_p50_ms.backlog", "tpot_p50_ms.backlog", "slot_occupancy.backlog", "decode_ms_per_step.backlog",
              "prefill_busy_share.backlog", "engine_host_ms_per_step.backlog", "window_compiles.backlog"]
    assert all(by_name[name]["workloads"][-1] == CELL for name in joined)
    # the accepted expert metrics keep their one cell (test_pangu_metrics.py holds them to it)
    assert all(CELL not in by_name[name]["workloads"]
               for name in ("expert_gmm_time_share", "expert_gmm_roofline", "expert_pairs_per_held_expert"))
    assert CELL not in by_name["paged_attn_time_share.backlog"]["workloads"]  # that reader sums the program's kernels
    cell = next(w for w in manifest["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("laguna-s-2.1-ep8", "backlog-long-ctx", 1)
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")["workloads"]


def test_decode_steps_are_counted_from_the_full_layers_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"paged_attn": 3, "window_attn": 6, "expert_gmm": 24}
    assert ctx.traced_decode_steps() == 100
    assert ctx.kernel_of("_decode_impl", "paged_attn") == {"seconds": pytest.approx(0.9), "calls": 300}
    assert cellspec.load_metric("decode_ms_per_step.backlog")(ctx) == pytest.approx(40.0)


def test_the_counts_match_the_hand_counts():
    """The issue's table: attention 44,187,648 (48 heads) and 63,135,744 (72);
    shared expert, router, an expert 9,437,184 / 786,432 / 9,437,184; layer 0
    157,433,856; a routed sliding layer with 32 experts 375,349,248, a full
    one 356,401,152; embedding + head at 12,544 rows 77,070,336; this chip
    3,199,401,984 matmul parameters."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    full = 2 * 3072 * 48 * 128 + 2 * 3072 * 8 * 128 + 3072 * 48
    sliding = 2 * 3072 * 72 * 128 + 2 * 3072 * 8 * 128 + 3072 * 72
    assert (full, sliding) == (44_187_648, 63_135_744)
    layer0 = full + 3 * 3072 * 12288
    routed = 9_437_184 + 786_432 + 32 * 9_437_184
    assert (layer0, sliding + routed, full + routed) == (157_433_856, 375_349_248, 356_401_152)
    assert counts["embedding"] + counts["lm_head"] == 77_070_336
    everything = layer0 + 6 * (sliding + routed) + 2 * (full + routed) + 77_070_336
    assert everything == 3_199_401_984
    assert counts["resident_matmul"] == everything - 12544 * 3072  # the embedding multiplies nothing
    # a token multiplies 10 x 32 / 256 = 1.25 experts a routed layer
    a_token = 10 * 32 * 9_437_184 // 256
    assert counts["matmul"] == counts["resident_matmul"] - 8 * (32 * 9_437_184 - a_token)
    assert counts["total"] == everything + 9 * 2 * 3072 + 3072
    assert arch.routing(model) == 8 and arch.attention_dims(model) == (9, 48, 8, 128)
    assert arch.full_decode_needs(model, context_tokens=1.0, rows=0.0) == {"flops": 4.0 * 48 * 128, "bytes": 4096.0}
    assert arch.window_decode_needs(model, window_tokens=1.0, rows=1.0) == {
        "flops": 4.0 * 72 * 128, "bytes": 4096.0 + 2 * 72 * 128 * 2}


def test_the_configuration_keeps_the_published_widths():
    model = _config()
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["head_dim"], kw["n_kv_heads"], kw["n_heads"]) == (3072, 128, 8, 48)
    full, *sliding = kw["layer_pattern"]
    assert len(set(sliding)) == 1 and len(sliding) == 3
    assert (full.name, full.n_heads, full.window) == ("full_attention", 48, 0)
    assert (sliding[0].name, sliding[0].n_heads, sliding[0].window) == ("sliding_attention", 72, 512)
    assert (full.rope_theta, full.rope_share, full.yarn_factor, full.yarn_original_len) == (5e5, 0.5, 128.0, 8192)
    assert (full.yarn_beta_fast, full.yarn_beta_slow, full.attention_factor) == (32.0, 1.0, 1.4852030263919618)
    assert (sliding[0].rope_theta, sliding[0].rope_share, sliding[0].plain_rope) == (1e4, 1.0, True)
    assert (kw["d_ff"], kw["expert_d_ff"], kw["n_experts"], kw["expert_top_k"]) == (12288, 1024, 256, 10)
    assert (kw["n_layers"], kw["n_dense_layers"], kw["experts_held"], kw["vocab_size"]) == (9, 1, 32, 12544)
    assert kw["routed_scaling"] == 2.5 and kw["norm_eps"] == 1e-6 and kw["attn_gate"] == "per_head"
    assert kw["n_shared_experts"] == 1 and kw["router_score"] == "sigmoid"
    assert set(model["reduced"]) == set(model["published"]) == set(model["cut"])
    assert model["first_k_dense_replace"] == len(model["mlp_only_layers"]) and model["n_routed_experts"] == model["num_experts"]
    eng = model["engine"]
    assert (eng["max_slots"], eng["total_pages"], eng["max_seq"], eng["page_size"]) == (64, 3072, 9344, 128)


def test_every_catalog_number_is_kept_or_listed_as_reduced():
    """The keys of the published config the file was started from: every top
    level number is the published one unless `reduced` names it."""
    model = _config()
    published = {"vocab_size": 100352, "hidden_size": 3072, "intermediate_size": 12288, "num_hidden_layers": 48,
                 "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
                 "max_position_embeddings": 1048576, "rms_norm_eps": 1e-06, "num_experts": 256,
                 "num_experts_per_tok": 10, "moe_intermediate_size": 1024, "shared_expert_intermediate_size": 1024,
                 "decoder_sparse_step": 1, "sliding_window": 512, "moe_routed_scaling_factor": 2.5,
                 "moe_router_logit_softcapping": 0}
    for key, value in published.items():
        assert (model[key] == value) != (key in model["reduced"]), key
    assert model["published"] == {k: published[k] for k in model["reduced"]}
