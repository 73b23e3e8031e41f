"""The readers the cell whose routed layers hold every expert brought
(tail_rows_per_step, and the `.all-experts` twins of the accepted readers whose
lists the cell cannot join), each on a hand-written run record with the answer
worked out by hand, `None` where a counter or a kernel is absent, the
manifest's new entries by membership, and the architecture file's counts
against the hand counts of its configuration (lfm2-24b-a2b-l9)."""
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
CELL = "lfm2-24b-a2b.backlog-long-out"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "lfm2-24b-a2b-l9.json")) as f:
        return json.load(f)


def _step(t, block, counts=None, pages=(0, 0)):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19, "emit": 0.004}, "block": block,
           "live_pages": pages[0], "grid_steps": pages[1]}
    if counts is not None:
        rec.update(zip(("expert_pairs", "expert_tiles", "tail_rows"), counts))
    return rec


def _record(with_counters=True):
    """200 traced decode steps (the paged kernel's 400 calls at 2 a step): 2.88 s
    in the grouped matmul's 4,800 calls (12 instances of the period's body, 400
    calls each: two periods a step) and 0.48 s in the paged kernel's, of a
    decode program of 4.0 s; prefill programs of 0.6 s; 4.8 s busy of a window
    of 5.0 s. In the window two decode blocks of 8 steps: each step routes 128
    rows x 4 choices in each of 8 layers (32,768 pairs a block) over 70 live
    tiles a layer (4,480 a block); 125 and 127 tails rewritten a step; one step
    without a block, one block before the window. The window's two blocks walked
    13,000 and 13,100 pages of the softmax layers in 2,000 and 2,100 grid steps; over the 200
    steps around the trace 25,000 (slot, step) pairs attended 30,000,000
    cached positions (125 rows a step, 1,200 positions a row); 2,000 of the
    window's 2,048 slot steps held a request; three requests' first tokens
    fell inside the window, 100, 300 and 200 ms after they were due, 20, 40
    and 30 ms a token."""
    c = (lambda *n: n) if with_counters else (lambda *n: None)
    steps = [_step(W0 - 4, 8, c(9999, 999, 99), (9999, 9)), _step(W0 + 1, 8, c(32768, 4480, 8 * 125), (13000, 2000)),
             _step(W0 + 2, 0, c(0, 0, 0)), _step(W0 + 3, 8, c(32768, 4480, 8 * 127), (13100, 2100))]
    records = [{"status": 200, "error": None, "done": W0 + 9, "n_out": 11, "out_len": 11, "bad_tokens": 0,
                "due": W0 + 5, "t_first": W0 + 5 + ttft, "t_last": W0 + 5 + ttft + 10 * tpot, "chunks": []}
               for ttft, tpot in ((0.1, 0.02), (0.3, 0.04), (0.2, 0.03))]
    decode = {"paged_attn.7": {"seconds": 0.48, "calls": 400}}
    decode.update({f"expert_gmm.{20 + i}": {"seconds": 0.24, "calls": 400} for i in range(12)})
    traced = {
        "window_s": 5.0, "busy_s": 4.8, "devices": 1,
        "module_s": {"jit__decode_impl": 4.0, "jit__prefill_batch_impl": 0.6},
        "module_runs": {"jit__decode_impl": 13, "jit__prefill_batch_impl": 3},
        "kernel": {"jit__decode_impl": {"seconds": 3.36, "calls": 5200}},
        "kernels": {"jit__decode_impl": decode,
                    "jit__prefill_batch_impl": {f"expert_gmm.{3 + i}": {"seconds": 0.01, "calls": 6} for i in range(12)}},
        "counters_before": {"decode_steps": 1000, "prefill_requests": 100, "prefill_padded_tokens": 400_000,
                            "decode_context_tokens": 10_000_000, "slot_steps_active": 60_000},
        "counters_after": {"decode_steps": 1200, "prefill_requests": 103, "prefill_padded_tokens": 402_048,
                           "decode_context_tokens": 40_000_000, "slot_steps_active": 85_000},
    }
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": [], "requests_total": 0, "steps": steps,
             "steps_total": 4, "phase_s": {}, "phase_n": {}, "dropped": {"requests": 0, "steps": 0},
             "compiles": [[W0 - 30, 9.0]], "compiles_total": 1}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config(), "traffic": {}, "plan": {"loop": "closed"},
            "client": {"w0": W0, "w1": W1, "records": records}, "stats": {"trace": trace},
            "window": {"slot_steps_active": 2000, "slot_steps_total": 2048},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


# Worked out by hand. The grouped matmul: 65,536 pairs over 16 steps x 8 routed layers = 512 a layer and step (8 an
# expert of 64), 8,960 tiles = 70 a layer and step; the traced 200 steps x 8 layers = 1,600 layer-steps hold 819,200
# pairs and 112,000 tiles. A tile streams an expert's three matrices, 3 x 2048 x 1536 x 2 = 18,874,368 bytes; a pair
# moves (3 x 2048 + 3 x 1536) x 2 = 21,504 bytes and multiplies 2 x 3 x 2048 x 1536 = 18,874,368 operations.
GMM_BYTES = 112_000 * 18_874_368 + 819_200 * 21_504
GMM_FLOPS = 819_200 * 18_874_368
# A softmax layer's paged calls: 200 steps x 125 rows x 1,200 positions = 30,000,000 positions, a position's K
# and V 2 x 8 x 64 x 2 = 2,048 bytes, 25,000 rows' q and o 2 x 32 x 64 x 2 = 8,192 bytes, in each of the two layers;
# 4 x 32 x 64 operations a position.
PAGED_BYTES = 2 * (30_000_000 * 2_048 + 25_000 * 8_192)
assert GMM_BYTES / 819e9 > GMM_FLOPS / 197e12 and PAGED_BYTES / 819e9 > 2 * 4 * 32 * 64 * 30_000_000 / 197e12  # by bandwidth
KNOWN = {
    "expert_gmm_time_share.all-experts": 100 * 2.88 / 4.8,
    "expert_gmm_roofline.all-experts": 100 * (GMM_BYTES / 819e9) / 2.88,  # 90.4
    "expert_pairs_per_held_expert.all-experts": 8.0,
    "paged_attn_time_share.all-experts": 100 * 0.48 / 4.8,  # the paged calls alone, not the 4,800 gmm calls beside them
    "paged_attn_roofline.all-experts": 100 * (PAGED_BYTES / 819e9) / 0.48,  # 31.4
    "pages_per_grid_step.all-experts": 26_100 / 4_100,
    "slot_occupancy.all-experts": 100 * 2000 / 2048,
    "tpot_p50_ms.all-experts": 30.0,
    "ttft_p50_ms.all-experts": 200.0,
    "engine_host_ms_per_step.all-experts": 4.0,
    "decode_ms_per_step.all-experts": 20.0,
    "prefill_busy_share.all-experts": 100 * 0.6 / 5.0,
    "tail_rows_per_step": 126.0,
}
NEW = tuple(KNOWN) + ("window_compiles.all-experts",)
TRACED = tuple(n for n in NEW if n.startswith(("expert_gmm_", "paged_attn_", "decode_ms", "prefill_busy")))
SOURCES = {"program_counter": ("tail_rows_per_step", "expert_pairs_per_held_expert.all-experts",
                               "pages_per_grid_step.all-experts", "slot_occupancy.all-experts",
                               "window_compiles.all-experts"),
           "host_clock": ("tpot_p50_ms.all-experts", "ttft_p50_ms.all-experts"),
           "program_span": ("engine_host_ms_per_step.all-experts",), "device_trace": TRACED}
JOINED = ("engine_host_cpu_ms_per_step.backlog", "engine_dispatch_blocked_ms_per_step.backlog",
          "kv_pages_reserved_share.backlog", "prefill_padding_share", "setup_before_replica_s", "setup_weights_s",
          "setup_warmup_s", "setup_after_replica_s")


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < 100 or name.startswith(("ttft_", "tail_rows"))


def test_no_compile_in_the_window_reads_zero_and_one_reads_one():
    read = cellspec.load_metric("window_compiles.all-experts")
    assert read(Context(_record(), 1)) == 0.0  # the one compile ended in the warm-up
    late = _record()
    late["stats"]["trace"].update(compiles=[[W0 - 30, 9.0], [W0 + 7, 2.0]], compiles_total=2)
    assert read(Context(late, 1)) == 1.0


def test_the_paged_twins_read_the_softmax_layers_calls_and_not_every_mosaic_call():
    """paged_attn_time_share sums every Mosaic call of the decode program: here
    the grouped matmul too (3.36 s of the 4.8 busy); its twin for this cell
    reads the `paged_attn` instance alone, and the roofline's needs count a
    head's own 64 columns, not the lane tile a row is padded to."""
    ctx = Context(_record(), 1)
    assert cellspec.load_metric("paged_attn_time_share")(ctx) == pytest.approx(100 * 3.36 / 4.8)
    assert cellspec.load_metric("paged_attn_time_share.all-experts")(ctx) == pytest.approx(100 * 0.48 / 4.8)
    arch = cellspec.architecture(_config())
    assert arch.full_decode_needs(_config(), context_tokens=1.0, rows=0.0) == {"flops": 4.0 * 32 * 64, "bytes": 2048.0}
    assert arch.full_decode_needs(_config(), context_tokens=0.0, rows=1.0)["bytes"] == 8192.0
    assert arch.expert_gmm_needs(_config(), pairs=0.0, tiles=1.0) == {"flops": 0.0, "bytes": 18_874_368.0}
    assert arch.expert_gmm_needs(_config(), pairs=1.0, tiles=0.0) == {"flops": 18_874_368.0, "bytes": 21_504.0}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the counters (the parent, which cannot run the cell at all) blanks the
    ones that read them, and raises nothing; a trace without the kernels'
    names, or without the kernel, blanks the ones that read it."""
    read = cellspec.load_metric(name)
    untraced = read(Context(dict(_record(), traced=None), 1))
    assert untraced == (None if name in TRACED else pytest.approx(KNOWN.get(name, 0.0)))
    if name not in TRACED and name != "expert_pairs_per_held_expert.all-experts":
        bare = dict(_record(), stats={}, window={"slot_steps_active": 0, "slot_steps_total": 0})
        bare["client"] = dict(bare["client"], records=[])
        assert read(Context(bare, 1)) is None  # a program without the record, a window without a request
    if name in ("tail_rows_per_step", "expert_pairs_per_held_expert.all-experts", "expert_gmm_roofline.all-experts"):
        assert read(Context(_record(with_counters=False), 1)) is None
    for kernel in ("paged_attn", "expert_gmm"):
        if name.startswith(kernel + "_"):
            no_names = _record()
            del no_names["traced"]["kernels"]  # a trace reduced before kernels were told apart
            assert read(Context(no_names, 1)) is None
            without = _record()
            for k in [k for k in without["traced"]["kernels"]["jit__decode_impl"] if k.startswith(kernel)]:
                del without["traced"]["kernels"]["jit__decode_impl"][k]
            assert read(Context(without, 1)) is None


def test_every_new_entry_is_in_the_manifest_by_membership():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
        assert name in SOURCES[by_name[name]["source"]], name
        if name.endswith(".all-experts"):  # a twin says of itself what the accepted twins of its reading say
            for suffix in (".backlog", ".backlog-chat", ".hybrid"):
                twin = by_name.get(name.replace(".all-experts", suffix))
                if twin:
                    assert [by_name[name][k] for k in ("unit", "better", "source", "layer")] == [
                        twin[k] for k in ("unit", "better", "source", "layer")]
    assert {by_name[n]["layer"] for n in NEW if n.startswith(("expert_", "paged_attn_", "pages_per"))} == {"kernels"}
    assert by_name["tail_rows_per_step"]["layer"] == "cache" and by_name["tail_rows_per_step"]["unit"] == "rows"
    assert by_name["expert_gmm_roofline.all-experts"]["better"] == by_name["paged_attn_roofline.all-experts"]["better"] == "higher"
    cells = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and (cells[0]["config"], cells[0]["traffic"], cells[0]["chips"]) == (
        "lfm2-24b-a2b-l9", "backlog-long-out", 1) and len(cells[0]["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == "lfm2-24b-a2b-l9")
    assert sorted(config["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert config["file"] == "benchmarks/configs/lfm2-24b-a2b-l9.json" and len(config["why"]) <= 200
    assert config["source"] == "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json"
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")["workloads"]
    for name in JOINED:
        assert CELL in by_name[name]["workloads"]
    # the lists a test here holds to their members stay as they were
    for name in ("ttft_p50_ms.backlog", "tpot_p50_ms.backlog", "slot_occupancy.backlog", "decode_ms_per_step.backlog",
                 "prefill_busy_share.backlog", "engine_host_ms_per_step.backlog", "window_compiles.backlog",
                 "pages_per_grid_step.backlog", "expert_gmm_roofline", "expert_gmm_roofline.hybrid",
                 "paged_attn_roofline.backlog-chat", "state_rows_per_step", "state_rows_per_step.ssm"):
        assert CELL not in by_name[name]["workloads"]
    spec = cellspec.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_out_tokens_per_s", "setup_s"}
    assert set(NEW) | set(JOINED) <= {m["name"] for m in spec["per_layer"]}
    assert spec["traffic"]["loop"] == "closed" and spec["traffic"]["output_len"]["median"] == 1024


def test_decode_steps_are_counted_from_the_softmax_layers_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"paged_attn": 2, "expert_gmm": 24}
    assert next(iter(cellspec.decode_kernels(ctx.config))) == "paged_attn"
    assert ctx.traced_decode_steps() == 200
    assert ctx.kernel_of("_decode_impl", "expert_gmm") == {"seconds": pytest.approx(2.88), "calls": 4800}
    assert cellspec.routing(ctx.config) == 8  # one choice a routed layer: the routed limits


def test_the_counts_match_the_hand_counts():
    """A conv mixer 16,783,360 (in_proj 12,582,912, taps 6,144, out_proj
    4,194,304), an attention mixer 10,485,888 (two head norms of 64 in it),
    two norms a layer 4,096, a router 131,072 and its bias 64, an expert
    9,437,184 and 64 of them 603,979,776, the dense FFN 72,351,744:
    89,139,200 + 2 x 614,600,896 + 6 x 620,898,368 + the embedding 134,217,728
    + the final norm = 5,177,950,976 parameters, 4,831,838,208 of them in
    experts; a token multiplies 8 x 4 x 9,437,184 of those (ISSUE 50's hand
    count). One period (1 + 4 layers) counts 2,700,654,976 by the same
    functions."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    conv, attention = 2048 * 6144 + 2048 * 2048, 2 * 2048 * 2048 + 2 * 2048 * 512
    expert, dense_ffn, router = 3 * 2048 * 1536, 3 * 2048 * 11776, 2048 * 64
    assert (conv + 3 * 2048, attention + 128, expert, dense_ffn, router) == (
        16_783_360, 10_485_888, 9_437_184, 72_351_744, 131_072)
    assert conv + 6144 + 4096 + dense_ffn == 89_139_200
    assert attention + 128 + 4096 + router + 64 + 64 * expert == 614_600_896
    assert conv + 6144 + 4096 + router + 64 + 64 * expert == 620_898_368
    assert counts["embedding"] == 65536 * 2048 == 134_217_728 and counts["lm_head"] == 0
    assert counts["total"] == 89_139_200 + 2 * 614_600_896 + 6 * 620_898_368 + 134_217_728 + 2048 == 5_177_950_976
    in_experts = 8 * 64 * expert
    assert in_experts == 4_831_838_208
    common = 7 * conv + 2 * attention + dense_ffn + 8 * router + 134_217_728  # the tied head multiplied once more
    assert counts["resident_matmul"] == common + in_experts
    assert counts["matmul"] == common + 8 * 4 * expert
    assert arch.attention_dims(model) == (2, 32, 8, 64) and arch.routing(model) == 8
    one = arch.param_counts(dict(model, num_hidden_layers=5, layer_types=model["layer_types"][:5]))
    assert one["total"] == 2_700_654_976 and one["resident_matmul"] - one["matmul"] == 4 * 60 * expert


def test_the_configuration_keeps_every_published_number():
    """The catalog row's `config`: every key of it stands in the file with the
    published value but the three in `reduced`, whose published values stand
    under `published`; and the groups the harness reads."""
    model = _config()
    published = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048, "intermediate_size": 11776,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe", "moe_intermediate_size": 1536, "norm_eps": 1e-05,
        "norm_topk_prob": True, "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 4,
        "num_key_value_heads": 8, "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}
    for key, value in published.items():
        assert model[key] == value, key
    types = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 9 + ["full_attention", "conv"]
    assert model["published"] == {"num_hidden_layers": 40, "num_dense_layers": 2, "layer_types": types}
    assert (model["num_hidden_layers"], model["num_dense_layers"]) == (9, 1)
    assert model["layer_types"] == types[:1] + types[2:10]
    assert sorted(model["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    assert model["architecture"] == "lfm2_moe" and model["tie_word_embeddings"] is True
    assert (model["first_k_dense_replace"], model["n_routed_experts"]) == (1, 64) and set(model["derived"]) == {
        "first_k_dense_replace", "n_routed_experts"}
    assert {"tie_word_embeddings", "router_selection", "router_weights", "initial_values"} <= set(model["assumed"])
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["head_dim"], kw["n_kv_heads"], kw["n_heads"], kw["d_ff"]) == (2048, 64, 8, 32, 11776)
    assert (kw["n_experts"], kw["experts_held"], kw["first_expert"], kw["expert_top_k"], kw["expert_d_ff"]) == (64, 64, 0, 4, 1536)
    assert [k.mixer for k in kw["layer_pattern"]] == ["conv", "attention", "conv", "conv"] and kw["n_dense_layers"] == 1
    assert kw["param_dtype"] == "bfloat16" and kw["qk_norm"] and kw["router_bias"] and kw["tie_embeddings"]
    eng = model["engine"]
    assert (eng["max_slots"], eng["total_pages"], eng["max_seq"], eng["page_size"], eng["decode_block"]) == (
        128, 2304, 3712, 128, 8) and eng["prefill_buckets"] == [256, 512, 1024, 2048] and not eng["prefix_cache"]
    assert eng["total_pages"] >= 16 * eng["max_slots"]


def test_shrink_keeps_the_layers_and_the_derived_keys_consistent():
    model = _config()
    cellspec.architecture(model).shrink(model)
    assert (model["num_hidden_layers"], model["num_dense_layers"], len(model["layer_types"])) == (9, 1, 9)
    assert model["n_routed_experts"] == model["num_experts"] == 8 and model["first_k_dense_replace"] == 1
    kw = cellspec.transformer_kwargs(model)
    assert kw["experts_held"] == kw["n_experts"] == 8 and kw["head_dim"] == 32 and kw["d_model"] == 128
