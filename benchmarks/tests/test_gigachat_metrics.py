"""The readers the cell that serves a latent-attention layer beside
gated-delta-rule layers in ONE model brought (latent_attn_roofline.latent-delta,
which counts the kernel once a LATENT layer, and the `.latent-delta` twins of the
accepted readers whose lists the cell cannot join), each on a hand-written run
record with the answer worked out by hand, `None` where a counter or a kernel is
absent, the manifest's new entries by membership, and the architecture file's
counts against the hand counts of its configuration
(gigachat3.5-432b-a28b-ep16)."""
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
CELL = "gigachat3.5-432b-ep16.backlog-long-out"
SUF = ".latent-delta"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "gigachat3.5-432b-a28b-ep16.json")) as f:
        return json.load(f)


def _step(t, block, counts=None, pages=(0, 0)):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19, "emit": 0.004}, "block": block,
           "live_pages": pages[0], "grid_steps": pages[1]}
    if counts is not None:
        rec.update(zip(("expert_pairs", "expert_tiles", "state_rows"), counts))
    return rec


def _record(with_counters=True):
    """200 traced decode steps (`kda_step`'s 800 calls at 4 a step: the dense
    layer's instance and the period body's three): 1.6 s in them, 0.1 s in
    `latent_attn`'s 200 calls (one latent layer), 1.44 s in the grouped matmul's
    2,400 (12 instances, 200 calls each), of a decode program of 4.0 s; prefill
    programs of 0.6 s with 0.012 s in `kda_chunk`'s 12 calls (3 prompts x 4 delta
    layers); 4.8 s busy of a window of 5.0 s. In the window two decode blocks of 8
    steps: each step routes 128 rows x 8 choices in each of 4 routed layers, of
    which a sixteenth lands here (2,048 pairs a block) over 14 live tiles a layer
    (448 a block); 125 and 127 states rewritten a step; one step without a
    block, one block before the window. The window's two blocks walked 13,000 and
    13,100 pages of the ONE latent layer in 2,000 and 2,100 grid steps; over the
    200 steps around the trace 25,000 (slot, step) pairs attended 30,000,000
    cached positions; 2,000 of the window's 2,048 slot steps held a request;
    three requests' first tokens fell inside the window, 100, 300 and 200 ms
    after they were due, 20, 40 and 30 ms a token."""
    c = (lambda *n: n) if with_counters else (lambda *n: None)
    steps = [_step(W0 - 4, 8, c(9999, 999, 99), (9999, 9)), _step(W0 + 1, 8, c(2048, 448, 8 * 125), (13000, 2000)),
             _step(W0 + 2, 0, c(0, 0, 0)), _step(W0 + 3, 8, c(2048, 448, 8 * 127), (13100, 2100))]
    records = [{"status": 200, "error": None, "done": W0 + 9, "n_out": 11, "out_len": 11, "bad_tokens": 0,
                "due": W0 + 5, "t_first": W0 + 5 + ttft, "t_last": W0 + 5 + ttft + 10 * tpot, "chunks": []}
               for ttft, tpot in ((0.1, 0.02), (0.3, 0.04), (0.2, 0.03))]
    decode = {"kda_step.4": {"seconds": 0.4, "calls": 200}, "latent_attn.9": {"seconds": 0.1, "calls": 200}}
    decode.update({f"kda_step.{11 + i}": {"seconds": 0.4, "calls": 200} for i in range(3)})
    decode.update({f"expert_gmm.{20 + i}": {"seconds": 0.12, "calls": 200} for i in range(12)})
    prefill = {"kda_chunk.3": {"seconds": 0.003, "calls": 3}, "flash_attn_fwd.5": {"seconds": 0.05, "calls": 3}}
    prefill.update({f"kda_chunk.{8 + i}": {"seconds": 0.003, "calls": 3} for i in range(3)})
    traced = {
        "window_s": 5.0, "busy_s": 4.8, "devices": 1,
        "module_s": {"jit__decode_impl": 4.0, "jit__prefill_batch_impl": 0.6},
        "module_runs": {"jit__decode_impl": 25, "jit__prefill_batch_impl": 3},
        "kernel": {"jit__decode_impl": {"seconds": 3.14, "calls": 3400}},
        "kernels": {"jit__decode_impl": decode, "jit__prefill_batch_impl": prefill},
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


# Worked out by hand, at the published widths and the chip's published peaks (819 GB/s, 197 T operations/s).
# The grouped matmul: 4,096 pairs over 16 steps x 4 routed layers = 64 a layer and step (4 an expert of 16), 896
# tiles = 14 a layer and step; the traced 200 steps x 4 layers = 800 layer-steps hold 51,200 pairs and 11,200 tiles.
# A tile streams an expert's three matrices, 3 x 7168 x 2048 x 2 = 88,080,384 bytes; a pair moves (3 x 7168 + 3 x
# 2048) x 2 = 55,296 bytes and multiplies 2 x 3 x 7168 x 2048 = 88,080,384 operations.
GMM_BYTES = 11_200 * 88_080_384 + 51_200 * 55_296
# `kda_step`: 126 rows a step x 200 steps x 4 delta layers = 100,800 rows; a row's state 64 x 128 x 128 x 4 bytes read
# and written (8,388,608), q and k of 32 key heads and v and o of 64 value heads in float32 ((64 + 128) x 128 x 4 =
# 98,304), the decay and beta a scalar a head (512): 8,487,424 bytes; 64 x 7 x 128 x 128 = 7,340,032 operations.
STEP_BYTES = 100_800 * 8_487_424
# `kda_chunk`: 12 calls of (402,048 - 400,000) / 3 padded tokens = 8,192 positions of one layer; a position reads q
# and k of 32 heads and v of 64 and writes o in bfloat16 ((64 + 128) x 128 x 2 = 49,152) and two float32 scalars a
# head (512): 49,664 bytes; 2 x (4 x 64 x 128 + 3 x 128 x 128) x 64 = 10,485,760 operations.
CHUNK_BYTES, CHUNK_FLOPS = 8_192 * 49_664, 8_192 * 10_485_760
# `latent_attn`, ONE layer: 30,000,000 positions' rows of (512 + 64) x 2 = 1,152 bytes, 25,000 rows' absorbed
# queries and contexts 64 x (576 + 512) x 2 = 139,264 bytes; 2 x 64 x 1,088 = 139,264 operations a position.
LATENT_BYTES, LATENT_FLOPS = 30_000_000 * 1_152 + 25_000 * 139_264, 30_000_000 * 139_264
assert GMM_BYTES / 819e9 > 51_200 * 88_080_384 / 197e12 and STEP_BYTES / 819e9 > 100_800 * 7_340_032 / 197e12
assert CHUNK_BYTES / 819e9 > CHUNK_FLOPS / 197e12 and LATENT_BYTES / 819e9 > LATENT_FLOPS / 197e12  # all by bandwidth
KNOWN = {
    "linear_attn_step_time_share.latent-delta": 100 * 1.6 / 4.8,
    "linear_attn_step_roofline.latent-delta": 100 * (STEP_BYTES / 819e9) / 1.6,  # 65.3
    "linear_attn_chunk_time_share.latent-delta": 100 * 0.012 / 4.8,
    "linear_attn_chunk_roofline.latent-delta": 100 * (CHUNK_BYTES / 819e9) / 0.012,  # 4.1
    "state_rows_per_step.latent-delta": 126.0,
    "latent_attn_time_share.latent-delta": 100 * 0.1 / 4.8,
    "latent_attn_roofline.latent-delta": 100 * (LATENT_BYTES / 819e9) / 0.1,  # 46.4
    "expert_gmm_time_share.latent-delta": 100 * 1.44 / 4.8,
    "expert_gmm_roofline.latent-delta": 100 * (GMM_BYTES / 819e9) / 1.44,  # 83.9
    "expert_pairs_per_held_expert.latent-delta": 4.0,
    "pages_per_grid_step.latent-delta": 26_100 / 4_100,
    "decode_ms_per_step.latent-delta": 20.0,
    "prefill_busy_share.latent-delta": 100 * 0.6 / 5.0,
    "slot_occupancy.latent-delta": 100 * 2000 / 2048,
    "tpot_p50_ms.latent-delta": 30.0,
    "ttft_p50_ms.latent-delta": 200.0,
    "engine_host_ms_per_step.latent-delta": 4.0,
}
NEW = tuple(KNOWN) + ("window_compiles.latent-delta",)
TRACED = tuple(n for n in NEW if n.startswith(("expert_gmm_", "latent_attn_", "linear_attn_", "decode_ms", "prefill_busy")))
SOURCES = {"program_counter": tuple(n + SUF for n in ("state_rows_per_step", "expert_pairs_per_held_expert",
                                                      "pages_per_grid_step", "slot_occupancy", "window_compiles")),
           "host_clock": ("tpot_p50_ms" + SUF, "ttft_p50_ms" + SUF),
           "program_span": ("engine_host_ms_per_step" + SUF,), "device_trace": TRACED}
JOINED = ("engine_host_cpu_ms_per_step.backlog", "engine_dispatch_blocked_ms_per_step.backlog",
          "kv_pages_reserved_share.backlog", "prefill_padding_share", "setup_before_replica_s", "setup_weights_s",
          "setup_warmup_s", "setup_after_replica_s")


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < 100 or name.startswith(("ttft_", "state_rows"))


def test_no_compile_in_the_window_reads_zero_and_one_reads_one():
    read = cellspec.load_metric("window_compiles" + SUF)
    assert read(Context(_record(), 1)) == 0.0  # the one compile ended in the warm-up
    late = _record()
    late["stats"]["trace"].update(compiles=[[W0 - 30, 9.0], [W0 + 7, 2.0]], compiles_total=2)
    assert read(Context(late, 1)) == 1.0


def test_the_latent_roofline_counts_one_layer_where_the_accepted_reader_counts_five():
    """latent_attn_roofline multiplies a layer's work by `num_hidden_layers`
    (right for a model that is latent throughout); here one layer of five is
    latent and the new reader multiplies by `decode_kernels`' count. The needs
    are the mathematics': a row of 1,152 bytes whatever the pool pads it to."""
    ctx = Context(_record(), 1)
    own = cellspec.load_metric("latent_attn_roofline" + SUF)(ctx)
    assert cellspec.load_metric("latent_attn_roofline")(ctx) == pytest.approx(5 * own)
    arch = cellspec.architecture(_config())
    assert arch.latent_decode_needs(_config(), context_tokens=1.0, rows=0.0) == {"flops": 139_264.0, "bytes": 1_152.0}
    assert arch.latent_decode_needs(_config(), context_tokens=0.0, rows=1.0)["bytes"] == 139_264.0
    assert arch.kda_step_needs(_config(), rows=1.0) == {"flops": 64 * 7.0 * 128 * 128, "bytes": 8_487_424.0}
    assert arch.kda_chunk_needs(_config(), padded_tokens=1.0) == {"flops": 10_485_760.0, "bytes": 49_664.0}
    assert arch.expert_gmm_needs(_config(), pairs=0.0, tiles=1.0) == {"flops": 0.0, "bytes": 88_080_384.0}
    assert arch.expert_gmm_needs(_config(), pairs=1.0, tiles=0.0) == {"flops": 88_080_384.0, "bytes": 55_296.0}


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the counters (the parent, which cannot run the cell at all) blanks the
    ones that read them, and raises nothing; a trace without the kernels'
    names, or without the kernel, blanks the ones that read it."""
    read = cellspec.load_metric(name)
    untraced = read(Context(dict(_record(), traced=None), 1))
    assert untraced == (None if name in TRACED else pytest.approx(KNOWN.get(name, 0.0)))
    if name not in TRACED and name != "expert_pairs_per_held_expert" + SUF:
        bare = dict(_record(), stats={}, window={"slot_steps_active": 0, "slot_steps_total": 0})
        bare["client"] = dict(bare["client"], records=[])
        assert read(Context(bare, 1)) is None  # a program without the record, a window without a request
    if name.startswith(("state_rows", "expert_pairs", "expert_gmm_roofline", "linear_attn_step_roofline")):
        assert read(Context(_record(with_counters=False), 1)) is None
    for kernel, program, fragment in (("linear_attn_step", "jit__decode_impl", "kda_step"),
                                      ("linear_attn_chunk", "jit__prefill_batch_impl", "kda_chunk"),
                                      ("latent_attn", "jit__decode_impl", "latent_attn"),
                                      ("expert_gmm", "jit__decode_impl", "expert_gmm")):
        if name.startswith(kernel + "_"):
            no_names = _record()
            del no_names["traced"]["kernels"]  # a trace reduced before kernels were told apart
            assert read(Context(no_names, 1)) is None
            without = _record()
            for k in [k for k in without["traced"]["kernels"][program] if k.startswith(fragment)]:
                del without["traced"]["kernels"][program][k]
            assert read(Context(without, 1)) is None


def test_every_new_entry_is_in_the_manifest_by_membership():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    assert [m["name"] for m in manifest["per_layer"][-len(NEW):]] and {m["name"] for m in manifest["per_layer"][-len(NEW):]} == set(NEW)
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
        assert name in SOURCES[by_name[name]["source"]], name
        # a twin says of itself what the accepted reading says (its closed-loop spelling where it has one), but what it moves
        accepted = by_name.get(name[:-len(SUF)] + ".backlog", by_name[name[:-len(SUF)]])
        assert [by_name[name][k] for k in ("unit", "better", "source", "layer")] == [
            accepted[k] for k in ("unit", "better", "source", "layer")], name
    assert {by_name[n]["layer"] for n in NEW if n.startswith(("expert_", "latent_", "linear_", "pages_per"))} == {"kernels"}
    cells = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert manifest["workloads"][-1]["name"] == CELL and manifest["configs"][-1]["name"] == "gigachat3.5-432b-a28b-ep16"
    assert len(cells) == 1 and (cells[0]["config"], cells[0]["traffic"], cells[0]["chips"]) == (
        "gigachat3.5-432b-a28b-ep16", "backlog-long-out", 1) and len(cells[0]["why"]) <= 200
    config = manifest["configs"][-1]
    assert sorted(config["reduced"]) == ["first_k_dense_replace", "full_attention_layers", "n_routed_experts",
                                         "num_hidden_layers", "vocab_size"]
    assert config["file"] == "benchmarks/configs/gigachat3.5-432b-a28b-ep16.json" and len(config["why"]) <= 200
    assert config["source"] == "https://huggingface.co/ai-sage/GigaChat3.5-432B-A28B/blob/main/config.json"
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")["workloads"]
    for name in JOINED:
        assert by_name[name]["workloads"][-1] == CELL
    # the lists a test here holds to their members stay as they were
    for name in ("ttft_p50_ms.backlog", "tpot_p50_ms.backlog", "slot_occupancy.backlog", "decode_ms_per_step.backlog",
                 "prefill_busy_share.backlog", "engine_host_ms_per_step.backlog", "window_compiles.backlog",
                 "pages_per_grid_step.backlog", "pages_per_grid_step.long-out", "expert_gmm_roofline",
                 "expert_gmm_roofline.hybrid", "expert_gmm_roofline.all-experts", "latent_attn_roofline",
                 "latent_attn_time_share", "linear_attn_step_roofline", "state_rows_per_step", "state_rows_per_step.ssm"):
        assert CELL not in by_name[name]["workloads"]
    spec = cellspec.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_out_tokens_per_s", "setup_s"}
    assert set(NEW) | set(JOINED) <= {m["name"] for m in spec["per_layer"]}
    assert spec["traffic"]["loop"] == "closed" and spec["traffic"]["output_len"]["median"] == 1024


def test_decode_steps_are_counted_from_the_delta_layers_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"kda_step": 4, "latent_attn": 1, "expert_gmm": 12}
    assert next(iter(cellspec.decode_kernels(ctx.config))) == "kda_step"
    assert ctx.traced_decode_steps() == 200
    assert ctx.kernel_of("_decode_impl", "kda_step") == {"seconds": pytest.approx(1.6), "calls": 800}
    assert ctx.kernel_of("_decode_impl", "expert_gmm") == {"seconds": pytest.approx(1.44), "calls": 2400}
    assert ctx.kernel_of("_prefill_batch_impl", "kda_chunk") == {"seconds": pytest.approx(0.012), "calls": 12}
    assert cellspec.routing(ctx.config) == 4  # one choice a routed layer: the routed limits


def test_the_counts_match_the_hand_counts():
    """A latent mixer 159,842,304 (wq_a 11,010,048, wq_b 18,874,368, wkv_a
    4,128,768, wk_b + wv_b 8,388,608, wo 58,720,256, the gate 58,720,256) and
    2,048 of inner norms; a delta mixer 235,798,528 (q and k 58,720,256, v, the
    gate and wo 176,160,768, beta and the decay 917,504) and 65,792 beside
    (taps 65,536, A_log 64, dt_bias 64, the head norm 128); an expert
    44,040,192; a routed FFN with 16 held, the shared one and the router
    750,518,272; the dense FFN 396,361,728; four norms a layer 28,672:
    632,254,720 + 910,391,296 + 3 x 986,411,264 + 229,834,752 + 7,168 =
    4,731,721,728 parameters resident, 2,818,572,288 of them in held experts;
    of expert parameters a token multiplies, in a routed layer, the shared
    expert's 44,040,192 and 8 x 16 / 256 of one routed expert's (22,020,096)
    (ISSUE 54's hand count)."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    d = 7168
    latent = d * 1536 + 1536 * 64 * 192 + d * 576 + 512 * 64 * 256 + 8192 * d + d * 8192
    delta = 2 * d * 32 * 128 + 3 * d * 64 * 128 + 2 * d * 64
    expert, dense_ffn, router = 3 * d * 2048, 3 * d * 18432, d * 256
    assert (latent, delta, expert, dense_ffn, router) == (159_842_304, 235_798_528, 44_040_192, 396_361_728, 1_835_008)
    assert 17 * expert + router == 750_518_272
    assert delta + 65_792 + dense_ffn + 28_672 == 632_254_720
    assert latent + 2_048 + 750_518_272 + 28_672 == 910_391_296
    assert delta + 65_792 + 750_518_272 + 28_672 == 986_411_264
    assert counts["embedding"] == counts["lm_head"] == 16_032 * d == 114_917_376
    assert counts["total"] == 632_254_720 + 910_391_296 + 3 * 986_411_264 + 229_834_752 + 7_168 == 4_731_721_728
    in_experts = 4 * 16 * expert
    assert in_experts == 2_818_572_288
    common = latent + 4 * delta + dense_ffn + 4 * (expert + router) + 16_032 * d  # the head multiplied, the embedding read
    assert counts["resident_matmul"] == common + in_experts
    assert counts["matmul"] == common + 4 * 22_020_096 and 8 * 16 * expert // 256 == 22_020_096
    assert counts["resident_matmul"] - counts["matmul"] == in_experts - 4 * 22_020_096
    assert arch.attention_dims(model) == (1, 64, 64, 192) and arch.routing(model) == 4
    whole = dict(model, num_hidden_layers=40, first_k_dense_replace=3, n_routed_experts=256, vocab_size=128_256,
                 full_attention_layers=model["published"]["full_attention_layers"])
    assert round(arch.param_counts(whole)["total"] / 1e9, 1) == 430.5  # the name's 432 B, less the two prediction modules


def test_the_configuration_keeps_every_published_number():
    """The catalog row's `config`: every key of it stands in the file with the
    published value but the five in `reduced`, whose published values stand
    under `published`; and the groups the harness reads."""
    model = _config()
    published = {
        "max_position_embeddings": 262144, "hidden_size": 7168, "intermediate_size": 18432, "moe_intermediate_size": 2048,
        "nextn_is_sparse": False, "num_attention_heads": 64, "n_shared_experts": 1, "routed_scaling_factor": 2.5,
        "kv_lora_rank": 512, "q_lora_rank": 1536, "qk_rope_head_dim": 64, "v_head_dim": 128, "qk_nope_head_dim": 128,
        "qk_head_dim": 192, "n_group": 1, "topk_group": 1, "num_experts_per_tok": 8, "norm_topk_prob": True,
        "rope_interleave": True, "num_key_value_heads": 64, "hidden_act": "silu", "rms_norm_eps": 1e-06,
        "rope_theta": 100000,
        "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 8, "mscale": 1, "mscale_all_dim": 1,
                         "original_max_position_embeddings": 32768, "type": "yarn"},
        "attention_bias": False, "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post",
        "layernorm_gating_weight": 2, "gated_attention": True, "use_shared_expert_sigmoid": False,
        "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet",
        "linear_key_head_dim": 128, "linear_value_head_dim": 128, "linear_conv_kernel_dim": 4,
        "linear_num_key_heads": 32, "linear_num_value_heads": 64,
        "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "linear_sigmoid_gate_scale": 2,
        "linear_attn_o_norm_eps": 1e-06, "swiglu_limit": 10, "tie_word_embeddings": False,
        "num_nextn_predict_layers": 2, "model_type": "gigachat3_5", "tf_legacy_loss": False}
    for key, value in published.items():
        assert model[key] == value, key
    assert model["published"] == {"num_hidden_layers": 40, "first_k_dense_replace": 3, "n_routed_experts": 256,
                                  "vocab_size": 128256, "full_attention_layers": [3, 7, 11, 15, 19, 23, 27, 31, 35, 39]}
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["full_attention_layers"]) == (5, 1, [1])
    assert (model["n_routed_experts"], model["router_experts"], model["first_expert"], model["vocab_size"]) == (16, 256, 0, 16032)
    assert sorted(model["reduced"]) == sorted(model["published"]) == sorted(model["cut"])
    assert model["architecture"] == "gigachat3_5" and "16 that share each layer" in model["deployment"]
    assert {"norm", "gated_attention", "use_mla_scaling_factor", "rope_pairing", "delta_mixer", "linear_gating_type", "state",
            "swiglu_limit", "score", "num_nextn_predict_layers", "weights", "absent_experts"} <= set(model["assumed"])
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["head_dim"], kw["n_heads"], kw["d_ff"], kw["n_layers"], kw["n_dense_layers"]) == (
        7168, 128, 64, 18432, 5, 1)
    assert (kw["n_experts"], kw["experts_held"], kw["first_expert"], kw["expert_top_k"], kw["expert_d_ff"]) == (256, 16, 0, 8, 2048)
    assert [k.mixer for k in kw["layer_pattern"]] == ["delta", "latent", "delta", "delta"]
    delta, latent = kw["layer_pattern"][:2]
    assert (delta.n_heads, delta.n_key_heads, delta.conv_size, delta.low_rank, delta.gate_scale) == (64, 32, 4, 0, 2.0)
    assert (latent.n_heads, latent.yarn_factor, latent.yarn_original_len, latent.rope_theta, latent.attention_factor) == (
        64, 8.0, 32768, 100000.0, 1.0)
    assert latent.softmax_factor == pytest.approx(1.2079 ** 2, rel=1e-4)
    assert kw["param_dtype"] == "bfloat16" and kw["sandwich_norm"] and kw["attn_gate"] == "elementwise"
    assert (kw["norm_gating"], kw["swiglu_limit"], kw["routed_scaling"], kw["router_score"]) == (2.0, 10.0, 2.5, "sigmoid")
    eng = model["engine"]
    assert (eng["max_slots"], eng["total_pages"], eng["max_seq"], eng["page_size"], eng["decode_block"]) == (
        128, 3584, 3712, 128, 8) and eng["prefill_buckets"] == [256, 512, 1024, 2048] and not eng["prefix_cache"]
    assert eng["total_pages"] >= 16 * eng["max_slots"]


def test_shrink_keeps_the_layers_the_heads_and_the_expert_counts_consistent():
    model = _config()
    cellspec.architecture(model).shrink(model)
    assert (model["num_hidden_layers"], model["first_k_dense_replace"], model["full_attention_layers"]) == (5, 1, [1])
    assert model["linear_num_value_heads"] == 2 * model["linear_num_key_heads"] and model["n_routed_experts"] == 4
    assert model["qk_head_dim"] == model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    kw = cellspec.transformer_kwargs(model)
    assert kw["experts_held"] == 4 and kw["n_experts"] == 16 and kw["head_dim"] == 32 and kw["d_model"] == 128
    assert [k.mixer for k in kw["layer_pattern"]] == ["delta", "latent", "delta", "delta"]
