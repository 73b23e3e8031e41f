"""The readers this architecture brought (ssm_step_time_share,
ssm_step_roofline, ssm_chunk_time_share, ssm_chunk_roofline,
state_rows_per_step.ssm, and the `.backlog-chat` twins of the accepted readers
whose lists the cell cannot join), each on a hand-written run record with the
answer worked out by hand, `None` where a counter or a kernel is absent, the
manifest's new entries by membership, and the architecture file's counts
against the hand counts of its configuration (granite-4.0-h-micro)."""
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
CELL = "granite-4.0-h-micro.backlog-chat"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "granite-4.0-h-micro.json")) as f:
        return json.load(f)


def _step(t, block, rows=None, pages=(0, 0)):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19, "emit": 0.004}, "block": block,
           "live_pages": pages[0], "grid_steps": pages[1]}
    if rows is not None:
        rec["state_rows"] = rows
    return rec


def _record(with_counter=True):
    """100 traced decode steps (the paged kernel's 400 calls at 4 a step):
    1.8 s in the state-space layers' 3,600 one-token calls and 0.2 s in the
    paged kernel's, of a decode program of 3.0 s; 30 prefilled requests inside
    the trace, 1,080 chunked calls of 0.9 s in all, prefill programs of 2.5 s;
    6.0 s busy of a window of 6.5 s. Around the trace the replica dispatched
    40 requests of 20,480 padded tokens. In the window two decode blocks of 8
    steps: 60 and 64 states rewritten a step; one step without a block, one
    block before the window. The window's two blocks walked 2,048 and 2,560
    pages of one softmax layer in 512 grid steps each; over the 200 steps
    around the trace 12,400 (slot, step) pairs attended 4,960,000 cached
    positions (62 rows a step, 400 positions a row); 992 of the window's 1,024
    slot steps held a request; three requests' first tokens fell inside the
    window, 100, 300 and 200 ms after they were due, 20, 40 and 30 ms a token."""
    w = (lambda n: n) if with_counter else (lambda n: None)
    steps = [_step(W0 - 4, 8, w(999), (9999, 9)), _step(W0 + 1, 8, w(8 * 60), (2048, 512)), _step(W0 + 2, 0, w(0)),
             _step(W0 + 3, 8, w(8 * 64), (2560, 512))]
    records = [{"status": 200, "error": None, "done": W0 + 9, "n_out": 11, "out_len": 11, "bad_tokens": 0,
                "due": W0 + 5, "t_first": W0 + 5 + ttft, "t_last": W0 + 5 + ttft + 10 * tpot, "chunks": []}
               for ttft, tpot in ((0.1, 0.02), (0.3, 0.04), (0.2, 0.03))]
    decode = {f"paged_attn.{5 + i}": {"seconds": 0.05, "calls": 100} for i in range(4)}
    decode.update({f"ssd_step.{20 + i}": {"seconds": 0.05, "calls": 100} for i in range(36)})
    traced = {
        "window_s": 6.5, "busy_s": 6.0, "devices": 1,
        "module_s": {"jit__decode_impl": 3.0, "jit__prefill_batch_impl": 2.5},
        "module_runs": {"jit__decode_impl": 13, "jit__prefill_batch_impl": 30},
        "kernel": {"jit__decode_impl": {"seconds": 2.0, "calls": 4000}},
        "kernels": {"jit__decode_impl": decode,
                    "jit__prefill_batch_impl": {f"ssd_chunk.{3 + i}": {"seconds": 0.025, "calls": 30} for i in range(36)}},
        "counters_before": {"decode_steps": 1000, "prefill_requests": 100, "prefill_padded_tokens": 400_000,
                            "decode_context_tokens": 10_000_000, "slot_steps_active": 60_000},
        "counters_after": {"decode_steps": 1200, "prefill_requests": 140, "prefill_padded_tokens": 420_480,
                           "decode_context_tokens": 14_960_000, "slot_steps_active": 72_400},
    }
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": [], "requests_total": 0, "steps": steps,
             "steps_total": 4, "phase_s": {}, "phase_n": {}, "dropped": {"requests": 0, "steps": 0},
             "compiles": [[W0 - 30, 9.0]], "compiles_total": 1}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config(), "traffic": {}, "plan": {"loop": "closed"},
            "client": {"w0": W0, "w1": W1, "records": records}, "stats": {"trace": trace},
            "window": {"slot_steps_active": 992, "slot_steps_total": 1024},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


# Worked out by hand. One-token rule: (480 + 512) / 16 = 62 rows a step; 100 steps x 36 layers = 223,200 rows; a
# row reads and writes 64 x 64 x 128 float32 and its x and y (64 x 64), B and C (128), dt and the decay (64) in
# float32: 4 x (2 x 524,288 + 2 x 4,096 + 2 x 128 + 2 x 64) = 4,228,608 bytes; 5 operations a value of the state.
STEP_BYTES = 223_200 * 4_228_608
STEP_FLOPS = 223_200 * 5 * 64 * 64 * 128
# Chunked rule: 20,480 / 40 = 512 padded tokens a request, 1,080 calls: 552,960 token-layers; a token 128 x 128 (the
# table C B^T) + 64 heads x (128 x 64 + 2 x 128 x 64) = 1,589,248 multiply-adds, and 2 x 4,096 x 2 (x, y) + 2 x 128 x
# 2 (B, C) + 2 x 64 x 4 (dt, the decay) = 17,408 bytes.
CHUNK_FLOPS = 2 * 1_589_248 * 552_960
CHUNK_BYTES = 552_960 * 17_408
assert STEP_BYTES / 819e9 > STEP_FLOPS / 197e12 and CHUNK_BYTES / 819e9 > CHUNK_FLOPS / 197e12  # both by bandwidth
# The softmax layers' paged calls: 100 steps x 62 rows x 400 positions = 2,480,000 positions a layer, a position's K
# and V 2 x 8 x 64 x 2 = 2,048 bytes, 6,200 rows' q and o 2 x 32 x 64 x 2 = 8,192 bytes: 5,129,830,400 bytes a layer,
# four layers; 4 x 32 x 64 operations a position.
PAGED_BYTES = 4 * (2_480_000 * 2_048 + 6_200 * 8_192)
assert PAGED_BYTES / 819e9 > 4 * 4 * 32 * 64 * 2_480_000 / 197e12
KNOWN = {
    "paged_attn_time_share.backlog-chat": 100 * 0.2 / 6.0,  # the four paged calls alone, not the 36 ssd_step beside them
    "paged_attn_roofline.backlog-chat": 100 * (PAGED_BYTES / 819e9) / 0.2,  # 12.5
    "pages_per_grid_step.backlog-chat": 4608 / 1024,
    "slot_occupancy.backlog-chat": 100 * 992 / 1024,
    "tpot_p50_ms.backlog-chat": 30.0,
    "ttft_p50_ms.backlog-chat": 200.0,
    "engine_host_ms_per_step.backlog-chat": 4.0,
    "ssm_step_time_share": 100 * 1.8 / 6.0,
    "ssm_chunk_time_share": 100 * 0.9 / 6.0,
    "ssm_step_roofline": 100 * (STEP_BYTES / 819e9) / 1.8,  # 64.0
    "ssm_chunk_roofline": 100 * (CHUNK_BYTES / 819e9) / 0.9,  # 1.31
    "state_rows_per_step.ssm": 62.0,
    "decode_ms_per_step.backlog-chat": 30.0,
    "prefill_busy_share.backlog-chat": 100 * 2.5 / 6.5,
}
NEW = tuple(KNOWN) + ("window_compiles.backlog-chat",)
TRACED = tuple(n for n in NEW if n.startswith(("ssm_", "paged_attn_", "decode_ms", "prefill_busy")))
SOURCES = {"program_counter": ("state_rows_per_step.ssm", "pages_per_grid_step.backlog-chat",
                               "slot_occupancy.backlog-chat", "window_compiles.backlog-chat"),
           "host_clock": ("tpot_p50_ms.backlog-chat", "ttft_p50_ms.backlog-chat"),
           "program_span": ("engine_host_ms_per_step.backlog-chat",), "device_trace": TRACED}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < 100 or name.startswith("ttft_")


def test_no_compile_in_the_window_reads_zero_and_one_reads_one():
    read = cellspec.load_metric("window_compiles.backlog-chat")
    assert read(Context(_record(), 1)) == 0.0  # the one compile ended in the warm-up
    late = _record()
    late["stats"]["trace"].update(compiles=[[W0 - 30, 9.0], [W0 + 7, 2.0]], compiles_total=2)
    assert read(Context(late, 1)) == 1.0
    del late["stats"]["trace"]["compiles"]
    assert read(Context(late, 1)) is None


def test_the_paged_twins_read_the_softmax_layers_calls_and_not_every_mosaic_call():
    """paged_attn_time_share sums every Mosaic call of the decode program: here
    `ssd_step` too (2.0 s of the 6.0 busy); its twin for this cell reads the
    four `paged_attn` instances alone. paged_attn_roofline counts a call a
    layer of `num_hidden_layers`, ten times this model's four."""
    ctx = Context(_record(), 1)
    assert cellspec.load_metric("paged_attn_time_share")(ctx) == pytest.approx(100 * 2.0 / 6.0)
    assert cellspec.load_metric("paged_attn_time_share.backlog-chat")(ctx) == pytest.approx(100 * 0.2 / 6.0)
    arch = cellspec.architecture(_config())
    assert arch.full_decode_needs(_config(), context_tokens=1.0, rows=0.0) == {"flops": 4.0 * 32 * 64, "bytes": 2048.0}
    assert arch.full_decode_needs(_config(), context_tokens=0.0, rows=1.0)["bytes"] == 8192.0


@pytest.mark.parametrize("name", NEW)
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the state counter blanks the two that read it, and raises nothing; a trace
    without the kernels' names, or without the two kernels (the parent, which
    cannot run the cell at all, or another architecture), blanks the four
    that read them; a trace with no prefill blanks the chunk's roofline."""
    read = cellspec.load_metric(name)
    untraced = read(Context(dict(_record(), traced=None), 1))
    assert untraced == (None if name in TRACED else pytest.approx(KNOWN.get(name, 0.0)))
    if name.endswith(".backlog-chat") and name not in TRACED:
        bare = dict(_record(), stats={}, window={"slot_steps_active": 0, "slot_steps_total": 0})
        bare["client"] = dict(bare["client"], records=[])
        assert read(Context(bare, 1)) is None  # a program without the record, a window without a request
    if name.startswith("paged_attn_"):
        without = _record()
        for kernel in [k for k in without["traced"]["kernels"]["jit__decode_impl"] if k.startswith("paged_attn")]:
            del without["traced"]["kernels"]["jit__decode_impl"][kernel]
        assert read(Context(without, 1)) is None
    if name in ("state_rows_per_step.ssm", "ssm_step_roofline"):
        assert read(Context(_record(with_counter=False), 1)) is None
    if name.startswith("ssm_"):
        no_names = _record()
        del no_names["traced"]["kernels"]  # a trace reduced before kernels were told apart
        assert read(Context(no_names, 1)) is None
        without = _record()
        for program in without["traced"]["kernels"].values():
            for kernel in [k for k in program if k.startswith("ssd_")]:
                del program[kernel]
        assert read(Context(without, 1)) is None
        dense = dict(_record(), config={"num_hidden_layers": 4, "hidden_size": 64, "num_attention_heads": 4,
                                        "num_key_value_heads": 2, "intermediate_size": 128, "vocab_size": 256})
        if name.endswith("roofline"):
            assert read(Context(dense, 1)) is None  # an architecture without the two `needs`
    if name == "ssm_chunk_roofline":
        no_prefill = _record()
        no_prefill["traced"]["counters_after"] = dict(no_prefill["traced"]["counters_before"])
        assert read(Context(no_prefill, 1)) is None


def test_every_new_entry_is_in_the_manifest_by_membership():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in NEW:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
        assert name in SOURCES[by_name[name]["source"]], name
        if name.endswith(".backlog-chat") and name.replace(".backlog-chat", ".backlog") in by_name:
            twin = by_name[name.replace(".backlog-chat", ".backlog")]  # a twin says of itself what its twin says
            assert [by_name[name][k] for k in ("unit", "better", "source", "layer")] == [
                twin[k] for k in ("unit", "better", "source", "layer")]
    assert {by_name[n]["layer"] for n in NEW if n.startswith(("ssm_", "paged_attn_", "pages_per"))} == {"kernels"}
    assert by_name["paged_attn_roofline.backlog-chat"]["better"] == "higher"
    assert by_name["state_rows_per_step.ssm"]["layer"] == "scheduler"
    assert by_name["decode_ms_per_step.backlog-chat"]["layer"] == by_name["prefill_busy_share.backlog-chat"]["layer"] == (
        "device programs")
    cells = [w for w in manifest["workloads"] if w["name"] == CELL]
    assert len(cells) == 1 and (cells[0]["config"], cells[0]["traffic"], cells[0]["chips"]) == (
        "granite-4.0-h-micro", "backlog-chat", 1) and len(cells[0]["why"]) <= 200
    config = next(c for c in manifest["configs"] if c["name"] == "granite-4.0-h-micro")
    assert config["reduced"] == [] and config["file"] == "benchmarks/configs/granite-4.0-h-micro.json"
    assert CELL in next(m for m in manifest["end_to_end"] if m["name"] == "serve_out_tokens_per_s")["workloads"]
    joined = ("engine_host_cpu_ms_per_step.backlog", "engine_dispatch_blocked_ms_per_step.backlog",
              "kv_pages_reserved_share.backlog", "setup_before_replica_s", "setup_weights_s", "setup_warmup_s",
              "setup_after_replica_s")
    for name in joined:
        assert CELL in by_name[name]["workloads"]
    # the lists a test here holds to their members stay as they were
    for name in ("ttft_p50_ms.backlog", "tpot_p50_ms.backlog", "slot_occupancy.backlog", "decode_ms_per_step.backlog",
                 "prefill_busy_share.backlog", "engine_host_ms_per_step.backlog", "window_compiles.backlog",
                 "pages_per_grid_step.backlog", "state_rows_per_step", "linear_attn_step_roofline"):
        assert CELL not in by_name[name]["workloads"]
    spec = cellspec.load_cell(CELL)
    assert {m["name"] for m in spec["end_to_end"]} == {"serve_out_tokens_per_s", "setup_s"}
    assert set(NEW) | set(joined) <= {m["name"] for m in spec["per_layer"]}


def test_the_traffic_is_chats_lengths_as_a_closed_loop_on_every_slot():
    traffic, chat = (json.load(open(os.path.join(BENCH_DIR, "traffic", name + ".json"))) for name in ("backlog-chat", "chat"))
    assert traffic["prompt_len"] == chat["prompt_len"] and traffic["output_len"] == chat["output_len"]
    assert (traffic["loop"], traffic["concurrency_x_slots"], traffic["multiset"], traffic["cycles"]) == ("closed", 1, 128, 48)
    assert (traffic["ramp_s"], traffic["drain_s"]) == (12.0, 60.0) and "prefix" not in traffic and "turns" not in traffic


def test_decode_steps_are_counted_from_the_softmax_layers_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"paged_attn": 4, "ssd_step": 36}
    assert ctx.traced_decode_steps() == 100
    assert ctx.kernel_of("_decode_impl", "ssd_step") == {"seconds": pytest.approx(1.8), "calls": 3600}
    assert ctx.kernel_of("_prefill_batch_impl", "ssd_chunk") == {"seconds": pytest.approx(0.9), "calls": 1080}
    assert cellspec.routing(ctx.config) is None  # no discrete choice: the dense limits


def test_the_counts_match_the_hand_counts():
    """The issue's table: a Mamba-2 mixer 25,847,232 (the input projection
    17,432,576, the output's 8,388,608, beside them 26,048), an attention
    mixer 10,485,760, a layer's FFN 50,331,648, two norms 4,096; 36 x
    76,182,976 + 4 x 60,821,504 + the embedding 205,520,896 + the final norm:
    3,191,396,096 parameters, 6,382,792,192 bytes in bfloat16."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    in_proj, out_proj = 2048 * (2 * 4096 + 2 * 128 + 64), 4096 * 2048
    small = 4 * 4352 + 4352 + 3 * 64 + 4096
    attention, ffn = 2 * 2048 * 2048 + 2 * 2048 * 512, 3 * 2048 * 8192
    assert (in_proj, out_proj, small, attention, ffn) == (17_432_576, 8_388_608, 26_048, 10_485_760, 50_331_648)
    assert in_proj + out_proj + small + ffn + 4096 == 76_182_976 and attention + ffn + 4096 == 60_821_504
    assert counts["embedding"] == 100_352 * 2048 == 205_520_896 and counts["lm_head"] == 0
    assert counts["total"] == 36 * 76_182_976 + 4 * 60_821_504 + 205_520_896 + 2048 == 3_191_396_096
    # a token multiplies every matrix and the tied head once more; the embedding's rows are read, not multiplied
    assert counts["matmul"] == counts["resident_matmul"] == (
        36 * (in_proj + out_proj) + 4 * attention + 40 * ffn + 205_520_896)
    assert arch.attention_dims(model) == (40, 32, 8, 64) and not hasattr(arch, "routing")
    assert arch.ssd_step_needs(model, rows=1.0) == {"flops": 5.0 * 64 * 64 * 128, "bytes": 4_228_608.0}
    assert arch.ssd_chunk_needs(model, padded_tokens=1.0) == {"flops": 2.0 * 1_589_248, "bytes": 17_408.0}


def test_the_configuration_keeps_every_published_number():
    """The catalog row's `config`: every key of it stands in the file with the
    published value (`reduced` is empty), and the groups the harness reads."""
    model = _config()
    published = {
        "attention_bias": False, "attention_multiplier": 0.015625, "embedding_multiplier": 12, "hidden_act": "silu",
        "hidden_size": 2048, "intermediate_size": 8192, "logits_scaling": 8, "mamba_chunk_size": 256,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False, "max_position_embeddings": 131072,
        "model_type": "granitemoehybrid", "normalization_function": "rmsnorm", "num_attention_heads": 32,
        "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8, "num_local_experts": 0,
        "position_embedding_type": "nope", "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
        "rope_theta": 10000, "shared_intermediate_size": 8192, "tie_word_embeddings": True, "vocab_size": 100352}
    for key, value in published.items():
        assert model[key] == value, key
    assert model["layer_types"] == (["mamba"] * 5 + ["attention"] + ["mamba"] * 4) * 4
    assert model["reduced"] == [] and model["architecture"] == "granite_hybrid"
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["head_dim"], kw["n_kv_heads"], kw["n_heads"], kw["d_ff"]) == (2048, 64, 8, 32, 8192)
    assert len(kw["layer_pattern"]) == 10 and kw["layer_pattern"][5].mixer == "attention"
    assert kw["param_dtype"] == "bfloat16" and "residual_dtype" not in kw
    eng = model["engine"]
    assert (eng["max_slots"], eng["total_pages"], eng["max_seq"], eng["page_size"], eng["decode_block"]) == (
        64, 640, 2048, 128, 8) and eng["prefill_buckets"] == [128, 256, 512, 1024, 1536] and not eng["prefix_cache"]
