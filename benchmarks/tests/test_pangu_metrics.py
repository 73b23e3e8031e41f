"""The readers this architecture brought (latent_attn_time_share,
latent_attn_roofline, expert_gmm_time_share, expert_gmm_roofline,
expert_pairs_per_held_expert), each on a hand-written run record with the
answer worked out by hand, and the architecture file's counts against the
hand counts of its configuration (openpangu-ultra-moe-718b-ep16)."""
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
CELL = "openpangu-718b-ep16.backlog-long-out"


def _config():
    with open(os.path.join(BENCH_DIR, "configs", "openpangu-ultra-moe-718b-ep16.json")) as f:
        return json.load(f)


def _step(t, block, counts=None):
    rec = {"t": t, "dur": 0.2, "phase_s": {"decode_fetch": 0.19}, "block": block, "live_pages": 0}
    if counts is not None:
        rec.update(expert_pairs=counts[0], expert_tiles=counts[1])
    return rec


def _record(with_counter=True):
    """100 traced decode steps (the latent kernel's 100 + 400 calls over its 5
    a step), 0.5 s in the latent kernel and 2.0 s in the grouped matmul's
    1,200 calls of 5.0 s busy; around the trace 200 steps attended 160,000
    positions in 128 active slots each; in the window two decode blocks of 8
    steps with 2,048 and 2,304 pairs on held experts in 512 and 520 live
    tiles (an expert's pairs now and then fill a second tile), one step
    without a block, one block before the window."""
    counts = (lambda *n: n) if with_counter else (lambda *n: None)
    steps = [_step(W0 - 4, 8, counts(9999, 999)), _step(W0 + 1, 8, counts(2048, 512)), _step(W0 + 2, 0, counts(0, 0)),
             _step(W0 + 3, 8, counts(2304, 520))]
    traced = {
        "window_s": 6.0, "busy_s": 5.0, "devices": 1,
        "module_s": {"jit__decode_impl": 4.5, "jit__prefill_batch_impl": 0.5},
        "module_runs": {"jit__decode_impl": 13, "jit__prefill_batch_impl": 2},
        "kernel": {"jit__decode_impl": {"seconds": 2.5, "calls": 1700}},
        "kernels": {"jit__decode_impl": {
            "latent_attn.5": {"seconds": 0.1, "calls": 100}, "latent_attn.9": {"seconds": 0.4, "calls": 400},
            "expert_gmm.11": {"seconds": 0.7, "calls": 400}, "expert_gmm.12": {"seconds": 0.7, "calls": 400},
            "expert_gmm.13": {"seconds": 0.6, "calls": 400}},
            "jit__prefill_batch_impl": {"expert_gmm.4": {"seconds": 0.2, "calls": 24}}},
        "counters_before": {"decode_steps": 1000, "decode_context_tokens": 0, "slot_steps_active": 0},
        "counters_after": {"decode_steps": 1200, "decode_context_tokens": 200 * 160_000,
                           "slot_steps_active": 200 * 128},
    }
    trace = {"clock": "monotonic", "now": W1 + 60, "requests": [], "requests_total": 0, "steps": steps,
             "steps_total": 4, "phase_s": {}, "phase_n": {}, "dropped": {"requests": 0, "steps": 0}}
    return {"kind": "serve", "seconds": W1 - W0, "config": _config(), "traffic": {}, "plan": {"loop": "closed"},
            "client": {"w0": W0, "w1": W1, "records": []}, "stats": {"trace": trace},
            "device": {"kind": "TPU v5 lite"}, "traced": traced}


# Worked out by hand. Latent kernel, 100 steps x 5 layers: 16,000,000 positions
# and 12,800 rows a layer; bytes 1,152 a position + 128 heads x 1,088 x 2 a row;
# operations 2 x 128 x 1,088 a position.
LATENT_BYTES = 5 * (1152 * 16_000_000 + 12_800 * 128 * 1088 * 2)  # 109,992,960,000
LATENT_FLOPS = 5 * 2 * 128 * 1088 * 16_000_000  # 22,282,240,000,000
assert LATENT_BYTES / 819e9 > LATENT_FLOPS / 197e12  # the memory roof binds with the rows' traffic counted
# Grouped matmul: 68 pairs a routed layer of a step ((2,048 + 2,304) / (16 steps x 4
# layers)) in 16.125 live tiles ((512 + 520) / 64), 400 routed layers of steps traced; a
# tile reads its expert, 3 x 7,680 x 2,048 = 47,185,920 parameters.
GMM_BYTES = 47_185_920 * 2 * 16.125 * 400 + 68 * 400 * (3 * 7680 + 3 * 2048) * 2
KNOWN = {
    "latent_attn_time_share": 100 * 0.5 / 5.0,
    "expert_gmm_time_share": 100 * 2.0 / 5.0,
    "latent_attn_roofline": 100 * (LATENT_BYTES / 819e9) / 0.5,  # 26.86
    "expert_gmm_roofline": 100 * (GMM_BYTES / 819e9) / 2.0,  # 37.2
    "expert_pairs_per_held_expert": 68 / 16,
}


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_each_reader_on_the_hand_written_record(name):
    got = cellspec.load_metric(name)(Context(_record(), 1))
    assert got == pytest.approx(KNOWN[name], rel=1e-9), name
    assert 0 < got < 100


@pytest.mark.parametrize("name", sorted(KNOWN))
def test_a_reader_finds_nothing_in_a_run_without_what_it_reads(name):
    """Untraced, the traced ones read None; a program whose step records lack
    the counter (the parent) blanks the two that read it, and raises nothing."""
    untraced = dict(_record(), traced=None)
    read = cellspec.load_metric(name)
    if name == "expert_pairs_per_held_expert":
        assert read(Context(untraced, 1)) == pytest.approx(KNOWN[name])
    else:
        assert read(Context(untraced, 1)) is None
    if "expert_gmm_roofline" == name or "pairs" in name:
        assert read(Context(_record(with_counter=False), 1)) is None


def test_every_new_metric_is_in_the_manifest_for_the_new_cell():
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    for name in KNOWN:
        assert by_name[name]["workloads"] == [CELL] and by_name[name]["moves"] == "serve_out_tokens_per_s"
    joined = ["ttft_p50_ms.backlog", "tpot_p50_ms.backlog", "slot_occupancy.backlog", "decode_ms_per_step.backlog",
              "prefill_busy_share.backlog", "engine_host_ms_per_step.backlog", "window_compiles.backlog"]
    assert all(CELL in by_name[name]["workloads"] for name in joined)
    assert CELL not in by_name["paged_attn_time_share.backlog"]["workloads"]  # that reader sums the program's kernels


def test_decode_steps_are_counted_from_the_latent_kernel():
    ctx = Context(_record(), 1)
    assert cellspec.decode_kernels(ctx.config) == {"latent_attn": 5, "expert_gmm": 12}
    assert ctx.traced_decode_steps() == 100
    assert cellspec.load_metric("decode_ms_per_step.backlog")(ctx) == pytest.approx(45.0)


def test_the_counts_match_the_hand_counts():
    """The issue's table: attention 196,575,232; dense FFN 424,673,280; shared
    expert + router 49,152,000; an expert 47,185,920; embedding + head at
    19,200 rows 294,912,000; this chip 4,918,968,320 matmul parameters."""
    arch, model = cellspec.architecture(_config()), _config()
    counts = arch.param_counts(model)
    attn = 7680 * 1536 + 1536 * 128 * 192 + 7680 * 576 + 512 * 128 * 256 + 16384 * 7680
    assert attn == 196_575_232
    dense, routed = attn + 424_673_280, attn + 49_152_000 + 16 * 47_185_920
    assert (dense, routed) == (621_248_512, 1_000_701_952)
    assert counts["embedding"] + counts["lm_head"] == 294_912_000
    assert counts["resident_matmul"] == dense + 4 * routed + 19200 * 7680 == 4_918_968_320 - 19200 * 7680
    # a token multiplies 8 x 16 / 256 = half an expert a routed layer
    assert counts["matmul"] == dense + 4 * (attn + 49_152_000 + 47_185_920 // 2) + 19200 * 7680
    assert counts["total"] == 4_918_968_320 + 5 * (4 * 7680 + 1536 + 512) + 7680
    assert arch.routing(model) == 4 and arch.attention_dims(model) == (5, 128, 128, 192)
    needs = arch.latent_decode_needs(model, context_tokens=1.0, rows=0.0)
    assert needs == {"flops": 2.0 * 128 * 1088, "bytes": 1152.0}  # 242 operations a byte against the chip's 240


def test_the_configuration_keeps_the_published_widths():
    model = _config()
    kw = cellspec.transformer_kwargs(model)
    assert (kw["d_model"], kw["n_heads"], kw["q_lora_rank"], kw["kv_lora_rank"]) == (7680, 128, 1536, 512)
    assert (kw["qk_nope_head_dim"], kw["qk_rope_head_dim"], kw["v_head_dim"]) == (128, 64, 128)
    assert (kw["d_ff"], kw["expert_d_ff"], kw["n_experts"], kw["expert_top_k"]) == (18432, 2048, 256, 8)
    assert (kw["n_layers"], kw["n_dense_layers"], kw["experts_held"], kw["vocab_size"]) == (5, 1, 16, 19200)
    assert kw["routed_scaling"] == 2.5 and kw["norm_eps"] == 1e-5 and kw["sandwich_norm"]
    assert set(model["reduced"]) == set(model["published"]) == set(model["cut"])
