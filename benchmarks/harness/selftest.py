"""The yardstick's own checks: `python3 benchmarks/run.py --selftest`, on the
CPU, in seconds. Kept with the benchmark (this PR may touch nothing else)."""
from __future__ import annotations

import json
import os
import traceback

from harness import flops, refcheck, schedule, xplane
from harness.cellspec import BENCH_DIR, architecture, load_cell, load_metric
from harness.stats import percentile, spread


def _load(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def check_schedule_same_work_every_seed():
    traffic = _load("traffic", "chat.json")
    seen, orders = set(), set()
    for seed in list(range(10)) + [2 ** 31 + 12345]:
        plan = schedule.serve_plan(traffic, seed, 45, 32)
        for phase in ("ramp", "window", "cooldown"):
            rs = [r for r in plan["requests"] if r["phase"] == phase]
            seen.add((phase, len(rs), tuple(sorted(r["prompt_len"] for r in rs)),
                      tuple(sorted(r["out_len"] for r in rs))))
        win = [r for r in plan["requests"] if r["phase"] == "window"]
        orders.add(tuple(r["prompt_len"] for r in win))
        due = [r["due"] for r in win]
        assert due == sorted(due) and plan["ramp_s"] < due[0] and due[-1] < plan["ramp_s"] + 45
        assert len(win) == round(traffic["rate_rps"] * 45)
    assert len(seen) == 3, "the multiset or the count of a phase differs between seeds"
    assert len(orders) == 11, "two seeds gave the same order"
    a = schedule.serve_plan(traffic, 7, 45, 32)
    assert a == schedule.serve_plan(traffic, 7, 45, 32), "the same seed gave another plan"
    assert schedule.prompt_tokens(7, 3, 50, 1000) == schedule.prompt_tokens(7, 3, 50, 1000)


def check_closed_loop_and_train_work():
    plan = schedule.serve_plan(_load("traffic", "backlog.json"), 1, 45, 32)
    assert plan["concurrency"] == 32 and len(plan["requests"]) == 64 * 32
    first, second = plan["requests"][:64], plan["requests"][64:128]
    assert sorted(r["out_len"] for r in first) == sorted(r["out_len"] for r in second)
    traffic = _load("traffic", "pretrain-packed-4k.json")
    rows = schedule.train_rows(traffic)
    assert all(sum(r) <= 4097 for r in rows) and sum(len(r) for r in rows) == 1024
    a, b = schedule.train_arrays(traffic, 0, 32768), schedule.train_arrays(traffic, 1, 32768)
    assert sorted(map(tuple, a["doc_lens"])) == sorted(map(tuple, b["doc_lens"]))
    assert a["doc_lens"] != b["doc_lens"]
    seg, pos, docs = a["segment_ids"][0], a["positions"][0], a["doc_lens"][0]
    assert int((seg > 0).sum()) == sum(docs) and int(pos[docs[0] - 1]) == docs[0] - 1
    assert int(pos[docs[0]]) == 0 or len(docs) == 1
    assert int(schedule.trained_tokens_per_row(a["doc_lens"])[0]) == sum(d - 1 for d in docs)


def check_percentile_and_spread():
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile(range(101), 90) == 90
    assert abs(percentile([10, 20], 90) - 19.0) < 1e-12
    assert percentile([7], 99) == 7
    # statistics.quantiles(n=4) of 1..6: Q1 = 1.75, Q3 = 5.25 (exclusive method)
    assert abs(spread([1, 2, 3, 4, 5, 6]) - 3.5 / 3.5) < 1e-12


def check_flops_against_hand_counts():
    m7 = _load("configs", "mistral-7b-v0.3-l2.json")
    # by hand: a layer is q 4096x4096 + k,v 2x4096x1024 + o 4096x4096 + 3x4096x14336
    layer = 16_777_216 + 8_388_608 + 16_777_216 + 176_160_768
    pc = flops.param_counts(m7)
    assert pc["per_layer_matmul"] == layer == 218_103_808
    assert pc["matmul"] == 2 * layer + 4096 * 32768 == 570_425_344
    assert pc["total"] == 704_663_552
    assert flops.param_counts(dict(m7, num_hidden_layers=32))["total"] == 7_248_023_552
    i2 = _load("configs", "internlm2-1.8b.json")
    assert flops.param_counts(i2)["total"] == 1_889_110_016
    # one document of 4 tokens: 10 causal pairs; 12 * L * H * hd * pairs
    assert flops.causal_pairs([4]) == 10
    assert flops.train_flops(m7, 4, [4]) == 6.0 * 570_425_344 * 4 + 12.0 * 2 * 32 * 128 * 10
    need = flops.paged_decode_needs(i2, context_tokens=1000, rows=10)
    assert need["bytes"] == 2 * 8 * 128 * 2 * 1000 + 2 * 10 * 16 * 128 * 2
    assert need["flops"] == 4.0 * 16 * 128 * 1000
    fl = flops.flash_train_needs(m7, [4])
    assert fl["flops"] == 12.0 * 32 * 128 * 10
    assert fl["bytes"] == 6 * (4 * 32 * 128 * 2) + 6 * (4 * 8 * 128 * 2)
    t, which = flops.roofline_seconds(need, {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9})
    assert which == "memory" and abs(t - need["bytes"] / 819e9) < 1e-18


def _param_counts_before_the_seam(model: dict) -> dict:
    """harness/flops.py's _dims and param_counts as they stood before an
    architecture became a file (PR 23 to PR 25), kept here word for word so
    that the dense file's counts are held to them key by key."""
    d, L = model["hidden_size"], model["num_hidden_layers"]
    H, KV = model["num_attention_heads"], model["num_key_value_heads"]
    hd = model.get("head_dim") or d // H
    F, V = model["intermediate_size"], model["vocab_size"]
    attn = d * H * hd + 2 * d * KV * hd + H * hd * d
    ffn = 3 * d * F
    norms = 2 * d
    head = 0 if model.get("tie_word_embeddings") else d * V
    return {"embedding": V * d, "lm_head": head, "per_layer_matmul": attn + ffn,
            "matmul": L * (attn + ffn) + d * V,  # the head multiplies even when tied
            "total": V * d + head + L * (attn + ffn + norms) + d}


def check_architecture_seam():
    for name in ("mistral-7b-v0.3-l2.json", "internlm2-1.8b.json", "mistral-7b-v0.3.json"):
        model = _load("configs", name)
        assert "architecture" not in model and architecture(model).__name__ == "bench_architecture_dense"
        now = flops.param_counts(model)
        assert now == architecture(model).param_counts(model)
        for key, value in _param_counts_before_the_seam(model).items():
            assert now[key] == value, (name, key)
        assert now["resident_matmul"] == now["matmul"]
    # A second architecture, in which resident and per-token parameters
    # differ: OLMoE-1B-7B's published row, counted by hand.
    moe = _load("selftest_data", "routed_experts_olmoe.json")
    assert architecture(moe).__name__ != architecture(model).__name__
    pc = flops.param_counts(moe)
    attn, expert, router = 4 * 2048 * 2048, 3 * 2048 * 1024, 2048 * 64
    assert (attn, 64 * expert, router) == (16_777_216, 402_653_184, 131_072)
    assert pc["per_layer_matmul"] == attn + 8 * expert + router == 67_239_936
    assert pc["total"] == 6_919_161_856
    assert pc["matmul"] == 16 * 67_239_936 + 2048 * 50304 == 1_178_861_568
    assert pc["resident_matmul"] == 16 * (attn + 64 * expert + router) + 2048 * 50304 == 6_816_006_144
    # 6 operations a multiplied parameter a token; attention over 16 heads of 128
    assert flops.train_flops(moe, 4, [4]) == 6.0 * 1_178_861_568 * 4 + 12.0 * 16 * 16 * 128 * 10
    assert flops.decode_weight_bytes(moe, 2) == 2.0 * 6_816_006_144
    assert flops.paged_decode_needs(moe, 1000, 10)["flops"] == 4.0 * 16 * 128 * 1000


def check_trace_reduction_on_recorded_trace():
    """selftest_data/trace_small.json: a two-device trace in the plain form,
    written by hand around the numbers below (ns). Device 0: module
    jit_step(1) 1000..9000; ops a 1000..3000, kernel 3000..4000 (named as a
    Mosaic call), all-reduce 5000..7000, b 6500..8000 (named by its whole
    HLO text, metadata last). Window 0..10000."""
    out = xplane.reduce(_load("selftest_data", "trace_small.json"))
    assert out["devices"] == 2 and abs(out["window_s"] - 10e-6) < 1e-15
    # device 0 busy: 1000..4000 and 5000..8000 = 6000; device 1: 2000..6000 = 4000
    assert abs(out["busy_s"] - 5e-6) < 1e-15, out["busy_s"]
    # exposed collective on device 0: 5000..6500 = 1500; device 1 has none
    assert abs(out["collective_exposed_s"] - 0.75e-6) < 1e-15, out["collective_exposed_s"]
    assert abs(out["module_s"]["jit_step"] - (8000 + 4000) / 2 * 1e-9) < 1e-15
    assert out["kernel"]["jit_step"]["calls"] == 0.5
    assert abs(out["kernel"]["jit_step"]["seconds"] - 0.5e-6) < 1e-15
    gaps = dict(out["idle_gaps"])
    # device 0 idle: 0..1000 (between steps), 4000..5000 (inside bench.engine.step
    # 3500..5200), 8000..10000 (inside bench.engine.step 7900..9500, llm.step
    # 7920..9480 and, innermost, the program's phase llm.step.decode_fetch 7950..9400)
    assert abs(gaps["inside_engine.step"] - 1e-6) < 1e-15 and abs(gaps["between_steps"] - 1e-6) < 1e-15
    assert abs(gaps["llm.step.decode_fetch"] - 2e-6) < 1e-15 and len(gaps) == 3
    assert out["device_ops"][0][0] == "jit_step/a"
    samples = {key: rest for key, _seconds, *rest in out["op_samples"]}
    assert samples["jit_step/b"][1] == "jit(step)/ffn/mul" and samples["jit_step/a"] == ["a", None]


def check_two_kernels_of_one_program():
    """selftest_data/trace_two_kernels.json (its "what" has the numbers): a
    decode block of 2 steps x 4 layers, the attention kernel in every layer
    and a grouped matmul in every second one; a prefill run with flash."""
    from harness.cellspec import decode_kernels
    from harness.context import Context

    out = xplane.reduce(_load("selftest_data", "trace_two_kernels.json"))
    decode = out["kernels"]["jit__decode_impl"]
    assert set(decode) == {"paged_attn.6", "gmm.9"} and set(out["kernels"]["jit__prefill_batch_impl"]) == {"flash_attn.7"}
    assert (decode["paged_attn.6"]["calls"], decode["gmm.9"]["calls"]) == (8, 4)
    assert abs(decode["paged_attn.6"]["seconds"] - 24e-6) < 1e-15 and abs(decode["gmm.9"]["seconds"] - 16e-6) < 1e-15
    total = out["kernel"]["jit__decode_impl"]  # what the parent read: every Mosaic call of the program
    assert total["calls"] == 12 and abs(total["seconds"] - 40e-6) < 1e-15

    def ctx(config):
        return Context({"kind": "serve", "seconds": 51.0, "config": config, "traffic": {}, "traced": out}, 1)

    declaring = {"architecture": "../selftest_data/two_kernel_decoder", "num_hidden_layers": 4}
    assert decode_kernels(declaring) == {"paged_attn": 4, "gmm": 2} and decode_kernels({"num_hidden_layers": 4}) is None
    assert ctx(declaring).traced_decode_steps() == 2.0   # from the declared kernel's 8 calls, 4 a step
    assert ctx({"num_hidden_layers": 4}).traced_decode_steps() == 3.0  # 12 calls over 4 layers: one kernel a layer assumed
    assert ctx(declaring).kernel_of("_decode_impl", "gmm")["calls"] == 4
    assert ctx(declaring).kernel_of("_decode_impl") == total and ctx(declaring).kernel_of("_decode_impl", "flash") is None
    small = xplane.reduce(_load("selftest_data", "trace_small.json"))
    assert small["kernels"] == {"jit_step": {"closed_call.1 (Mosaic kernel)": small["kernel"]["jit_step"]}}


def check_serve_check_two_tests():
    """judge by hand: 12 positions x 4 logits, the reference all zeros with
    logit 0 raised to 1 (the served token), the coarse reference 0.1 off at
    every position, so that every limit is a round number."""
    import numpy as np

    ref = np.zeros((12, 4), np.float32)
    ref[:, 0] = 1.0
    served, coarse = [0] * 12, ref + 0.1
    quiet, loud = refcheck.LIMITS["routed"]
    assert refcheck.LIMITS["dense"] == (0.4, 0.4) and quiet < 1 < loud
    one_odd = ref + 0.01
    one_odd[5] = ref[5] + 0.1  # one position with ten times the others' error, as large as the coarse one's
    routed, dense = refcheck.judge(ref, one_odd, coarse, served, routing=16), refcheck.judge(ref, one_odd, coarse, served)
    assert routed["ok"] and routed["refused_by"] == [] and routed["position_limit"] == loud
    assert not dense["ok"] and dense["refused_by"] == ["every_position"] and dense["position_limit"] == 0.4
    for verdict in (routed, dense):
        assert abs(verdict["quietest_share_of_coarse"] - 0.1) < 1e-6 and abs(verdict["noise_share_of_coarse"] - 1) < 1e-6
    for routing in (None, 16):  # every position at 1.2 x the coarse error: refused both ways
        all_off = refcheck.judge(ref, ref + 0.12, coarse, served, routing)
        assert not all_off["ok"] and "quietest_position" in all_off["refused_by"], all_off
        assert abs(all_off["quietest_share_of_coarse"] - 1.2) < 1e-6
    # one position beyond what a flip may do: refused with the declaration too
    one_odd[5] = ref[5] + 0.1 * (loud + 0.1)
    assert refcheck.judge(ref, one_odd, coarse, served, routing=16)["refused_by"] == ["every_position"]


def check_manifest_and_files():
    manifest = _load(os.pardir, "BENCHMARK.json")
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for w in manifest["workloads"]:
        spec = load_cell(w["name"])
        assert {m["name"] for m in spec["end_to_end"]} >= {"setup_s"} and len(spec["end_to_end"]) >= 2
        assert spec["per_layer"], w["name"]
        for m in spec["end_to_end"] + spec["per_layer"]:
            load_metric(m["name"])
        for m in spec["per_layer"]:
            assert m["moves"] in {x["name"] for x in spec["end_to_end"]}, (w["name"], m["name"])
    assert all(m["moves"] in e2e for m in manifest["per_layer"])


CHECKS = [check_schedule_same_work_every_seed, check_closed_loop_and_train_work,
          check_percentile_and_spread, check_flops_against_hand_counts, check_architecture_seam,
          check_trace_reduction_on_recorded_trace, check_two_kernels_of_one_program,
          check_serve_check_two_tests, check_manifest_and_files]


def main() -> int:
    failed = 0
    for check in CHECKS:
        try:
            check()
            print(f"ok    {check.__name__}")
        except Exception:
            failed += 1
            print(f"FAIL  {check.__name__}\n{traceback.format_exc()}")
    print(f"selftest: {len(CHECKS) - failed} of {len(CHECKS)} passed")
    return 1 if failed else 0
