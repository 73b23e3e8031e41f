"""The latent-attention, routed-expert decoder (models/transformer.py
``attention_kind="latent"``, ``sandwich_norm``, ``n_dense_layers``,
``experts_held``) against its plain reference
(models/reference_mla_moe.py), at toy widths on the CPU with seeded random
weights: the forward, the served path through the latent pool (logits, not
tokens), a partial prefix hit, the two kernels in interpret mode, the shares
of an expert-parallel layer, and the benchmark's copy of the reference."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_mla_moe as ref
from ray_tpu.models.transformer import TransformerConfig, _held_experts_ffn, forward, init_params
from ray_tpu.ops.grouped_matmul import expert_gmm, expert_gmm_reference, group_rows, plan_rows
from ray_tpu.ops.latent_attention import latent_attention_reference, latent_paged_attention

CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=3, n_heads=4, d_ff=48, max_seq_len=64, rope_theta=1e4,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-5, attention_impl="reference",
    attention_kind="latent", q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, sandwich_norm=True, n_dense_layers=1, n_experts=8, expert_top_k=3,
    experts_held=4, first_expert=2, expert_d_ff=16, n_shared_experts=1, routed_scaling=2.5,
    router_score="sigmoid",
)
MODEL = dict(rms_norm_eps=1e-5, rope_theta=1e4, kv_lora_rank=16, qk_nope_head_dim=8,
             num_experts_per_tok=3, routed_scaling_factor=2.5, sandwich_norm=True)
HELD = (CFG.first_expert, CFG.experts_held)
ENGINE_KW = dict(max_slots=2, max_seq=64, page_size=16, prefill_buckets=(32, 48), decode_block=4)


def _params(cfg=CFG, seed=0):
    """Seeded random weights, the norms' too (init_params makes them ones)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, a):
        if "norm" in jax.tree_util.keystr(path):
            return (a + 0.2 * jax.random.normal(next(keys), a.shape, jnp.float32)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(jitter, params)


def _tokens(n, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=(n,) if batch is None else (batch, n)).astype(np.int32)


def test_forward_matches_the_plain_reference():
    params, toks = _params(), jnp.asarray(_tokens(17, batch=2))
    got, _ = forward(params, toks, CFG)
    want = ref.logits(params, toks, MODEL, held=HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


@pytest.fixture
def logits_spy(monkeypatch):
    """Every row of logits the served path samples from, in order: the
    engine's ``sample_batch`` replaced by one that hands its logits to the
    host and takes the argmax."""
    seen = []

    def spy(logits, temps, top_ps, top_ks, key, cap=None):
        jax.debug.callback(lambda l: seen.append(np.asarray(l)), logits, ordered=True)
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)

    monkeypatch.setattr(engine_mod, "sample_batch", spy)
    return seen


def _served_logits(cfg, params, prompt, n_new, seen, **engine_kw):
    """The logits the served path chose each of n_new tokens from (prefill's
    one row, then slot 0's row of every decode step) and the tokens."""
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(**{**ENGINE_KW, **engine_kw}))
    del seen[:]
    toks = eng.generate(prompt, max_tokens=n_new)["tokens"]
    jax.effects_barrier()
    rows = [r[0] for r in seen][:n_new]
    return np.stack(rows).astype(np.float32), toks


@pytest.mark.parametrize("n_prompt", [9, 31])
def test_prefill_then_decode_through_the_latent_pool_matches_the_full_forward_f32(n_prompt, logits_spy):
    """13 tokens (the prefill's and 12 decoded, absorbed projections over
    the paged latent rows) against the reference's full forward over prompt +
    generated tokens: logits, position by position. float32 throughout: the
    difference is summation order, 1e-4 of logits of scale ~3."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    got, toks = _served_logits(CFG, params, prompt, 13, logits_spy)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n_prompt - 1:]
    assert got.shape == want.shape == (13, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_served_bf16_logits_are_within_a_tolerance_8_bit_arithmetic_passes_and_4_bit_fails(logits_spy):
    """bf16 activations and weights (8 bits of mantissa, the implied one
    counted) against the float32 reference, by a position's largest logit error:
    the served path's median position is under TOL of the logits' scale, and
    so is every position but those where a near-tie between two experts went
    the other way (among 8 experts at toy widths that is a few positions in
    13, each off by a whole expert's output: hence the median and the
    quietest, as the benchmark's check holds its quietest position). The
    reference itself from weights kept in 4 mantissa bits is over TOL at its
    median and at its quietest position."""
    TOL = 0.04
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params())
    prompt = _tokens(20, seed=5)
    got, toks = _served_logits(cfg, params, prompt, 13, logits_spy)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, 19:]
    scale = np.abs(want).max()
    served = np.abs(got - want).max(-1)  # [13], a position's largest error
    assert np.median(served) < TOL * scale and served.min() < TOL * scale / 2, (served, scale)
    coarse = jax.tree.map(lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), 8, 3), params)
    four_bit = np.abs(np.asarray(ref.logits(coarse, full, MODEL, held=HELD))[0, 19:] - want).max(-1)
    assert np.median(four_bit) > TOL * scale and four_bit.min() > TOL * scale / 2, (four_bit, scale)


def test_a_partial_prefix_hit_over_latent_pages_matches_a_cold_prefill(logits_spy):
    """A prompt that extends a cached page-aligned prefix: the matched latent
    pages are copied and only the tail is prefilled, attending the cached rows
    expanded to keys and values; its first token's logits, and the decoded
    ones after, are the cold prefill's."""
    params = _params()
    base, tail_a, tail_b = _tokens(32, seed=1), _tokens(5, seed=2), _tokens(7, seed=3)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW, prefix_cache=True))
    eng.generate(list(base) + list(tail_a), max_tokens=2)
    del logits_spy[:]
    prompt = list(base) + list(tail_b)
    warm = eng.generate(prompt, max_tokens=6)["tokens"]
    jax.effects_barrier()
    warm_logits = np.stack([r[0] for r in logits_spy][:6])
    assert eng.prefix_partial_hits == 1
    cold_logits, cold = _served_logits(CFG, params, prompt, 6, logits_spy)
    assert warm == cold
    np.testing.assert_allclose(warm_logits, cold_logits, atol=1e-4, rtol=1e-4)


def test_the_engine_refuses_experts_it_is_not_told_it_holds():
    cfg = TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=32, n_experts=4)
    with pytest.raises(ValueError, match="which experts this chip holds"):
        LLMEngine(cfg)
    with pytest.raises(ValueError, match="tensor_parallel"):
        LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=2))


def test_decode_counts_pairs_on_held_experts_in_the_step_record():
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    eng.generate(_tokens(9), max_tokens=6)
    steps = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    assert steps
    for s in steps:
        # 2 slots x 3 choices x 2 routed layers a step at most; an expert that is hit is one live tile
        assert 0 <= s["expert_pairs"] <= s["block"] * 2 * 3 * 2
        assert -(-s["expert_pairs"] // 2) <= s["expert_tiles"] <= min(s["expert_pairs"], s["block"] * 2 * 4)
    assert any(s["expert_pairs"] for s in steps)


def test_a_block_ahead_the_counts_ride_the_fetch_a_step_later_into_the_dispatching_steps_record():
    """The engine fetches a block's tokens, and with them the routed pairs and
    live tiles, one step after it dispatched the block: the counts land in the
    record of the step that dispatched it (the ring holds that dict), block
    for block what the program returned, and three requests through two
    slots return what each gives alone."""
    params = _params()
    prompts = [_tokens(n, seed=n) for n in (9, 20, 5)]
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    want = [solo.generate(p, max_tokens=11)["tokens"] for p in prompts]
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    decode, returned = eng._decode_jit, []

    def spy(*args):
        out = decode(*args)
        returned.append((args[6], out[4]))  # (n_steps, counts, still on the device)
        return out

    eng._decode_jit = spy
    for i, p in enumerate(prompts):
        eng.add_request(f"r{i}", p, 11)
    done, seen_late = {}, 0
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
        last = eng.trace_snapshot()["steps"][-1]
        # the step that has just ended dispatched a block: its counts are still on the device
        seen_late += bool(last["block"] and eng._inflight is not None and last["expert_pairs"] == 0)
    assert [done[f"r{i}"] for i in range(3)] == want
    blocks = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    assert len(blocks) == len(returned) > 3 and seen_late > 0
    assert [(s["block"], s["expert_pairs"], s["expert_tiles"]) for s in blocks] == [
        (n, int(counts[0]), int(counts[1])) for n, counts in returned]
    assert all(s["expert_pairs"] > 0 for s in blocks) and any(s["ahead"] for s in blocks)


# ---------------------------------------------------------------------------
# the kernels, in interpret mode, against their jax.numpy references
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("lengths", [(5, 33, 1), (16, 17, 64), (1, 1, 48)])
def test_latent_kernel_matches_its_reference(lengths):
    rng = np.random.default_rng(sum(lengths))
    B, H, W, V, ps, L, P_total = 3, 8, 128, 64, 16, 2, 14
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(L, P_total, ps, W)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 8], [9, 10, 11, 12]], jnp.int32)
    table = jnp.where(jnp.arange(4)[None, :] * ps < jnp.asarray(lengths)[:, None], table, 0)  # dead entries name page 0
    args = (q, row, pool, jnp.asarray(lengths, jnp.int32), table, 1)
    want, pool_want = latent_attention_reference(*args, v_width=V, scale=0.17)
    got, pool_got = latent_paged_attention(*args, v_width=V, scale=0.17, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool_got), np.asarray(pool_want))
    # the other layer untouched, the step's row where it belongs
    np.testing.assert_array_equal(np.asarray(pool_got[0]), np.asarray(pool[0]))
    for b, n in enumerate(lengths):
        page, at = int(table[b, (n - 1) // ps]), (n - 1) % ps
        np.testing.assert_array_equal(np.asarray(pool_got[1, page, at]), np.asarray(row[b]))


LOADS = {
    "even": [[0, 1, 2], [3, 0, 1], [2, 3, 0], [1, 2, 3]],
    "one expert takes nearly all": [[1, 9, 8]] * 11 + [[0, 1, 9]],
    "an expert with no token": [[0, 1, 8], [1, 3, 9], [3, 0, 8], [0, 3, 1], [3, 1, 9]],
    "nothing lands here": [[8, 9, 10]] * 4,
}


@pytest.mark.parametrize("load", sorted(LOADS))
def test_grouped_matmul_matches_its_reference_and_the_plain_products(load):
    """first = 0, four experts held of a dozen scored (ids 8.. are absent)."""
    rng = np.random.default_rng(len(load))
    experts = jnp.asarray(LOADS[load], jnp.int32)
    T, K = experts.shape
    E, tm, D, N = 4, 8, 32, 48
    plan = group_rows(experts, 0, E, tm)
    assert plan.token_of_row.shape == (plan_rows(T * K, E, tm),)
    sizes = [int((np.asarray(experts) == e).sum()) for e in range(E)]
    assert list(np.asarray(plan.sizes)) == sizes
    assert int(plan.n_tiles[0]) == sum(-(-s // tm) for s in sizes)
    x = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    stack = jnp.asarray(rng.normal(size=(3, E, D, N)), jnp.float32)  # three layers' experts; the second is used
    w = stack[1]
    xs = x[plan.token_of_row]
    want = expert_gmm_reference(xs, stack, 1, plan.tile_expert, plan.n_tiles, tm=tm)
    got = expert_gmm(xs, stack, 1, plan.tile_expert, plan.n_tiles, tm=tm, interpret=True)
    live = int(plan.n_tiles[0]) * tm
    np.testing.assert_allclose(np.asarray(got)[:live], np.asarray(want)[:live], atol=1e-4, rtol=1e-5)
    for t in range(T):
        for k in range(K):
            e = int(experts[t, k])
            assert bool(plan.held[t, k]) == (e < E)
            if e < E:  # no pair dropped: each has a row of its own with its product
                np.testing.assert_allclose(
                    np.asarray(want[plan.row_of_pair[t, k]]), np.asarray(x[t] @ w[e]), atol=1e-4, rtol=1e-5)
    rows = [int(plan.row_of_pair[t, k]) for t in range(T) for k in range(K) if bool(plan.held[t, k])]
    assert len(set(rows)) == len(rows)


# ---------------------------------------------------------------------------
# the shares of an expert-parallel layer
# ---------------------------------------------------------------------------

def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Four chips with 2 of 8 experts each: the parts of a routed layer's FFN
    the four compute, the shared expert counted once, add up to the uncut
    reference's output (every expert in the tree, held=None)."""
    whole = dataclasses.replace(CFG, experts_held=8, first_expert=0)
    lp = jax.tree.map(lambda a: a[0], _params(whole)["layers"])
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 11, CFG.d_model)), jnp.float32)
    want = ref.routed_ffn(x, lp, MODEL, held=None)
    shared = ref._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, pairs = jnp.zeros_like(x), 0
    for share in range(4):
        cfg = dataclasses.replace(CFG, experts_held=2, first_expert=2 * share)
        mine = {**lp, **{k: lp[k][2 * share:2 * share + 2] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = _held_experts_ffn(x, mine, cfg)
        np.testing.assert_allclose(  # a share is the reference's for the experts it holds
            np.asarray(out), np.asarray(ref.routed_ffn(x, mine, MODEL, held=(2 * share, 2))), atol=2e-5, rtol=1e-5)
        total, pairs = total + out - shared, pairs + int(counts[0])
    assert pairs == 2 * 11 * CFG.expert_top_k  # every pair landed on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference
# ---------------------------------------------------------------------------

def test_the_benchmarks_copy_and_the_repos_reference_give_equal_logits():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "architectures", "pangu_ultra_moe.py")
    spec = importlib.util.spec_from_file_location("bench_pangu_ultra_moe", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    published = dict(MODEL, num_hidden_layers=3, first_k_dense_replace=1, n_routed_experts=4, first_expert=2,
                     moe_intermediate_size=16)
    params, toks = _params(), jnp.asarray(_tokens(15, batch=2, seed=4))
    np.testing.assert_allclose(np.asarray(bench.logits(params, toks, published)),
                               np.asarray(ref.logits(params, toks, MODEL, held=HELD)), atol=1e-5, rtol=1e-5)
