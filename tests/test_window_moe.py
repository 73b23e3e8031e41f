"""The decoder with window and full attention layers, a per-head gate and held
experts (models/transformer.py ``layer_pattern``, ``attn_gate``, ``head_dim``,
``n_dense_layers``, ``experts_held``) against its plain reference
(models/reference_window_moe.py), at toy widths on the CPU with seeded random
weights: the forward, the served path through the two kinds of cache (logits,
not tokens), the windowed kernels in interpret mode, YaRN's frequencies, the
shares of an expert-parallel layer, the benchmark's copy of the reference, and
what the engine refuses for a model with window layers."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_window_moe as ref
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, _held_experts_ffn, forward, init_params, make_pipeline_train_step, rope_inv_freq,
)
from ray_tpu.ops.attention import band_blocks, flash_attention, mha_reference
from ray_tpu.ops.paged_attention import paged_attention, ring_pages, window_attention_reference

FULL_ROPE = dict(rope_theta=5e5, rope_type="yarn", factor=128, original_max_position_embeddings=16,
                 beta_slow=1, beta_fast=32, attention_factor=1.4852030263919618, partial_rotary_factor=0.5)
SLIDING_ROPE = dict(rope_type="default", rope_theta=1e4, partial_rotary_factor=1)
WINDOW, PS = 32, 16  # a window of two toy pages: a ring of three
FULL = LayerKind("full_attention", 4, rope_theta=5e5, rope_share=0.5, yarn_factor=128.0, yarn_original_len=16,
                 yarn_beta_fast=32.0, yarn_beta_slow=1.0, attention_factor=1.4852030263919618)
SLIDING = LayerKind("sliding_attention", 6, window=WINDOW, rope_theta=1e4)
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=9, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-6, attention_impl="reference",
    layer_pattern=(FULL, SLIDING, SLIDING, SLIDING), attn_gate="per_head", n_dense_layers=1,
    n_experts=8, expert_top_k=3, experts_held=4, first_expert=2, expert_d_ff=16, n_shared_experts=1,
    routed_scaling=2.5, router_score="sigmoid",
)
MODEL = dict(rms_norm_eps=1e-6, mlp_only_layers=[0], sliding_window=WINDOW,
             layer_types=(["full_attention"] + ["sliding_attention"] * 3) * 2 + ["full_attention"],
             rope_parameters={"full_attention": FULL_ROPE, "sliding_attention": SLIDING_ROPE},
             num_experts_per_tok=3, moe_routed_scaling_factor=2.5)
HELD = (CFG.first_expert, CFG.experts_held)
ENGINE_KW = dict(max_slots=2, max_seq=128, page_size=PS, prefill_buckets=(32, 80), decode_block=4)


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


def test_the_parameter_tree_keeps_each_kinds_layers_in_one_stack():
    params = _params()
    assert params["dense_layers"]["wq"].shape == (1, 32, 4, 16)  # layer 0: full attention, dense FFN
    kinds = params["kind_layers"]
    assert kinds["sliding_attention"]["wq"].shape == (6, 32, 6, 16) and kinds["full_attention"]["wq"].shape == (2, 32, 4, 16)
    assert kinds["sliding_attention"]["wg"].shape == (6, 32, 6) and kinds["full_attention"]["wo"].shape == (2, 4, 16, 32)
    assert kinds["full_attention"]["w_gate"].shape == (2, 4, 32, 16) and "layers" not in params


def test_forward_matches_the_plain_reference():
    """Contexts of more than two windows, so that most of a sliding layer's
    rows are masked by the window and not by the diagonal."""
    params, toks = _params(), jnp.asarray(_tokens(75, batch=2))
    got, _ = forward(params, toks, CFG)
    want = ref.logits(params, toks, MODEL, held=HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=3e-5, rtol=1e-5)


@pytest.fixture
def logits_spy(monkeypatch):
    """Every batch of logits the served path samples from, in order: the
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
    one row, then slot 0's row of every decode step), the tokens, the engine."""
    eng = LLMEngine(cfg, params=params, engine_config=EngineConfig(**{**ENGINE_KW, **engine_kw}))
    del seen[:]
    toks = eng.generate(prompt, max_tokens=n_new)["tokens"]
    jax.effects_barrier()
    rows = [r[0] for r in seen][:n_new]
    return np.stack(rows).astype(np.float32), toks, eng


@pytest.mark.parametrize("n_prompt", [70, 9])
def test_prefill_then_decode_through_both_kinds_of_cache_matches_the_full_forward_f32(n_prompt, logits_spy):
    """17 tokens (the prefill's and 16 decoded) against the reference's full
    forward over prompt + generated tokens: logits, position by position. A
    prompt of 70 is more than two windows of 32 and more than one turn of the
    ring's 48 rows, and decode carries it to 86, which wraps the ring again; a
    prompt of 9 is shorter than the window and grows past it. float32
    throughout: the difference is summation order, 1e-4 of logits of scale ~3."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    got, toks, eng = _served_logits(CFG, params, prompt, 17, logits_spy)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n_prompt - 1:]
    assert got.shape == want.shape == (17, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # a window layer's pools hold a ring a slot and nothing behind it; a full layer's every page
    k_full, _, k_win, _ = eng.cache
    assert k_full.shape == (3, 2, eng.ec.total_pages * PS, 16)
    assert k_win.shape == (6, 2, 2 * ring_pages(WINDOW, PS) * PS, 16) and ring_pages(WINDOW, PS) == 3


@pytest.mark.parametrize("n_prompt,bucket", [(40, 48), (90, 96)])
def test_a_prompt_on_a_rung_between_doublings_leaves_the_ring_of_its_own_length(n_prompt, bucket, logits_spy):
    """Buckets of 3 and of 6 pages, the rungs the engine puts between 32, 64 and
    128: one turn of the ring's 3 pages and two. The prefill's token and 12
    decoded ones against the reference's full forward, so the ring holds the
    window that ends at the prompt's own length, not at the bucket's."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    got, toks, eng = _served_logits(CFG, params, prompt, 13, logits_spy, prefill_buckets=(32, 64))
    assert eng.buckets == (32, 48, 64, 96, 128)
    assert eng.trace_snapshot()["requests"][0]["bucket"] == bucket
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_the_served_geometry_a_window_of_512_over_rings_of_5_pages_of_128(logits_spy):
    """Toy widths, the served cache geometry: a prompt of 2,680 tokens (5
    windows, 4 turns of a ring's 640 rows, so ``_write_ring`` keeps pages
    16..20 in ring pages 1, 2, 3, 4, 0) and 16 decoded tokens, the ninth of
    which opens page 21 in ring page 1, over page 16; a full layer holds
    every page."""
    sliding = dataclasses.replace(SLIDING, window=512)
    cfg = dataclasses.replace(CFG, layer_pattern=(FULL, sliding, sliding, sliding), max_seq_len=2816)
    model = dict(MODEL, sliding_window=512)
    params, prompt = _params(cfg), _tokens(2680, seed=11)
    got, toks, eng = _served_logits(cfg, params, prompt, 17, logits_spy, max_slots=1, max_seq=2816, page_size=128,
                                    prefill_buckets=(2688,), decode_block=8)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, model, held=HELD))[0, 2679:]
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    k_full, _, k_win, _ = eng.cache
    assert ring_pages(512, 128) == 5 and k_win.shape == (6, 2, 5 * 128, 16)
    assert k_full.shape[2] == eng.ec.total_pages * 128


def test_served_bf16_logits_are_within_a_tolerance_8_bit_arithmetic_passes_and_4_bit_fails(logits_spy):
    """bf16 activations and weights (8 bits of mantissa, the implied one
    counted) against the float32 reference, by a position's largest logit
    error: the served path's median position is under TOL of the logits'
    scale and its quietest under half of it (a near-tie between two experts
    that went the other way moves a few positions by a whole expert's output:
    hence the median and the quietest, as the benchmark's check holds its
    quietest position). The reference itself from weights kept in 4 mantissa
    bits is over TOL at its median and at its quietest position."""
    TOL = 0.04
    cfg = dataclasses.replace(CFG, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params())
    prompt = _tokens(70, seed=5)
    got, toks, _ = _served_logits(cfg, params, prompt, 17, logits_spy)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, 69:]
    scale = np.abs(want).max()
    served = np.abs(got - want).max(-1)  # [17], a position's largest error
    assert np.median(served) < TOL * scale and served.min() < TOL * scale / 2, (served, scale)
    coarse = jax.tree.map(lambda a: jax.lax.reduce_precision(a.astype(jnp.float32), 8, 3), params)
    four_bit = np.abs(np.asarray(ref.logits(coarse, full, MODEL, held=HELD))[0, 69:] - want).max(-1)
    assert np.median(four_bit) > TOL * scale and four_bit.min() > TOL * scale / 2, (four_bit, scale)


def test_two_slots_of_unequal_length_in_one_batch_each_match_their_own_forward(logits_spy):
    """A prompt past the window and the ring's turn beside one inside the
    first page, decoded in the same blocks: each slot's rings and pages are
    its own."""
    params = _params()
    prompts = {"long": _tokens(66, seed=1), "short": _tokens(7, seed=2)}
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    for rid, p in prompts.items():
        eng.add_request(rid, p, max_tokens=9)
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    jax.effects_barrier()
    # two prefills of one row each (buckets 80 and 32), then decode steps of two rows
    decode = [r for r in logits_spy if r.shape[0] == 2]
    for slot, rid in enumerate(prompts):
        n = len(prompts[rid])
        full = jnp.asarray([list(prompts[rid]) + done[rid][:-1]])
        want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n:]
        got = np.stack([r[slot] for r in decode[:8]])
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_the_step_record_counts_one_window_layers_walk_and_positions():
    """A prompt of 70 decoded in blocks of 4 steps: at lengths 71..74 (the step's
    own token counted) a window of 32 spans pages 2..4 (positions 39..73:
    3 pages) and attends 32 positions; a full layer walks 5 pages, in two
    grid steps of up to 4. The idle slot is walked by nobody."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    eng.generate(_tokens(70, seed=3), max_tokens=9)
    steps = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    first = steps[0]  # the first block of 4 steps, lengths 71..74, all inside page 4
    assert first["block"] == 4
    assert first["live_pages"] == 4 * 5 and first["grid_steps"] == 4 * -(-5 // eng.rules[0].group)
    assert first["window_pages"] == 4 * 3 and first["window_tokens"] == 4 * 32
    dense = LLMEngine(TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=32),
                      engine_config=EngineConfig(max_slots=2, max_seq=64, page_size=16, prefill_buckets=(32,)))
    dense.generate([1, 2, 3], max_tokens=2)
    assert "window_pages" not in dense.trace_snapshot()["steps"][0]  # absent for a model without a window
    assert set(eng.pool_bytes) == {"full_attention", "sliding_attention"} and set(dense.pool_bytes) == {"layers"}
    assert eng.pool_bytes["sliding_attention"] == 2 * 6 * 2 * (2 * 3 * PS) * 16 * 4


def test_a_block_ahead_a_slots_ring_reused_by_its_next_request_gives_that_requests_own_tokens():
    """One slot, three requests queued, the engine a block ahead: a request
    that has ended rides one more block and writes its slot's ring while the
    next request's prefill waits behind that block on the device; the prefill
    then writes the ring's window anew. A prompt past the window after one
    inside the first page, and the reverse: each returns what it gives alone."""
    params = _params()
    prompts = [_tokens(70, seed=4), _tokens(7, seed=5), _tokens(50, seed=6)]
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    want = [solo.generate(p, max_tokens=10)["tokens"] for p in prompts]
    eos = want[0][5]  # the first request ends inside its second block of 4
    want = [w[: w.index(eos) + 1] if eos in w else w for w in want]
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "max_slots": 1}, eos_id=eos))
    for i, p in enumerate(prompts):
        eng.add_request(f"r{i}", p, 10)
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    assert [done[f"r{i}"] for i in range(3)] == want and want[0][-1] == eos
    steps = eng.trace_snapshot()["steps"]
    assert sum(s["dropped_rows"] for s in steps) >= 1 and any(s["ahead"] for s in steps)
    blocks = [s for s in steps if s["block"]]
    assert all(s["window_pages"] >= s["block"] and s["window_tokens"] > 0 for s in blocks)


# ---------------------------------------------------------------------------
# the kernels, in interpret mode, against jax.numpy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("group,window,S,block", [(6, 128, 512, 128), (9, 128, 512, 128), (6, 200, 512, 128),
                                                  (9, 512, 2048, 512)])  # the last: the served blocks and window
def test_windowed_flash_matches_jax_numpy(group, window, S, block):
    rng = np.random.default_rng(group + window)
    B, KV, D = 1, 2, 128
    q = jnp.asarray(rng.normal(size=(B, S, KV * group, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(B, S, KV, D)), jnp.float32) for _ in range(2))
    seg = jnp.asarray((np.arange(S) >= S - 112).astype(np.int32))[None]  # pads behind a prompt, their own segment
    got = flash_attention(q, k, v, window=window, segment_ids=seg, block_q=block, block_k=block, interpret=True)
    i, j = np.arange(S)[:, None], np.arange(S)[None, :]
    allowed = (j <= i) & (j > i - window) & (np.asarray(seg)[0][:, None] == np.asarray(seg)[0][None, :])
    s = jnp.einsum("bqkgd,btkd->bkgqt", q.reshape(B, S, KV, group, D), k) / np.sqrt(D)
    p = jax.nn.softmax(jnp.where(allowed, s, -jnp.inf), axis=-1)
    want = jnp.einsum("bkgqt,btkd->bqkgd", p, v).reshape(B, S, KV * group, D)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(mha_reference(q, k, v, window=window, segment_ids=seg)), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


def test_a_block_outside_the_band_costs_no_grid_step():
    """At 8192 tokens, blocks of 512 and a window of 512, a q block's band
    lies in 2 k blocks of 16: the compiled call's grid is (heads, 16, 2), and
    (heads, 16, 16) without a window."""
    assert band_blocks(8192, 512, 512, 512) == 2 and band_blocks(8192, 512, 512, 0) == 16
    assert band_blocks(512, 128, 128, 128) == 2 and band_blocks(512, 128, 128, 200) == 3

    def grid(window):
        q = jax.ShapeDtypeStruct((1, 1024, 6, 128), jnp.float32)
        kv = jax.ShapeDtypeStruct((1, 1024, 1, 128), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda q, k, v: flash_attention(
            q, k, v, window=window, block_q=128, block_k=128, interpret=True))(q, kv, kv)
        def calls(jaxpr):
            for eqn in jaxpr.eqns:
                if eqn.primitive.name == "pallas_call":
                    yield eqn
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from calls(sub)

        call, = calls(jaxpr.jaxpr)
        return tuple(call.params["grid_mapping"].grid)

    assert grid(0) == (6, 8, 8) and grid(128) == (6, 8, 2)


def test_windowed_flash_backward_matches_the_reference_gradients():
    rng = np.random.default_rng(3)
    q = jnp.asarray(rng.normal(size=(1, 256, 2, 128)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 256, 1, 128)), jnp.float32) for _ in range(2))
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)
    got = jax.grad(loss(lambda q, k, v: flash_attention(q, k, v, window=100, block_q=128, block_k=128, interpret=True)),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(lambda q, k, v: mha_reference(q, k, v, window=100)), argnums=(0, 1, 2))(q, k, v)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=2e-4, rtol=1e-4)


# (KV heads, page size, window, lengths): a toy ring of 3 pages of 16, and the served geometry (8 KV heads, a ring
# of 5 pages of 128 for a window of 512) at contexts of 5, 1.4 and 0.6 windows: the ring turned 4 times, once, never
TOY, SERVED = (2, 16, 32), (8, 128, 512)


@pytest.mark.parametrize("group", [6, 9])
@pytest.mark.parametrize("geometry,lengths", [(TOY, (5, 40, 150)), (TOY, (32, 33, 97)), (TOY, (1, 48, 49)),
                                              (SERVED, (2601, 701, 300))])
def test_the_window_kernel_matches_jax_numpy_over_rings(group, geometry, lengths):
    """Sequences inside their first page, past the window, and past several
    turns of the ring (3 pages of 16: positions 150 - 32 .. 149 lie in ring
    pages that positions 54 .. 101 held before). The answer is worked from
    the keys by position, with no ring: the ring is filled here the way
    decode fills it, a row a position."""
    rng = np.random.default_rng(sum(lengths) + group)
    (KV, ps, W), B, D, L = geometry, 3, 128, 2
    ring = ring_pages(W, ps)
    n = max(lengths)
    keys = jnp.asarray(rng.normal(size=(B, n, KV, D)), jnp.float32)  # every position's K; V = K / 2 + 1
    pool_k = np.asarray(rng.normal(size=(L, KV, B * ring, ps, D)), np.float32)  # stale rows everywhere
    for b, length in enumerate(lengths):
        # a ring's last turn, but the current token, which the call writes
        for p in range(max(length - 1 - ring * ps, 0), length - 1):
            pool_k[1, :, b * ring + p // ps % ring, p % ps] = np.asarray(keys[b, p])
    pool_k = jnp.asarray(pool_k)
    pool_v = pool_k / 2 + 1
    lens = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(B, KV * group, D)), jnp.float32)
    k_new = jnp.stack([keys[b, length - 1] for b, length in enumerate(lengths)])
    table = jnp.zeros((B, -(-n // ps) + 2), jnp.int32)  # its width alone is read: how many pages a sequence may reach
    got, k2, v2 = paged_attention(q, k_new, k_new / 2 + 1, pool_k, pool_v, lens, table, 1, window=W, interpret=True)
    for b, length in enumerate(lengths):
        seen = keys[b, max(length - W, 0):length]  # [<= W, KV, D]
        s = jnp.einsum("kgd,tkd->kgt", q[b].reshape(KV, group, D), seen) / np.sqrt(D)
        want = jnp.einsum("kgt,tkd->kgd", jax.nn.softmax(s, axis=-1), seen / 2 + 1).reshape(KV * group, D)
        np.testing.assert_allclose(np.asarray(got[b]), np.asarray(want), atol=2e-5, rtol=1e-5)
        at = (1, slice(None), b * ring + (length - 1) // ps % ring, (length - 1) % ps)
        np.testing.assert_array_equal(np.asarray(k2[at]), np.asarray(k_new[b]))
    np.testing.assert_array_equal(np.asarray(k2[0]), np.asarray(pool_k[0]))  # the other layer untouched
    ref_o, ref_k, _ = window_attention_reference(q, k_new, k_new / 2 + 1, pool_k, pool_v, lens, 1, W)
    np.testing.assert_allclose(np.asarray(ref_o), np.asarray(got), atol=2e-5, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ref_k), np.asarray(k2))


def test_yarn_frequencies_against_numbers_worked_by_hand():
    """The published full-attention rope over the roped half of a head, 64
    columns: c(32) = 64 ln(8192 / (64 pi)) / (2 ln 5e5) = 9.04, c(1) = 64
    ln(8192 / (2 pi)) / (2 ln 5e5) = 17.49, so low = 9, high = 18: columns
    0..9 keep e_i = 5e5^(-i/32), columns 18..31 are e_i / 128, and column 12
    is a third of the way: e_12 (2/3 + 1/(3 x 128))."""
    kind = LayerKind("full_attention", 48, rope_theta=5e5, rope_share=0.5, yarn_factor=128.0,
                     yarn_original_len=8192, attention_factor=1.4852030263919618)
    got = rope_inv_freq(kind, 64)
    e = lambda i: 5e5 ** (-i / 32)
    assert got.shape == (32,) and got[0] == 1.0
    np.testing.assert_allclose(got[9], e(9), rtol=1e-12)
    np.testing.assert_allclose(got[9], 0.02495, rtol=1e-3)  # 5e5^(-9/32)
    np.testing.assert_allclose(got[18], e(18) / 128, rtol=1e-12)
    np.testing.assert_allclose(got[12], e(12) * (2 / 3 + 1 / 384), rtol=1e-12)
    np.testing.assert_allclose(got[31], 5e5 ** (-31 / 32) / 128, rtol=1e-12)
    np.testing.assert_allclose(got, ref.inv_freq(dict(FULL_ROPE, original_max_position_embeddings=8192), 128), rtol=1e-12)
    plain = rope_inv_freq(LayerKind("sliding_attention", 72, window=512), 128)
    np.testing.assert_allclose(plain[[0, 1, 63]], [1.0, 1e4 ** (-1 / 64), 1e4 ** (-63 / 64)], rtol=1e-12)


# ---------------------------------------------------------------------------
# the shares of an expert-parallel layer
# ---------------------------------------------------------------------------

def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Four chips with 4 of 16 experts each: the parts of a routed layer's FFN
    the four compute, the shared expert counted once, add up to the uncut
    reference's output (every expert in the tree, held=None)."""
    whole = dataclasses.replace(CFG, n_experts=16, experts_held=16, first_expert=0)
    lp = jax.tree.map(lambda a: a[0], _params(whole)["kind_layers"]["sliding_attention"])
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 11, CFG.d_model)), jnp.float32)
    want = ref.routed_ffn(x, lp, MODEL, held=None)
    shared = ref._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, pairs = jnp.zeros_like(x), 0
    for share in range(4):
        cfg = dataclasses.replace(whole, experts_held=4, first_expert=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = _held_experts_ffn(x, mine, cfg)
        np.testing.assert_allclose(  # a share is the reference's for the experts it holds
            np.asarray(out), np.asarray(ref.routed_ffn(x, mine, MODEL, held=(4 * share, 4))), atol=2e-5, rtol=1e-5)
        total, pairs = total + out - shared, pairs + int(counts[0])
    assert pairs == 2 * 11 * CFG.expert_top_k  # every pair landed on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference
# ---------------------------------------------------------------------------

def _bench_architecture():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "architectures", "laguna.py")
    spec = importlib.util.spec_from_file_location("bench_laguna", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


PUBLISHED = dict(
    MODEL, num_hidden_layers=9, hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2,
    num_attention_heads_per_layer=[4, 6, 6, 6, 4, 6, 6, 6, 4], intermediate_size=48, moe_intermediate_size=16,
    shared_expert_intermediate_size=16, router_experts=8, num_experts=4, first_expert=2, vocab_size=96,
    max_position_embeddings=128, norm_topk_prob=True, gating="per-head", moe_router_logit_softcapping=0,
    moe_apply_router_weight_on_input=False,
    transformer=dict(dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="reference"))


def test_the_benchmarks_copy_and_the_repos_reference_give_equal_logits():
    bench = _bench_architecture()
    params, toks = _params(), jnp.asarray(_tokens(70, batch=2, seed=4))
    np.testing.assert_allclose(np.asarray(bench.logits(params, toks, PUBLISHED)),
                               np.asarray(ref.logits(params, toks, MODEL, held=HELD)), atol=1e-5, rtol=1e-5)


def test_the_benchmarks_key_mapping_builds_this_configuration():
    """The published keys -> the TransformerConfig the tests above run."""
    assert TransformerConfig(**_bench_architecture().transformer_kwargs(PUBLISHED)) == CFG


# ---------------------------------------------------------------------------
# what is refused, each with a message that names the mechanism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kw,message", [
    (dict(prefix_cache=True), "prefix_cache is not written for window layers: a hit copies pages"),
    (dict(chunked_prefill=16), "chunked_prefill is not written for window layers: a chunk attends"),
])
def test_the_engine_refuses_what_a_ring_cannot_restore(engine_kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, **engine_kw))


def test_the_engine_refuses_window_layers_under_tensor_parallelism():
    no_experts = dataclasses.replace(CFG, n_experts=0, experts_held=0, expert_d_ff=0, n_shared_experts=0)
    with pytest.raises(ValueError, match="tensor_parallel > 1 is not written for window layers: a slot's ring"):
        LLMEngine(no_experts, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=2))


def test_a_trailing_partial_period_and_a_pipelined_pattern_are_refused():
    with pytest.raises(ValueError, match="a trailing partial period is not written"):
        dataclasses.replace(CFG, n_layers=8)
    with pytest.raises(ValueError, match="layers of two kinds are two stacks"):
        make_pipeline_train_step(dataclasses.replace(CFG, n_experts=0, experts_held=0, expert_d_ff=0), None, 2)
