"""The decoder with ONE latent-attention layer in four beside gated-delta-rule
layers (models/transformer.py ``LayerKind(mixer="latent")`` beside
``LayerKind(mixer="delta", n_key_heads=.., low_rank=0)``, an elementwise gate on
the latent layer, norms whose scale is ``2 sigmoid(w)`` before and after every
sublayer, a clamp inside SwiGLU, a leading dense layer of the delta kind and
held experts) against its plain reference
(models/reference_latent_delta_moe.py), at toy widths on the CPU with seeded
random weights: the forward with each of the gate, the clamp and the norm on
and off, the served path through BOTH caches (a state and a tail a slot beside
latent rows in pages; logits, not tokens), the four slot cases, the delta mixer
a token at a time, a decay a head through the kernels' ``jax.numpy`` forms,
YaRN's frequencies and the softmax scale, the shares of an expert-parallel
layer, the two cache rules side by side, the step record's counters, the
benchmark's copy of the reference, and what the pair refuses."""
import dataclasses
import importlib.util
import itertools
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import cache_rules
from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_latent_delta_moe as ref
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, _delta_mixer, _held_experts_ffn, cross_entropy_loss, forward, init_params,
    latent_scale, param_logical_axes, rope_inv_freq, slot_state_shapes,
)
from ray_tpu.ops.linear_attention import kda_chunk_reference, kda_scan_reference, kda_step_reference
from ray_tpu.ops.paged_attention import group_pages

PS = 16
SCALING = dict(factor=8.0, original_max_position_embeddings=32, beta_fast=32, beta_slow=1, mscale=1, mscale_all_dim=1)
M = 0.1 * math.log(8.0) + 1.0  # the softmax scale's factor is its square
MLA = LayerKind("latent", 4, mixer="latent", rope_theta=1e4, yarn_factor=8.0, yarn_original_len=32,
                yarn_beta_fast=32.0, yarn_beta_slow=1.0, softmax_factor=M * M)
GDN = LayerKind("delta", 4, mixer="delta", conv_size=4, n_key_heads=2, gate_scale=2.0)
LIMIT = 0.5  # the published 10 never binds at toy widths: a limit that binds for a share of columns
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=5, n_heads=4, head_dim=16, d_ff=48, max_seq_len=128,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-6, attention_impl="reference",
    layer_pattern=(GDN, MLA, GDN, GDN), n_dense_layers=1, attn_gate="elementwise", sandwich_norm=True,
    norm_gating=2.0, swiglu_limit=LIMIT,
    q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
    n_experts=8, expert_top_k=3, experts_held=4, first_expert=2, expert_d_ff=16, n_shared_experts=1,
    routed_scaling=2.5, router_score="sigmoid",
)
MODEL = dict(
    rms_norm_eps=1e-6, num_hidden_layers=5, first_k_dense_replace=1, full_attention_layers=[1],
    kv_lora_rank=16, qk_nope_head_dim=8, rope_theta=1e4, rope_scaling=SCALING, use_mla_scaling_factor=True,
    gated_attention=True, layernorm_gating_weight=2, swiglu_limit=LIMIT, linear_sigmoid_gate_scale=2,
    linear_attn_o_norm_eps=1e-6, num_experts_per_tok=3, routed_scaling_factor=2.5)
HELD = (CFG.first_expert, CFG.experts_held)
ENGINE_KW = dict(max_slots=3, max_seq=128, page_size=PS, prefill_buckets=(32, 80), decode_block=4)


def _deep(cfg=CFG, model=MODEL):
    """2 + 8 layers: two leading dense layers of the delta kind, then two periods."""
    return (dataclasses.replace(cfg, n_layers=10, n_dense_layers=2, layer_pattern=(GDN, GDN, MLA, GDN)),
            dict(model, num_hidden_layers=10, first_k_dense_replace=2, full_attention_layers=[2, 6]))


def _params(cfg=CFG, seed=0):
    """Seeded random weights; a delta layer's head norm too (init_params makes it ones), and the other norms'
    where the model does not gate them (gated ones are drawn N(0, 1/4) by init_params)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 96))

    def jitter(path, a):
        name = jax.tree_util.keystr(path)
        if "norm" in name and ("o_norm" in name or not cfg.norm_gating):
            return (a + 0.2 * jax.random.normal(next(keys), a.shape, jnp.float32)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(jitter, params)


def _tokens(n, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=(n,) if batch is None else (batch, n)).astype(np.int32)


def test_the_parameter_tree_has_a_dense_delta_layer_one_latent_stack_and_one_delta_stack():
    params = _params()
    assert set(params["kind_layers"]) == {"latent", "delta"} and "layers" not in params
    dense, latent, delta = params["dense_layers"], params["kind_layers"]["latent"], params["kind_layers"]["delta"]
    assert dense["wq"].shape == dense["wk"].shape == (1, 32, 2, 16) and dense["wv"].shape == (1, 32, 4, 16)
    assert dense["w_gate"].shape == (1, 32, 48) and "router" not in dense and "wq_a" not in dense
    assert delta["wq"].shape == (3, 32, 2, 16) and delta["wv"].shape == delta["wz"].shape == (3, 32, 4, 16)
    assert delta["conv"].shape == (3, 4, 2 * 2 + 4, 16)  # q's, k's and v's heads along one axis
    assert delta["wa"].shape == delta["wb"].shape == (3, 32, 4) and delta["dt_bias"].shape == delta["a_log"].shape == (3, 4)
    assert not {"wf_a", "wf_b", "wg_a", "wg_b", "wg"} & set(delta) and delta["w_gate"].shape == (3, 4, 32, 16)
    assert latent["wq_a"].shape == (1, 32, 24) and latent["wq_b"].shape == (1, 24, 4, 16)
    assert latent["wkv_a"].shape == (1, 32, 24) and latent["wg"].shape == (1, 32, 4, 8)  # the gate, over v_head_dim
    assert latent["post_attn_norm"].shape == (1, 32) and delta["post_ffn_norm"].shape == (3, 32)  # the sandwich
    assert float(jnp.std(latent["attn_norm"])) > 0.3 and float(jnp.std(params["final_norm"])) > 0.3  # N(0, 1/4)
    assert (np.asarray(delta["o_norm"]) != 1).any()
    axes = param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)
    assert slot_state_shapes(CFG, GDN) == ((4, 16, 16), (3, 8, 16))  # the tail follows the convolution's channels


def _switched(gate: bool, clamp: bool, gated_norm: bool):
    cfg = dataclasses.replace(CFG, attn_gate="elementwise" if gate else "", swiglu_limit=LIMIT if clamp else 0.0,
                              norm_gating=2.0 if gated_norm else 0.0)
    model = dict(MODEL, gated_attention=gate, swiglu_limit=LIMIT if clamp else 0, layernorm_gating_weight=2 if gated_norm else 0)
    return cfg, model


@pytest.mark.parametrize("gate,clamp,gated_norm", list(itertools.product([True, False], repeat=3)))
def test_forward_matches_the_plain_reference_with_each_switch_on_and_off(gate, clamp, gated_norm):
    """1 + 4 layers, two rows of 70 positions (more than one chunk of the
    chunked form against the reference's scan over positions). float32 on both
    sides: logits of order 1 differ by 3e-5 .. 2.3e-4 over the eight cases, float32's
    rounding (the chunked form against the scan is two thirds of it, and a norm
    AFTER each sublayer hands a small output's rounding on at full size); a state
    or a decay in bfloat16 is off by 1e-2."""
    cfg, model = _switched(gate, clamp, gated_norm)
    params, toks = _params(cfg), jnp.asarray(_tokens(70, batch=2))
    got, _ = forward(params, toks, cfg)
    want = ref.logits(params, toks, model, held=HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-4, rtol=1e-5)
    # and the switch is no dead letter: the reference with it flipped is far from the program
    for key, flipped in (("gated_attention", not gate), ("swiglu_limit", 0 if clamp else LIMIT),
                         ("layernorm_gating_weight", 0 if gated_norm else 2)):
        if key == "gated_attention" and not gate:
            continue  # no gate weights in the tree to turn on
        other = ref.logits(params, toks, dict(model, **{key: flipped}), held=HELD)
        assert float(jnp.max(jnp.abs(other - want))) > 1e-2, key


def test_forward_matches_the_plain_reference_at_two_dense_layers_and_two_periods():
    """2 + 8 layers: the dense stack two deep, the period starting on another
    kind, the latent stack two deep."""
    cfg, model = _deep()
    params, toks = _params(cfg), jnp.asarray(_tokens(70, batch=2, seed=3))
    assert params["dense_layers"]["wq"].shape[0] == 2 and params["kind_layers"]["latent"]["wq_a"].shape[0] == 2
    assert params["kind_layers"]["delta"]["wq"].shape[0] == 6
    got, _ = forward(params, toks, cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(params, toks, model, held=HELD)),
                               atol=1e-3, rtol=1e-5)  # twice the layers' rounding


def test_the_clamp_binds_for_a_share_of_the_dense_layers_columns():
    """The limit the tests run with cuts a real share of both branches of the
    leading layer's SwiGLU, neither none nor all of them."""
    params, toks = _params(), jnp.asarray(_tokens(70, batch=2))
    lp = {k: v[0] for k, v in params["dense_layers"].items()}
    norm = lambda x, w: ref._norm(x, w, 1e-6, 2.0)
    x = params["embed"][toks]
    x = x + norm(ref.delta_attention(norm(x, lp["attn_norm"]), lp, MODEL)[0], lp["post_attn_norm"])
    h = norm(x, lp["ffn_norm"])
    over = float(jnp.mean(h @ lp["w_gate"] > LIMIT)), float(jnp.mean(jnp.abs(h @ lp["w_up"]) > LIMIT))
    assert all(0.05 < share < 0.9 for share in over), over  # 30% of the gate, 63% of the other branch


def test_a_packed_batch_is_refused_loudly():
    batch = {"tokens": jnp.asarray(_tokens(17, batch=1)), "segment_ids": jnp.zeros((1, 17), jnp.int32)}
    with pytest.raises(NotImplementedError, match="packed sequences are not written for a delta layer.*ROADMAP M4"):
        cross_entropy_loss(_params(), batch, CFG)


# ---------------------------------------------------------------------------
# the served path: a state and a tail a slot beside latent rows in pages
# ---------------------------------------------------------------------------

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


def _run(eng):
    done = {}
    while eng.has_work():
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
    jax.effects_barrier()
    return done


def _want(params, prompt, tokens, model=MODEL):
    """The reference's logits at the positions the served tokens were chosen from."""
    full = jnp.asarray([list(prompt) + list(tokens[:-1])])
    return np.asarray(ref.logits(params, full, model, held=HELD))[0, len(prompt) - 1:]


@pytest.mark.parametrize("n_prompt", [70, 1, 32])
def test_prefill_then_decode_through_both_caches_matches_the_full_forward_f32(n_prompt, logits_spy):
    """21 tokens (the prefill's and 20 decoded) against the reference's full
    forward over prompt + generated tokens: logits, position by position. A
    prompt of 70 is padded to a bucket of 80 (padding the state must not see);
    one of ONE token is shorter than the convolution's reach and leaves a tail
    of zeros before it; one of 32 ends on its bucket's last position. float32
    throughout: 5e-4 holds float32's rounding (the forward's, above) over 20
    recurrent steps, and a state kept in bfloat16 (three decimal digits) fails it
    at the first."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    toks = eng.generate(prompt, max_tokens=21)["tokens"]
    jax.effects_barrier()
    assert eng.trace_snapshot()["requests"][0]["bucket"] == {70: 80, 1: 32, 32: 32}[n_prompt]
    got = np.stack([r[0] for r in logits_spy][:21]).astype(np.float32)
    want = _want(params, prompt, toks)
    assert got.shape == want.shape == (21, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=5e-4, rtol=1e-4)
    # the delta kind's pools are a state and a tail a slot, FOUR layers deep (the dense one among them);
    # the latent kind's is one pool of rows in pages, ONE layer deep
    state, tails, rows = eng.cache
    assert state.shape == (4, 3, 4, 16, 16) and state.dtype == jnp.float32 and tails.shape == (4, 3, 3, 8, 16)
    assert rows.shape == (1, eng.ec.total_pages * PS, 128) and [r.name for r in eng.rules] == ["delta", "latent"]
    assert eng.pool_bytes == {"delta": state.nbytes + tails.nbytes, "latent": rows.nbytes}


def test_requests_of_unequal_length_share_decode_blocks_and_a_later_one_takes_a_left_slot(logits_spy):
    """Three slots, four requests in one batch: prompts of 66, 7 and 1 tokens
    decode in the same blocks, each on its own state, tail and pages; the
    shortest budget ends first and the fourth request is admitted into the slot
    it left, whose state and tail its prefill replaces and whose pages are new.
    Every request's decoded logits are its own full forward's."""
    params = _params()
    prompts = {"long": _tokens(66, seed=1), "short": _tokens(7, seed=2), "one": _tokens(1, seed=5), "next": _tokens(40, seed=3)}
    budget = {"long": 26, "short": 9, "one": 14, "next": 12}
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    lives = {rid: eng.add_request(rid, p, max_tokens=budget[rid]) for rid, p in prompts.items()}
    done = _run(eng)
    slot = {rid: life["slot"] for rid, life in lives.items()}
    assert slot["next"] == slot["short"] and len({slot[r] for r in ("long", "short", "one")}) == 3
    decode = [r for r in logits_spy if r.shape[0] == 3]  # a decode step's rows: every slot's
    for rid in ("long", "short", "one"):
        got = np.stack([r[slot[rid]] for r in decode[:budget[rid] - 1]])
        np.testing.assert_allclose(got, _want(params, prompts[rid], done[rid])[1:], atol=5e-4, rtol=1e-4)
    # the fourth request's tokens are what it gives alone (greedy, float32), and the reference's own
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    assert done["next"] == solo.generate(prompts["next"], max_tokens=budget["next"])["tokens"]
    assert [int(t) for t in np.argmax(_want(params, prompts["next"], done["next"]), axis=-1)] == done["next"]


def test_a_prefill_leaves_the_reference_s_state_tail_and_latent_rows_whatever_its_bucket():
    """A prompt of 23 through a bucket of 32 and one of 80, into slot 1 of a
    fresh engine: the state and the tail of every delta layer and the latent
    layer's rows are the reference's after 23 positions, and slot 0's state
    and tail and every page but the request's stay zeros."""
    params, prompt = _params(), _tokens(23, seed=9)
    ints = lambda *x: jnp.asarray(x, jnp.int32)
    # the reference's, layer by layer, from the reference's own hidden states
    norm = lambda x, w: ref._norm(x, w, 1e-6, 2.0)
    x = params["embed"][jnp.asarray(prompt)[None]]
    positions, allowed = jnp.arange(23)[None], jnp.tril(jnp.ones((23, 23), bool))[None]
    states, tails, rows = [], [], []
    for kind, lp, routed in ref.layers(params, MODEL):
        u = norm(x, lp["attn_norm"])
        if kind == ref.DELTA:
            a, s = ref.delta_attention(u, lp, MODEL)
            states.append(s[0])
            tails.append(ref.delta_projections(u, lp)[0, 20:23])
        else:
            a = ref.latent_attention(u, lp, MODEL, positions, allowed)
            ckr = u @ lp["wkv_a"]
            freq = ref.yarn_inv_freq(8, 1e4, SCALING)
            rows.append(jnp.concatenate([norm(ckr[..., :16], lp["kv_norm"]), ref._rope(ckr[..., 16:], positions, freq)], -1)[0])
        x = x + norm(a, lp["post_attn_norm"])
        h = norm(x, lp["ffn_norm"])
        f = ref.routed_ffn(h, lp, MODEL, HELD) if routed else ref._swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"], LIMIT)
        x = x + norm(f, lp["post_ffn_norm"])
    for bucket in (32, 80):
        eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (bucket,)}))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :23] = prompt
        pages = np.zeros((1, bucket // PS), np.int32)
        pages[0, :2] = (5, 3)
        cache, _ = eng._prefill(bucket, 1)(
            eng.params, eng.cache, jnp.asarray(padded), ints(23), jnp.asarray(pages), jax.random.PRNGKey(0),
            jnp.zeros(1), jnp.ones(1), ints(0), ints(1))  # into slot 1
        state, tail, pool = (np.asarray(a) for a in cache)
        assert not state[:, (0, 2)].any() and not tail[:, (0, 2)].any()  # the other slots were not written
        np.testing.assert_allclose(state[:, 1], np.stack(states), atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(tail[:, 1], np.stack(tails), atol=2e-5, rtol=1e-4)
        want = np.asarray(rows[0])
        np.testing.assert_allclose(pool[0, 5 * PS:6 * PS, :24], want[:16], atol=2e-5, rtol=1e-4)
        np.testing.assert_allclose(pool[0, 3 * PS:3 * PS + 7, :24], want[16:], atol=2e-5, rtol=1e-4)
        assert not pool[0, :, 24:].any()  # the row's padding columns
        untouched = np.ones(pool.shape[1], bool)
        untouched[[*range(5 * PS, 6 * PS), *range(3 * PS, 4 * PS), *range(PS)]] = False  # its pages and the dead sink
        assert not pool[0, untouched].any()


def test_an_empty_slots_state_tail_and_pages_are_bit_for_bit_what_they_were_after_decode_blocks():
    """Slots 1 and 2 never hold a request: decode blocks on slot 0 leave their
    state and tail (set to a pattern first) and every page slot 0 does not own
    bit for bit, and the step record counts one rewritten state a step."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    state, tails, rows = eng.cache
    marked = (state.at[:, 1:].set(jnp.arange(16, dtype=jnp.float32)), tails.at[:, 1:].set(0.5))
    eng.cache = (*marked, rows.at[:, 9 * PS:].set(0.25))  # pages the one request will not be given
    want = [np.asarray(a[:, 1:]) for a in marked]
    eng.generate(_tokens(20, seed=4), max_tokens=13)
    for got, a in zip(eng.cache[:2], want):
        assert (np.asarray(got[:, 1:]) == a).all()
    assert np.asarray(eng.cache[0][:, 0]).any()  # slot 0's moved
    pool = np.asarray(eng.cache[2])
    assert (pool[:, 9 * PS:] == 0.25).all() and pool[0, PS:4 * PS, :24].any()
    steps = eng.trace_snapshot()["steps"]
    blocks = [s for s in steps if s["block"]]
    assert blocks and all(s["state_rows"] == s["block"] * s["active"] == s["block"] for s in blocks)
    assert sum(s["n_prefill"] for s in steps) == 1


def test_the_step_records_carry_both_rules_counters_together():
    """One record a step holds the state rule's count (state_rows), the latent walk's (live_pages, grid_steps) and the held
    experts' (expert_pairs, expert_tiles), each under the name it has."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    eng.add_request("a", _tokens(40, seed=1), max_tokens=9)
    eng.add_request("b", _tokens(3, seed=2), max_tokens=9)
    _run(eng)
    steps = eng.trace_snapshot()["steps"]
    keys = {"state_rows", "live_pages", "grid_steps", "expert_pairs", "expert_tiles"}
    assert all(keys <= set(s) for s in steps)
    block = next(s for s in steps if s["block"] and s["active"] == 2)
    n = block["block"]
    assert block["state_rows"] == 2 * n  # a live slot a step, whatever its kind's four layers
    # ONE latent layer's walk: a slot's ceil(length / page) pages at each step, each in one grid step here
    assert block["live_pages"] >= 2 * n and block["grid_steps"] == 2 * n
    assert 0 < block["expert_pairs"] <= 4 * n * 3 * CFG.expert_top_k and block["expert_tiles"] > 0
    assert sum(s["n_prefill"] for s in steps) == 2
    assert not {"window_pages", "tail_rows"} & set(steps[0])  # no rule of this model counts those


# ---------------------------------------------------------------------------
# the delta mixer and the kernels' jax.numpy forms, a decay a head
# ---------------------------------------------------------------------------

def _delta_layer(seed=0):
    lp = jax.tree.map(lambda a: a[1], _params(seed=seed)["kind_layers"]["delta"])
    u = jnp.asarray(np.random.default_rng(seed).normal(size=(2, 37, CFG.d_model)), jnp.float32)
    return lp, u


def test_the_delta_mixer_a_token_at_a_time_from_a_state_and_a_tail_equals_the_mixer_over_the_sequence():
    lp, u = _delta_layer()
    whole, _ = _delta_mixer(u, lp, CFG, GDN, (None, lambda ops, window: (kda_scan_reference(*ops)[0], None)))
    pool = jnp.zeros((1, 2, *slot_state_shapes(CFG, GDN)[0]), jnp.float32)
    tail, live, outs = jnp.zeros((2, 3, 8, 16), jnp.float32), jnp.ones(2, bool), []
    for t in range(u.shape[1]):
        def rule(ops, window):
            o, new = kda_step_reference(*(a[:, 0] for a in ops), pool, 0, live)
            return o[:, None], (new, window[:, 1:])
        o, (pool, tail) = _delta_mixer(u[:, t:t + 1], lp, CFG, GDN, (tail, rule))
        outs.append(o)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(outs, axis=1)), np.asarray(whole), atol=2e-5, rtol=1e-4)
    # and both are the reference's mixer before its output projection
    want, s = ref.delta_rule(*ref.delta_inputs(u, lp))
    np.testing.assert_allclose(np.asarray(pool[0]), np.asarray(s), atol=2e-5, rtol=1e-4)
    gate = 2.0 * jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", u, lp["wz"]))
    np.testing.assert_allclose(np.asarray(whole), np.asarray(ref._norm(want, lp["o_norm"], 1e-6) * gate), atol=2e-5, rtol=1e-4)


def test_a_decay_a_head_through_the_kernels_jax_numpy_forms_equals_the_scan_with_a_scalar_decay():
    """What the mixer hands the rule for a decay a HEAD (the scalar repeated
    over a head's key channels) gives, through the chunked form and through
    the one-token form, what the plain scan gives with the scalar itself."""
    lp, u = _delta_layer(seed=3)
    q, k, v, g, beta = ref.delta_inputs(u[:, :, :], lp)
    want, s_want = ref.delta_rule(q, k, v, g, beta)
    wide = jnp.broadcast_to(g[..., None], q.shape)
    got, s = kda_chunk_reference(q, k, v, wide, beta)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_want), atol=2e-5, rtol=1e-4)
    pool, live = jnp.zeros((1, *s.shape), jnp.float32), jnp.ones(2, bool)
    for t in range(q.shape[1]):
        o, pool = kda_step_reference(q[:, t], k[:, t], v[:, t], wide[:, t], beta[:, t], pool, 0, live)
    np.testing.assert_allclose(np.asarray(o), np.asarray(want[:, -1]), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(pool[0]), np.asarray(s_want), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# YaRN on the latent layer's roped columns, and the softmax scale
# ---------------------------------------------------------------------------

def test_yarn_frequencies_at_the_published_sizes_against_values_worked_out_by_hand():
    """64 roped columns, theta 100000, factor 8 over 32768, beta 32 and 1. The
    column at which a frequency turns r times over 32768 positions is c(r) =
    64 ln(32768 / (2 pi r)) / (2 ln 100000): c(32) = 14.16 -> low 14, c(1) =
    23.79 -> high 24. Columns 0..14 keep theta^(-i/32), columns 24..31 have it
    divided by 8, and column 19 lies halfway up the ramp."""
    kind = LayerKind("latent", 64, mixer="latent", rope_theta=100000.0, yarn_factor=8.0, yarn_original_len=32768)
    got = rope_inv_freq(kind, 64)
    base = lambda i: 100000.0 ** (-i / 32.0)
    assert got.shape == (32,)
    np.testing.assert_allclose(got[:15], [base(i) for i in range(15)], rtol=1e-12)
    np.testing.assert_allclose(got[24:], [base(i) / 8.0 for i in range(24, 32)], rtol=1e-12)
    np.testing.assert_allclose(got[19], base(19) * (0.5 + 0.5 / 8.0), rtol=1e-12)  # (19 - 14) / (24 - 14) of the way
    np.testing.assert_allclose(got[14], 100000.0 ** (-14 / 32.0), rtol=1e-12)
    np.testing.assert_allclose(got[15], base(15) * (0.9 + 0.1 / 8.0), rtol=1e-12)
    np.testing.assert_allclose(np.asarray(ref.yarn_inv_freq(64, 100000.0, dict(
        SCALING, original_max_position_embeddings=32768))), got, rtol=1e-6)  # the reference's own, in float32


def test_the_latent_softmax_scale_carries_the_square_of_yarns_factor():
    published = dataclasses.replace(CFG, qk_nope_head_dim=128, qk_rope_head_dim=64)
    m = 0.1 * math.log(8.0) + 1.0
    assert abs(m - 1.2079) < 1e-4
    assert latent_scale(published, MLA) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert latent_scale(published) == latent_scale(published, LayerKind("x", 4, mixer="latent")) == 1 / math.sqrt(192)
    assert ref.softmax_scale(MODEL, 192) == pytest.approx(192 ** -0.5 * m * m, rel=1e-12)
    assert ref.softmax_scale(dict(MODEL, use_mla_scaling_factor=False), 192) == 1 / math.sqrt(192)


# ---------------------------------------------------------------------------
# the share of an expert-parallel layer
# ---------------------------------------------------------------------------

def test_the_two_halves_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Two chips with 4 of 8 experts each (first_expert 0 and 4): the parts of
    the latent layer's routed FFN the two compute, the shared expert counted
    once, add up to the uncut reference's output (held=None), clamp and all."""
    whole = dataclasses.replace(CFG, experts_held=8, first_expert=0)
    lp = jax.tree.map(lambda a: a[0], _params(whole)["kind_layers"]["latent"])
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 11, CFG.d_model)), jnp.float32)
    want = ref.routed_ffn(x, lp, MODEL, held=None)
    shared = ref._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"], LIMIT)
    total, pairs = jnp.zeros_like(x), 0
    for first in (0, 4):
        cfg = dataclasses.replace(whole, experts_held=4, first_expert=first)
        mine = {**lp, **{k: lp[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = _held_experts_ffn(x, mine, cfg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.routed_ffn(x, mine, MODEL, held=(first, 4))), atol=2e-5, rtol=1e-5)
        total, pairs = total + out - shared, pairs + int(counts[0])
    assert pairs == 2 * 11 * CFG.expert_top_k  # every pair landed on exactly one half
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the two rules side by side
# ---------------------------------------------------------------------------

def test_rule_for_gives_the_state_rule_and_the_latent_rule_in_the_pools_order():
    ec = EngineConfig(**{**ENGINE_KW, "total_pages": 25})
    rules = []
    for kind in CFG.kinds:
        rules.append(cache_rules.rule_for(CFG, kind, ec, first=rules[-1].sl.stop if rules else 0))
    assert [type(r) for r in rules] == [cache_rules.SlotState, cache_rules.LatentRows]
    assert [(r.sl.start, r.sl.stop) for r in rules] == [(0, 2), (2, 3)] and CFG.kinds == (GDN, MLA)
    assert CFG.layers_of(GDN) == 4 and CFG.layers_of(MLA) == 1
    assert rules[1].pools()[0][0] == (1, 25 * PS, 128)  # one layer deep, not the model's five
    assert [shape[0] for shape, *_ in rules[0].pools()] == [4, 4]
    assert all(a.beside(b) is None for a in rules for b in rules)
    eng = LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW))
    assert [type(r) for r in eng.rules] == [cache_rules.SlotState, cache_rules.LatentRows]
    assert eng._pages_needed(70, 20) == -(-(70 + 20 + 4) // PS)  # pages for the one layer that keeps every token


def test_the_latent_walks_group_is_sized_from_its_own_bfloat16_pages():
    """At the published widths the first kind keeps a float32 state, and the
    latent rule beside it still counts a page of its pool in bfloat16: 8 pages
    a grid step, where float32 pages (what the first pool's dtype would say)
    give 6."""
    delta = LayerKind("delta", 64, mixer="delta", conv_size=4, n_key_heads=32, gate_scale=2.0)
    latent = LayerKind("latent", 64, mixer="latent")
    cfg = TransformerConfig(
        vocab_size=512, d_model=7168, n_layers=5, n_heads=64, head_dim=128, d_ff=18432, dtype=jnp.bfloat16,
        layer_pattern=(delta, latent, delta, delta), n_dense_layers=1, q_lora_rank=1536, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128)
    ec = EngineConfig(max_slots=128, max_seq=3712, page_size=128, total_pages=3584)
    rule = cache_rules.rule_for(cfg, latent, ec, first=2)
    assert cfg.kinds[0].state and rule.width == 640
    assert rule.group == group_pages(1, 128, 640, 2, 29) == 8 and group_pages(1, 128, 640, 4, 29) == 6
    assert rule.walk_key == (0, 8)
    state = cache_rules.rule_for(cfg, delta, ec, first=0)
    assert [shape for shape, *_ in state.pools()] == [(4, 128, 64, 128, 128), (4, 128, 3, 128, 128)]  # 16,384 channels


@pytest.mark.parametrize("engine_kw,message", [
    (dict(prefix_cache=True), "prefix_cache is not written for delta layers: a hit copies pages.*ROADMAP M4"),
    (dict(chunked_prefill=16), "chunked_prefill is not written for delta layers: a chunk would have to start.*ROADMAP M4"),
    (dict(tensor_parallel=2), "tensor_parallel > 1 is not written for delta layers: the state pool.*ROADMAP M4"),
])
def test_the_engine_refuses_for_the_model_what_either_rule_refuses(engine_kw, message):
    no_experts = dataclasses.replace(CFG, n_experts=0, experts_held=0, expert_d_ff=0, n_shared_experts=0)
    with pytest.raises(ValueError, match=message):
        LLMEngine(no_experts, engine_config=EngineConfig(**ENGINE_KW, **engine_kw))


def test_each_refusals_sentence_names_its_rule():
    ec = EngineConfig(**{**ENGINE_KW, "total_pages": 25})
    state, latent = (cache_rules.rule_for(CFG, kind, ec, first=0) for kind in CFG.kinds)
    for option in ("prefix_cache", "chunked_prefill", "tensor_parallel > 1"):
        assert state.refuses(option).startswith(f"{option} is not written for delta layers")
    assert latent.refuses("tensor_parallel > 1") == cache_rules.ONE_CHIP and "latent cache" in cache_rules.ONE_CHIP
    assert latent.refuses("prefix_cache") is None and latent.refuses("chunked_prefill") is None  # its pages restore


def test_a_latent_kind_stands_beside_plain_attention_kinds_too():
    """A pattern may hold a latent kind beside none of the recurrent ones: a
    latent layer and a roped GQA layer, each with its own rule."""
    gqa = LayerKind("gqa", 4)
    cfg = dataclasses.replace(CFG, n_layers=4, n_dense_layers=0, layer_pattern=(MLA, gqa), attn_gate="", n_kv_heads=2)
    eng = LLMEngine(cfg, engine_config=EngineConfig(**ENGINE_KW))
    assert [type(r) for r in eng.rules] == [cache_rules.LatentRows, cache_rules.PagedRows]
    solo = eng.generate(_tokens(20, seed=4), max_tokens=6)["tokens"]
    toks = jnp.asarray([list(_tokens(20, seed=4)) + solo[:-1]])
    assert [int(t) for t in np.argmax(np.asarray(forward(eng.params, toks, cfg)[0])[0, 19:], axis=-1)] == solo


def test_kinds_that_cannot_be_built_are_refused_by_the_configuration():
    with pytest.raises(AssertionError, match="a latent layer has no window"):
        dataclasses.replace(CFG, layer_pattern=(GDN, dataclasses.replace(MLA, window=8), GDN, GDN))
    with pytest.raises(AssertionError, match="key heads that divide its heads"):
        odd = dataclasses.replace(GDN, n_key_heads=3)
        dataclasses.replace(CFG, layer_pattern=(odd, MLA, odd, odd))
    with pytest.raises(AssertionError, match="a latent layer's output gate is elementwise"):
        dataclasses.replace(CFG, attn_gate="per_head")
    with pytest.raises(AssertionError, match="latent throughout has no layer pattern"):
        dataclasses.replace(CFG, attention_kind="latent")


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference
# ---------------------------------------------------------------------------

def _bench_architecture():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "architectures", "gigachat3_5.py")
    spec = importlib.util.spec_from_file_location("bench_gigachat3_5", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


PUBLISHED = dict(
    MODEL, hidden_size=32, num_attention_heads=4, num_key_value_heads=4, intermediate_size=48, moe_intermediate_size=16,
    q_lora_rank=24, qk_rope_head_dim=8, v_head_dim=8, qk_head_dim=16, linear_key_head_dim=16, linear_value_head_dim=16,
    linear_conv_kernel_dim=4, linear_num_key_heads=2, linear_num_value_heads=4,
    norm_type="ZeroCenteredGatedNorm", layernorm_type="pre_post", linear_attention_type="GigaChat35GatedDeltaNet",
    linear_gating_type="gated_rmsnorm_sigmoid_zero_centered", use_shared_expert_sigmoid=False, n_group=1, topk_group=1,
    router_experts=8, n_routed_experts=4, first_expert=2, n_shared_experts=1, norm_topk_prob=True,
    vocab_size=96, max_position_embeddings=128, tie_word_embeddings=False,
    transformer=dict(dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="reference"))


def test_the_benchmarks_copy_and_the_repos_reference_give_equal_logits():
    bench = _bench_architecture()
    params, toks = _params(), jnp.asarray(_tokens(70, batch=2, seed=4))
    np.testing.assert_allclose(np.asarray(bench.logits(params, toks, PUBLISHED)),
                               np.asarray(ref.logits(params, toks, MODEL, held=HELD)), atol=5e-5, rtol=1e-5)


def test_the_benchmarks_key_mapping_builds_this_configuration():
    """The published keys -> the TransformerConfig the tests above run."""
    assert TransformerConfig(**_bench_architecture().transformer_kwargs(PUBLISHED)) == CFG
