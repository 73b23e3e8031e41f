"""The decoder with gated short-convolution layers beside QK-normed roped GQA
layers, leading dense layers and every routed expert held behind a router that
chooses by a bias it does not weigh by (models/transformer.py
``LayerKind(mixer="conv")``, ``qk_norm``, ``router_bias``, ``experts_held ==
n_experts``) against its plain reference (models/reference_conv_moe.py), at toy
widths on the CPU with seeded random weights: the forward at two depths with the
head norm and the bias on and off, the served path through the tail kept by slot
beside paged KV (logits, not tokens), what a prompt of one token, a slot's second
request and a prompt that fills its bucket leave of a tail, the mixer a token at
a time, the router's bias, the halves of the experts adding up, the rule's pools,
refusals and counters, that the defaults leave every other model's program as it
was, and the benchmark's copy of the reference with its key mapping.

The tolerances, written once. Logits here are about 4 in size and float32
throughout. The forward sums the same terms as the reference in another order
(the grouped rows of an expert against one expert at a time, a float32 router
either way): 5.4e-6 as read on this machine, so FORWARD = 5e-5 holds ten times
that. The served path adds pages against one score matrix and a tail against a
padded sequence: 6e-6 as read, SERVED = 5e-5. What they must tell apart moves
a logit by 1e-2 or more (a head norm, a bias, a tap, a gate left out:
``test_what_the_tolerance_tells_apart`` asks for 5e-3, a hundred times either)."""
import collections
import dataclasses
import hashlib
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.cache_rules import PagedRows, SlotTail
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_conv_moe as ref
from ray_tpu.ops.paged_attention import group_pages
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, _conv_mixer, _held_experts_pass, cross_entropy_loss, forward, init_params,
    param_logical_axes,
)

FORWARD = SERVED = 5e-5
LAYER = 1e-5  # one routed layer's output, about 3 in size: the grouped rows' sums against an expert at a time read 2.4e-6
PS = 16
CONV = LayerKind("conv", 0, mixer="conv", conv_size=3)
ATT = LayerKind("attention", 4, rope_theta=1e6)
_COMMON = dict(
    vocab_size=96, d_model=32, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128, dtype=jnp.float32,
    param_dtype=jnp.float32, norm_eps=1e-5, attention_impl="reference", n_experts=8, expert_top_k=2, experts_held=8,
    expert_d_ff=16, router_score="sigmoid", router_bias=True, qk_norm=True, tie_embeddings=True)
# 1 + 4: the benchmark's cut in small (one leading dense conv layer, then attention, conv, conv, conv); 2 + 8: the
# published opening (two dense conv layers, two periods), so a second dense layer is run once
CFG = TransformerConfig(n_layers=5, n_dense_layers=1, layer_pattern=(CONV, ATT, CONV, CONV), **_COMMON)
CFG10 = TransformerConfig(n_layers=10, n_dense_layers=2, layer_pattern=(CONV, CONV, ATT, CONV), **_COMMON)
TYPES = ["conv", "full_attention", "conv", "conv", "conv"]
TYPES10 = ["conv", "conv"] + ["full_attention", "conv", "conv", "conv"] * 2
MODEL = dict(norm_eps=1e-5, layer_types=TYPES, num_dense_layers=1, num_experts_per_tok=2, routed_scaling_factor=1,
             rope_parameters={"rope_theta": 1e6, "rope_type": "default"}, use_expert_bias=True)
MODEL10 = dict(MODEL, layer_types=TYPES10, num_dense_layers=2)
ENGINE_KW = dict(max_slots=2, max_seq=128, page_size=PS, prefill_buckets=(32, 80), decode_block=4)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _params(cfg=CFG, seed=0):
    """Seeded random weights, the norms' too (init_params makes them ones): a head norm of ones would let a program
    that drops its weight pass."""
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


def test_the_parameter_tree_has_three_leaves_a_conv_mixer_two_head_norms_and_a_float32_bias():
    params = _params()
    assert set(params) == {"embed", "dense_layers", "kind_layers", "final_norm"}  # tied: no lm_head
    dense, kinds = params["dense_layers"], params["kind_layers"]
    assert set(kinds) == {"conv", "attention"}
    assert set(dense) == {"attn_norm", "w_in", "conv", "w_out", "ffn_norm", "w_gate", "w_up", "w_down"}
    assert dense["w_in"].shape == (1, 32, 96) and dense["conv"].shape == (1, 3, 32) and dense["w_out"].shape == (1, 32, 32)
    assert dense["w_gate"].shape == (1, 32, 48) and "router" not in dense
    conv, att = kinds["conv"], kinds["attention"]
    assert conv["w_in"].shape == (3, 32, 96) and conv["conv"].shape == (3, 3, 32) and "wq" not in conv
    assert att["q_norm"].shape == att["k_norm"].shape == (1, 16) and att["wk"].shape == (1, 32, 2, 16)
    for stack in (conv, att):  # every expert of a routed layer, the router, and a bias that is no part of the weight
        n = stack["router"].shape[0]
        assert stack["w_gate"].shape == (n, 8, 32, 16) and stack["router"].shape == (n, 32, 8)
        bias = np.asarray(stack["router_bias"])
        assert bias.shape == (n, 8) and bias.dtype == np.float32 and 0 < np.abs(bias).max() < 0.05
    bf16 = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), dataclasses.replace(CFG, param_dtype=jnp.bfloat16)))
    assert bf16["kind_layers"]["conv"]["router_bias"].dtype == jnp.float32  # whatever the weights' dtype
    assert bf16["kind_layers"]["conv"]["router"].dtype == jnp.bfloat16
    axes = param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)


@pytest.mark.parametrize("qk_norm", [True, False])
@pytest.mark.parametrize("bias", [True, False])
@pytest.mark.parametrize("depth", ["1+4", "2+8"])
def test_forward_matches_the_plain_reference(depth, bias, qk_norm):
    """Two rows of 50 positions through every layer, at both depths, with the head norm and the router's bias
    each on and off (off: the leaves are absent and the reference takes the unbiased path)."""
    cfg, model = (CFG, MODEL) if depth == "1+4" else (CFG10, MODEL10)
    cfg = dataclasses.replace(cfg, router_bias=bias, qk_norm=qk_norm)
    params, toks = _params(cfg), jnp.asarray(_tokens(50, batch=2))
    assert ("router_bias" in params["kind_layers"]["conv"]) == bias
    assert ("q_norm" in params["kind_layers"]["attention"]) == qk_norm
    got, _ = forward(params, toks, cfg)
    want = ref.logits(params, toks, dict(model, use_expert_bias=bias))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=FORWARD, rtol=FORWARD)


def _without(params, kind, leaf, value=0.0):
    kinds = dict(params["kind_layers"])
    kinds[kind] = {**kinds[kind], leaf: jnp.full_like(kinds[kind][leaf], value)}
    return {**params, "kind_layers": kinds}


@pytest.mark.parametrize("what", ["qk_norm", "router_bias", "first_tap", "q_norm_weight", "tie_embeddings"])
def test_what_the_tolerance_tells_apart(what):
    """The head norm or the bias switched off, the oldest tap zeroed, the head norm's weight replaced by ones, the
    head untied: each moves the forward's logits away from the reference's by 5e-3 or more, a hundred times FORWARD."""
    params, toks = _params(), jnp.asarray(_tokens(60, batch=1))
    want = np.asarray(ref.logits(params, toks, MODEL))
    cfg = CFG
    if what in ("qk_norm", "router_bias"):
        cfg = dataclasses.replace(CFG, **{what: False})
    elif what == "first_tap":
        taps = params["kind_layers"]["conv"]["conv"]
        params = {**params, "kind_layers": {**params["kind_layers"], "conv": {
            **params["kind_layers"]["conv"], "conv": taps.at[:, 0].set(0.0)}}}
    elif what == "q_norm_weight":
        params = _without(params, "attention", "q_norm", 1.0)
    else:
        cfg = dataclasses.replace(CFG, tie_embeddings=False)
        params = {**params, "lm_head": init_params(jax.random.PRNGKey(0), cfg)["lm_head"]}
    got, _ = forward(params, toks, cfg)
    assert float(np.abs(np.asarray(got) - want).max()) > 5e-3


def test_a_packed_batch_is_refused_loudly():
    batch = {"tokens": jnp.asarray(_tokens(17, batch=1)), "segment_ids": jnp.zeros((1, 17), jnp.int32)}
    with pytest.raises(NotImplementedError, match="packed sequences are not written for a conv layer: its convolution "
                                                  "would have to start again.*ROADMAP M4"):
        cross_entropy_loss(_params(), batch, CFG)


def test_the_conv_mixer_a_token_at_a_time_from_a_tail_is_the_mixer_over_the_sequence():
    """The mixer over 21 positions at once, and one position at a time with the tail the position before kept (zeros
    before position 0, end to end as the engine's pool holds it): the same outputs, and every kept tail is the two
    rows of z = B u before it, which are the reference's."""
    params = _params()
    lp = {k: v[1] for k, v in params["kind_layers"]["conv"].items()}
    h = jax.random.normal(jax.random.PRNGKey(5), (2, 21, 32), jnp.float32)
    whole, window = _conv_mixer(h, lp, CFG, CONV, (None, lambda window: window))
    z, gate = ref.conv_inputs(h, lp)
    np.testing.assert_allclose(np.asarray(window[:, 2:]), np.asarray(z), atol=1e-6)
    assert not np.asarray(window[:, :2]).any()  # zeros before position 0
    np.testing.assert_allclose(np.asarray(whole), np.asarray(gate * ref.short_conv(z, lp["conv"])), atol=1e-6)
    tail = jnp.zeros((2, 2 * 32), jnp.float32)
    for t in range(21):
        step, tail = _conv_mixer(h[:, t:t + 1], lp, CFG, CONV, (tail, lambda w: w[:, 1:].reshape(2, -1)))
        np.testing.assert_allclose(np.asarray(step[:, 0]), np.asarray(whole[:, t]), atol=1e-6)
        want = np.asarray(window[:, t + 1:t + 3]).reshape(2, -1)  # z of positions t - 1 and t
        np.testing.assert_allclose(np.asarray(tail), want, atol=1e-6)


# ---------------------------------------------------------------------------
# the router: chosen by score + bias, weighed by the score
# ---------------------------------------------------------------------------

def _routed_layer(seed=0):
    params = _params(seed=seed)
    lp = {k: v[0] for k, v in params["kind_layers"]["conv"].items()}
    return lp, jax.random.normal(jax.random.PRNGKey(seed + 7), (4, 64, 32), jnp.float32)


def test_the_bias_changes_the_chosen_set_for_a_real_share_of_tokens_and_never_the_weights():
    """With b drawn as the model draws it the chosen pair differs from the unbiased one for between a tenth and
    nine tenths of 256 tokens; a chosen expert's weight is its unbiased score over the chosen scores' sum plus 1e-6;
    b = 0 chooses what the parent's router chose."""
    lp, x = _routed_layer()
    model = dict(MODEL)
    biased, weights = ref.route(x, lp, model)
    plain, _ = ref.route(x, lp, dict(model, use_expert_bias=False))
    differs = np.asarray(jnp.sort(biased, -1) != jnp.sort(plain, -1)).any(-1)
    assert 0.1 < differs.mean() < 0.9, differs.mean()
    score = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", x, lp["router"], precision="highest"))
    chosen = jnp.take_along_axis(score, biased, axis=-1)
    np.testing.assert_allclose(np.asarray(weights), np.asarray(chosen / (chosen.sum(-1, keepdims=True) + 1e-6)), rtol=1e-6)
    zero, _ = ref.route(x, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, model)
    assert (np.asarray(jnp.sort(zero, -1)) == np.asarray(jnp.sort(plain, -1))).all()
    # the program: the layer's output is the reference's with the bias, and the unbiased reference's with b = 0
    got, _ = _held_experts_pass(x, lp, CFG)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.routed_ffn(x, lp, model)), atol=LAYER)
    got0, _ = _held_experts_pass(x, {**lp, "router_bias": jnp.zeros_like(lp["router_bias"])}, CFG)
    np.testing.assert_allclose(np.asarray(got0), np.asarray(ref.routed_ffn(x, lp, dict(model, use_expert_bias=False))),
                               atol=LAYER)
    assert float(np.abs(np.asarray(got) - np.asarray(got0)).max()) > 1e-3  # and the two differ


def test_eight_of_eight_held_is_the_whole_layer_and_the_two_halves_add_up_to_it():
    """The share tied to the model: every expert held (first_expert 0, 8 of 8) gives the uncut reference's layer;
    held as two chips' halves (first_expert 0 and 4, 4 each, the same router, bias and weights) the two partial
    results add up to it. The counts: every pair lands on a held expert."""
    lp, x = _routed_layer(seed=3)
    whole_ref = np.asarray(ref.routed_ffn(x, lp, MODEL))
    whole, counts = _held_experts_pass(x, lp, CFG)
    np.testing.assert_allclose(np.asarray(whole), whole_ref, atol=LAYER)
    assert int(counts[0]) == 4 * 64 * 2
    halves = []
    for first in (0, 4):
        cfg = dataclasses.replace(CFG, experts_held=4, first_expert=first)
        half_lp = {**lp, **{k: lp[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, c = _held_experts_pass(x, half_lp, cfg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref.routed_ffn(x, half_lp, MODEL, held=(first, 4))),
                                   atol=LAYER)
        halves.append((np.asarray(out), int(c[0])))
    np.testing.assert_allclose(halves[0][0] + halves[1][0], whole_ref, atol=LAYER)
    assert halves[0][1] + halves[1][1] == 4 * 64 * 2 and min(h[1] for h in halves) > 0


# ---------------------------------------------------------------------------
# the served path: logits, position by position
# ---------------------------------------------------------------------------

@pytest.fixture
def logits_spy(monkeypatch):
    """Every batch of logits the served path samples from, in order: the engine's ``sample_batch`` replaced by one
    that hands its logits to the host and takes the argmax."""
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


def _want(params, prompt, toks, model=MODEL):
    full = jnp.asarray([list(prompt) + toks[:-1]])
    return np.asarray(ref.logits(params, full, model))[0, len(prompt) - 1:]


@pytest.mark.parametrize("n_prompt", [70, 33, 32, 1])
def test_prefill_then_decode_through_tails_and_pages_matches_the_full_forward_f32(n_prompt, logits_spy):
    """17 tokens (the prefill's and 16 decoded) against the reference's full forward over prompt + generated
    tokens: logits, position by position. A prompt of 70 is padded to a bucket of 80 (the tail is cut at 70), one of
    33 is a page and a row, one of 32 ends on its bucket's last position (the tail is the bucket's last two rows),
    one of 1 is shorter than the convolution's reach (its tail is a row of zeros and z_0)."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    toks = eng.generate(prompt, max_tokens=17)["tokens"]
    jax.effects_barrier()
    if n_prompt in (70, 32):
        assert eng.trace_snapshot()["requests"][0]["bucket"] == {70: 80, 32: 32}[n_prompt]
    got = np.stack([r[0] for r in logits_spy][:17]).astype(np.float32)
    want = _want(params, prompt, toks)
    assert got.shape == want.shape == (17, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=SERVED, rtol=SERVED)


@pytest.mark.parametrize("n_prompt,bucket", [(1, 32), (32, 32), (23, 32), (23, 80)])
def test_a_prefill_leaves_the_tail_the_references_z_rows_say(n_prompt, bucket):
    """What a prompt's prefill writes into its slot is rows n - 2 and n - 1 of the reference's z = B u in every
    conv layer (zeros before position 0: a prompt of one token leaves a row of zeros and z_0), at its own length
    whatever the bucket (23 in 32 and in 80; 32 fills its bucket), and the slot beside it is not written."""
    params, prompt = _params(), _tokens(n_prompt, seed=9)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (bucket,)}))
    ints = lambda *x: jnp.asarray(x, jnp.int32)
    padded = np.zeros((1, bucket), np.int32)
    padded[0, :n_prompt] = prompt
    cache, _ = eng._prefill(bucket, 1)(
        eng.params, eng.cache, jnp.asarray(padded), ints(n_prompt), jnp.zeros((1, bucket // PS), jnp.int32),
        jax.random.PRNGKey(0), jnp.zeros(1), jnp.ones(1), ints(0), ints(1))  # into slot 1
    tails = np.asarray(cache[0])
    assert tails.shape == (4, 2, 2 * 32) and not tails[:, 0].any()
    x = params["embed"][jnp.asarray(prompt)[None]]
    conv_layer = 0
    for kind, lp in ref.layers(params, MODEL):
        h = ref._norm(x, lp["attn_norm"], 1e-5)
        if kind == ref.CONV:
            z = np.asarray(jnp.pad(ref.conv_inputs(h, lp)[0], ((0, 0), (2, 0), (0, 0)))[0])  # zeros before position 0
            np.testing.assert_allclose(tails[conv_layer, 1], z[n_prompt:n_prompt + 2].reshape(-1), atol=LAYER, rtol=LAYER)
            conv_layer += 1
            x = x + ref.conv_mixer(h, lp)
        else:
            x = x + ref.attention(h, lp, 1e6, 1e-5)
        h = ref._norm(x, lp["ffn_norm"], 1e-5)
        x = x + (ref.routed_ffn(h, lp, MODEL) if "router" in lp else ref._swiglu(h, lp["w_gate"], lp["w_up"], lp["w_down"]))
    assert conv_layer == 4


def test_requests_of_unequal_length_share_decode_blocks_and_a_third_takes_a_left_slot(logits_spy):
    """Two slots, three requests: a prompt of 66 and one of 7 decode in the same blocks, each on its own tails and
    pages; the short one ends first and a third request is admitted into the slot it left, whose tails its prefill
    replaces. Every request's logits are its own full forward's, the third's too (its prefill's row and the decode
    rows of the slot it took)."""
    params = _params()
    prompts = {"long": _tokens(66, seed=1), "short": _tokens(7, seed=2), "next": _tokens(40, seed=3)}
    budget = {"long": 30, "short": 9, "next": 12}
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    lives = {rid: eng.add_request(rid, p, max_tokens=budget[rid]) for rid, p in prompts.items()}
    done = _run(eng)
    assert lives["next"]["slot"] == lives["short"]["slot"] == 1 and lives["long"]["slot"] == 0
    decode = [r for r in logits_spy if r.shape[0] == 2]  # a decode step's rows: both slots'
    for rid in ("long", "short"):
        want = _want(params, prompts[rid], done[rid])[1:]
        got = np.stack([r[lives[rid]["slot"]] for r in decode[:budget[rid] - 1]])
        np.testing.assert_allclose(got, want, atol=SERVED, rtol=SERVED)
    # the third request alone gives the same tokens (greedy, float32), and they are the reference's argmax
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    assert done["next"] == solo.generate(prompts["next"], max_tokens=budget["next"])["tokens"]
    want = _want(params, prompts["next"], done["next"])
    assert [int(t) for t in np.argmax(want, axis=-1)] == done["next"]
    # its decode rows in the slot it took: the last 11 rows slot 1 decoded that match no row of "short"
    tail_rows = np.stack([r[1] for r in decode])
    start = next(i for i in range(len(tail_rows) - 10)
                 if np.allclose(tail_rows[i:i + 11], want[1:], atol=SERVED, rtol=SERVED))
    assert start >= budget["short"] - 1


def test_the_deeper_model_is_served_too(logits_spy):
    """2 + 8 layers: two leading dense conv layers and two periods, six tails a slot beside two attention layers'
    pages."""
    params, prompt = _params(CFG10), _tokens(21, seed=6)
    eng = LLMEngine(CFG10, params=params, engine_config=EngineConfig(**ENGINE_KW))
    toks = eng.generate(prompt, max_tokens=9)["tokens"]
    jax.effects_barrier()
    assert eng.cache[0].shape == (8, 2, 64) and eng.cache[1].shape[0] == 2
    got = np.stack([r[0] for r in logits_spy][:9]).astype(np.float32)
    np.testing.assert_allclose(got, _want(params, prompt, toks, MODEL10), atol=SERVED, rtol=SERVED)


# ---------------------------------------------------------------------------
# the rule: pools, an empty slot, counters, refusals
# ---------------------------------------------------------------------------

def test_the_rule_is_one_pool_of_tails_in_the_activations_dtype_and_pages_count_in_it():
    """One pool [the kind's layers, slots, (T - 1) x D] and no float32 pool beside it; the attention kind's pages
    behind it; a walk's group is sized from the activations' bytes (no float32 first pool to count them in), so a
    bfloat16 model's attention layers walk the 8 pages a step their pages allow, where a model whose first kind
    keeps a float32 state walks 4."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    tail_rule, page_rule = eng.rules
    assert isinstance(tail_rule, SlotTail) and isinstance(page_rule, PagedRows)
    assert (tail_rule.n_pools, tail_rule.tok_axis, tail_rule.sl, page_rule.sl) == (1, None, slice(0, 1), slice(1, 3))
    tails, k_pages, v_pages = eng.cache
    assert tails.shape == (4, 2, 2 * 32) and tails.dtype == jnp.float32 and not np.asarray(tails).any()
    assert k_pages.shape == v_pages.shape == (1, 2, eng.ec.total_pages * PS, 16)
    assert eng.pool_bytes == {"conv": 4 * 2 * 64 * 4, "attention": 2 * 2 * eng.ec.total_pages * PS * 16 * 4}
    assert tail_rule.place(np.asarray([1, 0])).tolist() == [1, 0] and page_rule.place([1]) is None
    bf16 = dataclasses.replace(CFG, dtype=jnp.bfloat16, param_dtype=jnp.bfloat16, max_seq_len=4096)
    big = dict(max_slots=2, max_seq=4096, page_size=128, total_pages=40, prefill_buckets=(128,))
    eng16 = LLMEngine(bf16, engine_config=EngineConfig(**big))
    assert eng16.cache[0].dtype == jnp.bfloat16 and eng16.rules[1].itemsize == 2
    ssd = LayerKind("mamba", 4, mixer="ssd", conv_size=4, head_width=16, state_size=32, n_groups=1)
    hybrid = dataclasses.replace(bf16, layer_pattern=(ssd, dataclasses.replace(ATT, rope_share=0.0)), n_layers=2,
                                 n_dense_layers=0, n_experts=0, experts_held=0, router_bias=False, qk_norm=False)
    state_first = LLMEngine(hybrid, engine_config=EngineConfig(**big))
    assert state_first.rules[1].itemsize == 4  # the quirk PR 49 kept: the float32 state's
    # at the published shape (8 KV heads, pages of 128 rows a lane tile wide, 29 pages a sequence): 8 pages, not 4
    assert group_pages(8, 128, 128, 2, 29) == 8 == 2 * group_pages(8, 128, 128, 4, 29)


def test_an_empty_slots_tail_is_bit_for_bit_what_it_was_and_the_records_count_tails():
    """Slot 1 never holds a request: decode blocks on slot 0 leave its tail (set to a pattern first) bit for bit,
    and the step records count one rewritten tail a step, not two, and one tail written by the one prefill; the
    experts' counters beside them: 2 pairs a row of the batch (the empty slot's too) in each of 4 routed layers."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    tails, *pages = eng.cache
    marked = tails.at[:, 1].set(jnp.arange(64, dtype=jnp.float32))
    want = np.asarray(marked[:, 1])  # before the programs take the pool
    eng.cache = (marked, *pages)
    eng.generate(_tokens(20, seed=4), max_tokens=13)
    assert (np.asarray(eng.cache[0][:, 1]) == want).all()
    assert np.asarray(eng.cache[0][:, 0]).any()  # slot 0's moved
    steps = eng.trace_snapshot()["steps"]
    assert all({"tail_rows", "expert_pairs", "expert_tiles"} <= set(s) for s in steps)
    assert not any("state_rows" in s for s in steps)
    blocks = [s for s in steps if s["block"]]
    assert blocks and all(s["tail_rows"] == s["block"] * 1 == s["block"] * s["active"] for s in blocks)
    assert all(s["expert_pairs"] == s["block"] * 2 * 2 * 4 for s in blocks)  # both rows of the batch are routed
    assert sum(s["n_prefill"] for s in steps) == 1 and sum(s["tail_rows"] for s in steps) >= 12


@pytest.mark.parametrize("engine_kw,message", [
    (dict(prefix_cache=True), "prefix_cache is not written for conv layers: a hit copies pages, and no page holds "
                              "the convolution tail.*ROADMAP M4"),
    (dict(chunked_prefill=16), "chunked_prefill is not written for conv layers: a chunk would have to start from the "
                               "convolution tail.*ROADMAP M4"),
])
def test_the_engine_refuses_what_a_tail_cannot_do(engine_kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, **engine_kw))


def test_tensor_parallel_a_window_beside_it_and_a_conv_kind_with_heads_are_refused():
    no_experts = dataclasses.replace(CFG, n_experts=0, experts_held=0, router_bias=False)
    with pytest.raises(ValueError, match="tensor_parallel > 1 is not written for conv layers: the tail pool is "
                                         "addressed by the slot.*ROADMAP M4"):
        LLMEngine(no_experts, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=2))
    with pytest.raises(ValueError, match="tensor_parallel > 1 is not written for a latent cache or held experts"):
        LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, tensor_parallel=2))  # the FFN's refusal comes first
    sliding = LayerKind("sliding", 4, window=32)
    with pytest.raises(ValueError, match="window layers beside conv layers are not written"):
        LLMEngine(dataclasses.replace(no_experts, n_layers=3, n_dense_layers=0, layer_pattern=(ATT, sliding, CONV)),
                  engine_config=EngineConfig(**ENGINE_KW))
    with pytest.raises(AssertionError, match="a conv layer has a short convolution, no heads and no window"):
        dataclasses.replace(CFG, layer_pattern=(dataclasses.replace(CONV, n_heads=4), ATT))
    with pytest.raises(AssertionError, match="a selection bias is written for held experts"):
        dataclasses.replace(CFG, experts_held=0)


# ---------------------------------------------------------------------------
# every other model's program and parameter tree are what they were
# ---------------------------------------------------------------------------

def _primitives(jaxpr, counts):
    for eqn in jaxpr.eqns:
        counts[eqn.primitive.name] += 1
        for v in eqn.params.values():
            for j in (v if isinstance(v, (list, tuple)) else [v]):
                if hasattr(j, "jaxpr"):
                    _primitives(j.jaxpr if hasattr(j.jaxpr, "eqns") else j.jaxpr.jaxpr, counts)
                elif hasattr(j, "eqns"):
                    _primitives(j, counts)
    return counts


_F32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
_FULL = LayerKind("full", 4, rope_theta=5e5, rope_share=0.5, yarn_factor=8.0, yarn_original_len=64, attention_factor=1.2)
_SLIDING = LayerKind("sliding", 6, window=32)
_GQA = LayerKind("gqa", 4, rope_share=0.0)
_KDA = LayerKind("kda", 4, mixer="delta", conv_size=4, low_rank=16, beta_scale=2.0)
_NOPE = LayerKind("attention", 4, rope_share=0.0)
_SSD = LayerKind("mamba", 4, mixer="ssd", conv_size=4, head_width=16, state_size=32, n_groups=1)
_EXPERTS = dict(n_experts=8, experts_held=4, expert_d_ff=16, n_shared_experts=1, router_score="sigmoid",
                attention_impl="reference")
# the toy forms of the configurations the benchmark had before this one (the two dense models share a form), each with
# the primitives of its forward's jaxpr as the commit before this kind counted them (sha1 of the sorted counts, their
# sum) and the sha1 of its parameter tree's paths, shapes and dtypes there; the three with held experts as PR 60 left
# them (a routed layer's two copies are two custom-VJP calls and the row plan divides by ``lax.div``: 8 primitives fewer
# a routed layer, the arithmetic what it was)
OTHER_MODELS = {
    "dense_f32": (TransformerConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=48,
                                    max_seq_len=128, **_F32), "81b910a0bb0b", 129, "0563a35d7bbb"),
    "dense_bf16": (TransformerConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=48,
                                     max_seq_len=128), "507cc76c6bd9", 153, "0563a35d7bbb"),
    "latent_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=3, n_heads=4, d_ff=48, max_seq_len=128, attention_kind="latent",
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, sandwich_norm=True,
        n_dense_layers=1, expert_top_k=2, **_EXPERTS, **_F32), "28038231f2d1", 675, "96937bb89adf"),
    "window_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
        layer_pattern=(_FULL, _SLIDING, _SLIDING, _SLIDING), attn_gate="per_head", n_dense_layers=1, expert_top_k=2,
        **_EXPERTS, **_F32), "e1e8af932c33", 2203, "ce1310a7800d"),
    "delta_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
        layer_pattern=(_GQA, _KDA, _KDA, _KDA), attn_gate="elementwise", expert_top_k=3, first_expert=2, norm_eps=1e-5,
        **_EXPERTS, **_F32), "9f94454d8ec2", 3014, "25c64f8affaf"),
    "ssd_hybrid": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
        norm_eps=1e-5, attention_impl="reference", layer_pattern=(_SSD, _SSD, _NOPE, _SSD), embed_multiplier=12.0,
        residual_multiplier=0.22, attention_multiplier=0.125, logits_divisor=8.0, tie_embeddings=True, **_F32),
        "bd5447d6d758", 917, "e2b78a7ff110"),
}


def _tree_digest(shapes) -> str:
    leaves = sorted((jax.tree_util.keystr(path), tuple(a.shape), str(a.dtype))
                    for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0])
    return hashlib.sha1(repr(leaves).encode()).hexdigest()[:12]


@pytest.mark.parametrize("name", sorted(OTHER_MODELS))
def test_the_new_fields_at_their_defaults_leave_another_models_program_and_tree_as_they_were(name):
    """``qk_norm`` and ``router_bias`` off and no conv kind: the forward's jaxpr holds the primitives it held at the
    commit before this kind, count by count (the 1e-6 of the biased normaliser is under the bias's switch), and the
    parameter tree has the leaves, shapes and dtypes it had (values compared once on this machine, weights, logits
    and served tokens bit for bit: CHANGES.md PR 50)."""
    cfg, digest, total, tree = OTHER_MODELS[name]
    assert not cfg.qk_norm and not cfg.router_bias and not any(k.mixer == "conv" for k in cfg.kinds)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(3), cfg))
    assert _tree_digest(shapes) == tree
    params = init_params(jax.random.PRNGKey(3), cfg)
    toks = jnp.asarray(_tokens(40, batch=2))
    counts = _primitives(jax.make_jaxpr(lambda p, t: forward(p, t, cfg))(params, toks).jaxpr, collections.Counter())
    assert sum(counts.values()) == total
    assert hashlib.sha1(repr(sorted(counts.items())).encode()).hexdigest()[:12] == digest


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference, and its key mapping
# ---------------------------------------------------------------------------

def _bench_architecture():
    path = os.path.join(ROOT, "benchmarks", "architectures", "lfm2_moe.py")
    spec = importlib.util.spec_from_file_location("bench_lfm2_moe", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


PUBLISHED = dict(
    MODEL, model_type="lfm2_moe", hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    moe_intermediate_size=16, num_experts=8, num_hidden_layers=5, vocab_size=96, max_position_embeddings=128,
    conv_L_cache=3, conv_bias=False, norm_topk_prob=True, tie_word_embeddings=True,
    transformer=dict(dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="reference"))


def test_the_benchmarks_copy_and_the_repos_reference_give_equal_logits():
    bench = _bench_architecture()
    params, toks = _params(), jnp.asarray(_tokens(70, batch=2, seed=4))
    with jax.default_matmul_precision("highest"):  # as the serve check calls it
        got = bench.logits(params, toks, PUBLISHED)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(params, toks, MODEL)), atol=1e-5, rtol=1e-5)


def test_the_benchmarks_key_mapping_builds_this_configuration():
    """The published keys -> the TransformerConfig the tests above run, at both depths."""
    bench = _bench_architecture()
    assert TransformerConfig(**bench.transformer_kwargs(PUBLISHED)) == CFG
    deeper = dict(PUBLISHED, layer_types=TYPES10, num_dense_layers=2, num_hidden_layers=10)
    assert TransformerConfig(**bench.transformer_kwargs(deeper)) == CFG10


def test_the_cut_configuration_counts_5_18_g_parameters_with_every_expert_held():
    """benchmarks/configs/lfm2-24b-a2b-l9.json through the key mapping: 1 + 8 layers, conv at 0, attention at 1 and 5,
    all 64 experts of 8 routed layers; the tree's shapes (nothing allocated) count what the architecture file
    counts: 89,139,200 + 2 x 614,600,896 + 6 x 620,898,368 + the embedding 134,217,728 + the final norm."""
    bench = _bench_architecture()
    with open(os.path.join(ROOT, "benchmarks", "configs", "lfm2-24b-a2b-l9.json")) as f:
        model = json.load(f)
    assert sorted(model["reduced"]) == ["layer_types", "num_dense_layers", "num_hidden_layers"]
    cfg = TransformerConfig(**bench.transformer_kwargs(model))
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.n_periods, len(cfg.layer_pattern)) == (9, 1, 2, 4)
    assert [l for l in range(9) if not cfg.kind_of(l).recurrent] == [1, 5]
    assert cfg.kind_of(0).mixer == "conv" and cfg.kind_of(0).conv_size == 3 and cfg.kind_of(1).rope_theta == 1e6
    assert (cfg.n_experts, cfg.experts_held, cfg.first_expert, cfg.expert_top_k, cfg.expert_d_ff) == (64, 64, 0, 4, 1536)
    assert cfg.qk_norm and cfg.router_bias and cfg.tie_embeddings and cfg.router_score == "sigmoid"
    assert (cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (2048, 32, 8, 64, 11776, 65536)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)  # the file says it by name
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    counts = bench.param_counts(model)
    assert total == counts["total"] == 89_139_200 + 2 * 614_600_896 + 6 * 620_898_368 + 134_217_728 + 2_048 == 5_177_950_976
    assert bench.decode_kernels(model) == {"paged_attn": 2, "expert_gmm": 24}
    # one period (1 + 4 layers: 2,700,654,976) counts the same way
    one = dict(model, num_hidden_layers=5, layer_types=model["layer_types"][:5])
    assert bench.param_counts(one)["total"] == 2_700_654_976


def test_a_conv_layers_taps_are_drawn_with_the_newest_input_the_heaviest():
    """Initial values only (a checkpoint's taps are its own): the newest input's tap 4 times the older ones' in
    amplitude, a channel's three variances summing to 1 as three equal taps of N(0, 1/3) would: 1/18, 1/18, 8/9,
    the oldest first. Sampling error at 6 x 4,096 draws a tap is 1.3% of a variance; 5% is asked."""
    from ray_tpu.models.transformer import _conv_taps_init

    taps = np.asarray(_conv_taps_init(jax.random.PRNGKey(3), (6, 3, 4096), jnp.float32))
    var = taps.var(axis=(0, 2))
    np.testing.assert_allclose(var, [1 / 18, 1 / 18, 8 / 9], rtol=0.05)
    assert abs(var.sum() - 1.0) < 0.03 and abs(taps.mean()) < 0.01
    params = init_params(jax.random.PRNGKey(0), CFG)
    assert params["kind_layers"]["conv"]["conv"].shape[1] == 3 and params["dense_layers"]["conv"].shape[1] == 3
