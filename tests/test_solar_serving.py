"""The decoder with delta-rule linear-attention layers beside gated softmax
layers without positions and held experts (models/transformer.py
``LayerKind(mixer="delta")``, ``attn_gate="elementwise"``, ``rope_share=0``)
against its plain reference (models/reference_linear_moe.py), at toy widths on
the CPU with seeded random weights: the forward, the served path through the
state kept by slot beside paged KV (logits, not tokens), what a bucket's
padding leaves of a prompt, an empty slot's state, the step record's counters,
the shares of an expert-parallel layer, the benchmark's copy of the reference,
and what the engine refuses for a model with such layers."""
import dataclasses
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_linear_moe as ref
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, _held_experts_ffn, cross_entropy_loss, forward, init_params, param_logical_axes,
)

PS = 16
GQA = LayerKind("gqa", 4, rope_share=0.0)
KDA = LayerKind("kda", 4, mixer="delta", conv_size=4, low_rank=16, beta_scale=2.0)
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-5, attention_impl="reference",
    layer_pattern=(GQA, KDA, KDA, KDA), attn_gate="elementwise",
    n_experts=8, expert_top_k=3, experts_held=4, first_expert=2, expert_d_ff=16, n_shared_experts=1,
    routed_scaling=1.0, router_score="sigmoid",
)
MODEL = dict(rms_norm_eps=1e-5, num_hidden_layers=8, gqa_layers=[0, 4], kda_allow_neg_eigval=True,
             num_experts_per_tok=3, routed_scaling_factor=1)
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
    kinds = params["kind_layers"]
    assert set(kinds) == {"gqa", "kda"} and "layers" not in params and "dense_layers" not in params
    assert kinds["gqa"]["wq"].shape == (2, 32, 4, 16) and kinds["gqa"]["wk"].shape == (2, 32, 2, 16)
    assert kinds["gqa"]["wg"].shape == (2, 32, 4, 16)  # the elementwise gate
    kda = kinds["kda"]
    assert kda["wq"].shape == kda["wk"].shape == kda["wv"].shape == (6, 32, 4, 16)  # keys and values have every head
    assert kda["conv"].shape == (6, 4, 3, 4, 16) and kda["wf_a"].shape == kda["wg_a"].shape == (6, 32, 16)
    assert kda["wf_b"].shape == kda["wg_b"].shape == (6, 16, 4, 16) and kda["dt_bias"].shape == (6, 4, 16)
    assert kda["a_log"].shape == (6, 4) and kda["wb"].shape == (6, 32, 4) and kda["o_norm"].shape == (6, 16)
    assert "wg" not in kda and kda["w_gate"].shape == (6, 4, 32, 16)
    axes = param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)


def test_forward_matches_the_plain_reference():
    """Two rows of 150 positions: more than two chunks of the chunked form,
    against the reference's scan over positions."""
    params, toks = _params(), jnp.asarray(_tokens(150, batch=2))
    got, _ = forward(params, toks, dataclasses.replace(CFG, max_seq_len=256))
    want = ref.logits(params, toks, MODEL, held=HELD)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=5e-5, rtol=1e-5)


def test_a_packed_batch_is_refused_loudly():
    batch = {"tokens": jnp.asarray(_tokens(17, batch=1)), "segment_ids": jnp.zeros((1, 17), jnp.int32)}
    dense = dataclasses.replace(CFG, n_experts=0, experts_held=0, expert_d_ff=0, n_shared_experts=0)
    with pytest.raises(NotImplementedError, match="packed sequences are not written for a delta layer.*ROADMAP M4"):
        cross_entropy_loss(_params(dense), batch, dense)


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


@pytest.mark.parametrize("n_prompt", [70, 5])
def test_prefill_then_decode_through_state_and_pages_matches_the_full_forward_f32(n_prompt, logits_spy):
    """25 tokens (the prefill's and 24 decoded) against the reference's full
    forward over prompt + generated tokens: logits, position by position. A
    prompt of 70 is padded to a bucket of 80 (two chunks' worth of padding
    the state must not see), one of 5 is shorter than a page and, less one,
    than the convolution's reach. float32 throughout."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    toks = eng.generate(prompt, max_tokens=25)["tokens"]
    jax.effects_barrier()
    got = np.stack([r[0] for r in logits_spy][:25]).astype(np.float32)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n_prompt - 1:]
    assert got.shape == want.shape == (25, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # the softmax kind's pools are pages of tokens; the delta kind's are a state and a tail a slot
    k_pages, _, state, tails = eng.cache
    assert k_pages.shape == (2, 2, eng.ec.total_pages * PS, 16) and k_pages.dtype == jnp.float32
    assert state.shape == (6, 2, 4, 16, 16) and state.dtype == jnp.float32 and tails.shape == (6, 2, 3, 3, 4, 16)
    assert set(eng.pool_bytes) == {"gqa", "kda"}
    assert eng.pool_bytes["kda"] == 6 * 2 * 4 * 16 * 16 * 4 + 6 * 2 * 3 * 3 * 4 * 16 * 4


@pytest.mark.parametrize("n_prompt,bucket", [(40, 48), (90, 96)])
def test_a_prompt_on_a_rung_between_doublings_leaves_the_state_of_its_own_length(n_prompt, bucket, logits_spy):
    """Buckets of 3 and of 6 pages, the rungs the engine puts between 32, 64 and
    128 (48 is no whole chunk of the delta kernel's 64 positions, 96 is one and
    a half): the prefill's token and 12 decoded ones against the reference's
    full forward, so the state and the convolution tail the prefill left are
    those of the prompt's own length, not the bucket's."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (32, 64)}))
    assert eng.buckets == (32, 48, 64, 96, 128)
    toks = eng.generate(prompt, max_tokens=13)["tokens"]
    jax.effects_barrier()
    assert eng.trace_snapshot()["requests"][0]["bucket"] == bucket
    got = np.stack([r[0] for r in logits_spy][:13]).astype(np.float32)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_two_requests_of_unequal_length_share_decode_blocks_and_a_third_takes_a_left_slot(logits_spy):
    """Two slots, three requests: a prompt of 66 and one of 7 decode in the
    same blocks, each on its own state; the short one ends first and the
    third request is admitted into the slot it left, whose state and tail its
    prefill replaces. Every request's decoded logits are its own full
    forward's."""
    params = _params()
    prompts = {"long": _tokens(66, seed=1), "short": _tokens(7, seed=2), "next": _tokens(40, seed=3)}
    budget = {"long": 26, "short": 9, "next": 12}
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    lives = {rid: eng.add_request(rid, p, max_tokens=budget[rid]) for rid, p in prompts.items()}
    done = _run(eng)
    assert lives["next"]["slot"] == lives["short"]["slot"] == 1 and lives["long"]["slot"] == 0
    slot = {rid: life["slot"] for rid, life in lives.items()}
    decode = [r for r in logits_spy if r.shape[0] == 2]  # a decode step's rows: both slots'
    for rid in ("long", "short"):
        n = len(prompts[rid])
        full = jnp.asarray([list(prompts[rid]) + done[rid][:-1]])
        want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, n:]
        got = np.stack([r[slot[rid]] for r in decode[:budget[rid] - 1]])
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)
    # the third request's tokens are what it gives alone (greedy, float32)
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    assert done["next"] == solo.generate(prompts["next"], max_tokens=budget["next"])["tokens"]
    full = jnp.asarray([list(prompts["next"]) + done["next"][:-1]])
    want = np.asarray(ref.logits(params, full, MODEL, held=HELD))[0, len(prompts["next"]) - 1:]
    assert [int(t) for t in np.argmax(want, axis=-1)] == done["next"]


def test_one_prompt_through_two_buckets_leaves_the_same_state_and_tail():
    """A prompt of 23 padded to a bucket of 32 and to one of 80: the state and
    the convolution tail its prefill leaves are those of its own length (the
    reference's, computed over the 23 positions alone), whatever the padding."""
    params, prompt = _params(), _tokens(23, seed=9)
    left = []
    for buckets in ((32,), (80,)):
        eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": buckets}))
        eng.add_request("r", prompt, max_tokens=2)
        eng.step()  # the prefill, and a decode block dispatched behind it
        assert eng.request_ring.total == 0 and eng.slots[0].life["bucket"] == buckets[0]
        left.append(eng)
    # read before the block in flight is absorbed: the pools are the block's outputs, so compare
    # what one position of decode made of them, in both engines alike, and the prefill's own
    # against the reference below
    for a, b in zip(left[0].cache[2:], left[1].cache[2:]):
        np.testing.assert_allclose(np.asarray(a[:, 0]), np.asarray(b[:, 0]), atol=2e-5, rtol=1e-4)
    fresh = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (80,)}))
    ints = lambda *x: jnp.asarray(x, jnp.int32)
    padded = np.zeros((1, 80), np.int32)
    padded[0, :23] = prompt
    cache, _ = fresh._prefill(80, 1)(
        fresh.params, fresh.cache, jnp.asarray(padded), ints(23), jnp.zeros((1, 5), jnp.int32), jax.random.PRNGKey(0),
        jnp.zeros(1), jnp.ones(1), ints(0), ints(1))  # into slot 1
    state, tails = cache[2:]
    assert not np.asarray(state[:, 0]).any() and not np.asarray(tails[:, 0]).any()  # slot 0 was not written
    # the reference's state after 23 positions, layer by layer, from the reference's own hidden states
    x = params["embed"][jnp.asarray(prompt)[None]]
    allowed = jnp.tril(jnp.ones((23, 23), bool))[None]
    seen = 0
    for l in range(8):
        kind = "gqa" if l % 4 == 0 else "kda"
        lp = {k: v[l // 4 if kind == "gqa" else seen] for k, v in params["kind_layers"][kind].items()}
        h = ref._norm(x, lp["attn_norm"], 1e-5)
        if kind == "gqa":
            x = x + ref.attention(h, lp, allowed)
        else:
            _, s = ref.delta_rule(*ref.delta_inputs(h, lp, 2.0))
            np.testing.assert_allclose(np.asarray(state[seen, 1]), np.asarray(s[0]), atol=2e-5, rtol=1e-4)
            u = jnp.stack([jnp.einsum("bsd,dhk->bshk", h, lp[w]) for w in ("wq", "wk", "wv")], axis=2)
            np.testing.assert_allclose(np.asarray(tails[seen, 1]), np.asarray(u[0, 20:23]), atol=2e-5, rtol=1e-4)
            x = x + ref.delta_attention(h, lp, 2.0, 1e-5)
            seen += 1
        x = x + ref.routed_ffn(ref._norm(x, lp["ffn_norm"], 1e-5), lp, MODEL, HELD)


def test_an_empty_slots_state_is_bit_for_bit_what_it_was_after_decode_blocks():
    """Slot 1 never holds a request: decode blocks on slot 0 leave its state
    and its tail (set to a pattern first) bit for bit, and the step record
    counts one rewritten state a step, not two."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    k_pages, v_pages, state, tails = eng.cache
    marked = (state.at[:, 1].set(jnp.arange(16, dtype=jnp.float32)), tails.at[:, 1].set(0.5))
    eng.cache = (k_pages, v_pages, *marked)
    want = [np.asarray(a[:, 1]) for a in marked]
    eng.generate(_tokens(20, seed=4), max_tokens=13)
    for got, a in zip(eng.cache[2:], want):
        assert (np.asarray(got[:, 1]) == a).all()
    assert np.asarray(eng.cache[2][:, 0]).any()  # slot 0's moved
    steps = eng.trace_snapshot()["steps"]
    blocks = [s for s in steps if s["block"]]
    assert blocks and all(s["state_rows"] == s["block"] * 1 == s["block"] * s["active"] for s in blocks)
    assert sum(s["n_prefill"] for s in steps) == 1
    dense = LLMEngine(TransformerConfig(vocab_size=64, d_model=32, n_layers=1, n_heads=4, d_ff=32),
                      engine_config=EngineConfig(max_slots=2, max_seq=64, page_size=16, prefill_buckets=(32,)))
    dense.generate([1, 2, 3], max_tokens=2)
    assert "state_rows" not in dense.trace_snapshot()["steps"][0]  # absent for a model without such layers


def test_admission_budgets_pages_for_the_layers_that_keep_every_token():
    """A request's pages are its tokens over the page size, whatever the delta
    layers are: their state does not grow with the context."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    dense = dataclasses.replace(CFG, layer_pattern=(), attn_gate="", n_layers=2)
    other = LLMEngine(dense, engine_config=EngineConfig(**ENGINE_KW))
    assert eng._pages_needed(70, 20) == other._pages_needed(70, 20) == -(-(70 + 20 + 4) // PS)
    assert eng.pool_bytes["gqa"] == 2 * 2 * 2 * eng.ec.total_pages * PS * 16 * 4  # two layers of eight hold pages


def test_the_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Four chips with 4 of 16 experts each: the parts of a delta layer's
    routed FFN the four compute, the shared expert counted once, add up to
    the uncut reference's output (every expert in the tree, held=None)."""
    whole = dataclasses.replace(CFG, n_experts=16, experts_held=16, first_expert=0)
    lp = jax.tree.map(lambda a: a[0], _params(whole)["kind_layers"]["kda"])
    x = jnp.asarray(np.random.default_rng(7).normal(size=(2, 11, CFG.d_model)), jnp.float32)
    want = ref.routed_ffn(x, lp, MODEL, held=None)
    shared = ref._swiglu(x, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
    total, pairs = jnp.zeros_like(x), 0
    for share in range(4):
        cfg = dataclasses.replace(whole, experts_held=4, first_expert=4 * share)
        mine = {**lp, **{k: lp[k][4 * share:4 * share + 4] for k in ("w_gate", "w_up", "w_down")}}
        out, counts = _held_experts_ffn(x, mine, cfg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref.routed_ffn(x, mine, MODEL, held=(4 * share, 4))), atol=2e-5, rtol=1e-5)
        total, pairs = total + out - shared, pairs + int(counts[0])
    assert pairs == 2 * 11 * CFG.expert_top_k  # every pair landed on exactly one share
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(want), atol=5e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference
# ---------------------------------------------------------------------------

def _bench_architecture():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "architectures", "solar_open2.py")
    spec = importlib.util.spec_from_file_location("bench_solar_open2", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


PUBLISHED = dict(
    MODEL, hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    moe_intermediate_size=16, router_experts=8, n_routed_experts=4, first_expert=2, n_shared_experts=1,
    vocab_size=96, max_position_embeddings=128, norm_topk_prob=True, first_k_dense_replace=0, use_rope=False,
    use_gqa_gate=True, kda_use_full_proj=False,
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16, num_heads=4, num_kv_heads=None),
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
    (dict(prefix_cache=True), "prefix_cache is not written for delta layers: a hit copies pages.*ROADMAP M4"),
    (dict(chunked_prefill=16), "chunked_prefill is not written for delta layers: a chunk would have to start.*ROADMAP M4"),
    (dict(tensor_parallel=2), "tensor_parallel > 1 is not written for delta layers: the state pool.*ROADMAP M4"),
])
def test_the_engine_refuses_what_a_state_cannot_do(engine_kw, message):
    no_experts = dataclasses.replace(CFG, n_experts=0, experts_held=0, expert_d_ff=0, n_shared_experts=0)
    with pytest.raises(ValueError, match=message):
        LLMEngine(no_experts, engine_config=EngineConfig(**ENGINE_KW, **engine_kw))


def test_a_delta_kind_without_its_sizes_and_a_window_beside_it_are_refused():
    with pytest.raises(AssertionError, match="a delta layer has a short convolution"):
        dataclasses.replace(CFG, layer_pattern=(GQA, LayerKind("kda", 4, mixer="delta")))
    sliding = LayerKind("sliding", 4, window=32)
    with pytest.raises(ValueError, match="window layers beside delta layers are not written"):
        LLMEngine(dataclasses.replace(CFG, n_layers=3, layer_pattern=(GQA, sliding, KDA), n_experts=0, experts_held=0,
                                      expert_d_ff=0, n_shared_experts=0), engine_config=EngineConfig(**ENGINE_KW))
