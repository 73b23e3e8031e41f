"""The decoder with selective state-space layers beside softmax layers without
positions, four scalar multipliers and a tied head (models/transformer.py
``LayerKind(mixer="ssd")``, ``embed_multiplier``, ``residual_multiplier``,
``attention_multiplier``, ``logits_divisor``, ``tie_embeddings``) against its
plain reference (models/reference_ssm_hybrid.py), at toy widths on the CPU
with seeded random weights: the forward, the served path through the state
kept by slot beside paged KV (logits, not tokens), what a bucket's padding
leaves of a prompt, an empty slot's state, the step record's counters, what
each scalar, the tie, the skip and the convolution's bias are worth against
the tolerances, that the defaults leave every other model's program as it was,
the benchmark's copy of the reference and its key mapping at the published
sizes, and what the engine refuses for a model with such layers.

The tolerances, written once. Logits here are about 1 in size and float32
throughout: the served path sums the same terms in another order (a chunk's
products against a scan, pages against one score matrix), which moves a logit
by 1.5e-7 as read on this machine, so SERVED = 2e-6 holds ten times that. What
it must tell apart moves a logit by 1e-2 or more (a dropped skip, bias or
multiplier: ``test_what_the_tolerance_tells_apart`` asks for 5e-3) or by
1.5e-5 (a state rounded to bfloat16 ONCE:
``test_a_state_rounded_to_bfloat16_misses_the_tolerance`` asks for 3 x SERVED)."""
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

from ray_tpu.llm import cache_rules, engine as engine_mod
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models import reference_ssm_hybrid as ref
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, cross_entropy_loss, forward, init_params, param_logical_axes,
)

SERVED = 2e-6
PS = 16
ATT = LayerKind("attention", 4, rope_share=0.0)
SSD = LayerKind("mamba", 4, mixer="ssd", conv_size=4, head_width=16, state_size=32, n_groups=1)
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=8, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-5, attention_impl="reference",
    layer_pattern=(SSD, SSD, ATT, SSD), embed_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.125, logits_divisor=8.0, tie_embeddings=True,
)
MODEL = dict(rms_norm_eps=1e-5, layer_types=["mamba", "mamba", "attention", "mamba"] * 2, embedding_multiplier=12,
             residual_multiplier=0.22, attention_multiplier=0.125, logits_scaling=8,
             mamba_n_heads=4, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=32)
ENGINE_KW = dict(max_slots=2, max_seq=128, page_size=PS, prefill_buckets=(32, 80), decode_block=4)
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _params(cfg=CFG, seed=0):
    """Seeded random weights, the norms' and the skip's too (init_params makes them ones)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, a):
        if "norm" in jax.tree_util.keystr(path) or "d_skip" in jax.tree_util.keystr(path):
            return (a + 0.2 * jax.random.normal(next(keys), a.shape, jnp.float32)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(jitter, params)


def _tokens(n, seed=0, batch=None):
    rng = np.random.default_rng(seed)
    return rng.integers(0, CFG.vocab_size, size=(n,) if batch is None else (batch, n)).astype(np.int32)


def test_the_parameter_tree_keeps_each_kinds_layers_in_one_stack_and_one_array_for_both_ends():
    params = _params()
    kinds = params["kind_layers"]
    assert set(params) == {"embed", "kind_layers", "final_norm"} and set(kinds) == {"attention", "mamba"}  # no lm_head
    assert kinds["attention"]["wq"].shape == (2, 32, 4, 16) and kinds["attention"]["wk"].shape == (2, 32, 2, 16)
    m = kinds["mamba"]
    assert m["w_in"].shape == (6, 64 + 128 + 4, 32)  # [z | x B C | dt] from D, the outputs' axis first
    assert m["conv"].shape == (6, 4, 128) and m["conv_bias"].shape == (6, 128) and np.asarray(m["conv_bias"]).any()
    assert m["dt_bias"].shape == m["a_log"].shape == m["d_skip"].shape == (6, 4)
    assert m["o_norm"].shape == (6, 64) and m["wo"].shape == (6, 4, 16, 32) and m["w_gate"].shape == (6, 32, 48)
    axes = param_logical_axes(CFG)
    is_axes = lambda x: isinstance(x, tuple)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == jax.tree.structure(
        jax.tree.map(lambda a: 0, axes, is_leaf=is_axes))
    for leaf, names in zip(jax.tree.leaves(params), jax.tree.leaves(axes, is_leaf=is_axes)):
        assert leaf.ndim == len(names)


def test_forward_matches_the_plain_reference():
    """Two rows of 150 positions: more than one chunk of the chunked form,
    against the reference's scan over positions."""
    params, toks = _params(), jnp.asarray(_tokens(150, batch=2))
    got, _ = forward(params, toks, dataclasses.replace(CFG, max_seq_len=256))
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(params, toks, MODEL)), atol=5e-5, rtol=1e-5)


def _without(params, leaf):
    kinds = dict(params["kind_layers"])
    kinds["mamba"] = {**kinds["mamba"], leaf: jnp.zeros_like(kinds["mamba"][leaf])}
    return {**params, "kind_layers": kinds}


@pytest.mark.parametrize("what", ["embed_multiplier", "residual_multiplier", "attention_multiplier", "logits_divisor",
                                  "tie_embeddings", "d_skip", "conv_bias"])
def test_what_the_tolerance_tells_apart(what):
    """Each of the four scalars at its default, the head untied, and the skip
    or the convolution's bias left out, moves the forward's logits away from
    the reference's by 5e-3 or more, thousands of times SERVED."""
    params, toks = _params(), jnp.asarray(_tokens(60, batch=1))
    want = np.asarray(ref.logits(params, toks, MODEL))
    if what in ("d_skip", "conv_bias"):
        got, _ = forward(_without(params, what), toks, CFG)
    else:
        default = {f.name: f.default for f in dataclasses.fields(TransformerConfig)}[what]
        cfg = dataclasses.replace(CFG, **{what: default})
        if what == "tie_embeddings":  # a head of its own beside the same trunk
            params = {**params, "lm_head": init_params(jax.random.PRNGKey(0), cfg)["lm_head"]}
        got, _ = forward(params, toks, cfg)
    assert float(np.abs(np.asarray(got) - want).max()) > 5e-3


def test_a_packed_batch_is_refused_loudly():
    batch = {"tokens": jnp.asarray(_tokens(17, batch=1)), "segment_ids": jnp.zeros((1, 17), jnp.int32)}
    with pytest.raises(NotImplementedError, match="packed sequences are not written for a ssd layer.*ROADMAP M4"):
        cross_entropy_loss(_params(), batch, CFG)


# ---------------------------------------------------------------------------
# the served path: logits, position by position
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


@pytest.mark.parametrize("n_prompt", [70, 33, 2])
def test_prefill_then_decode_through_state_and_pages_matches_the_full_forward_f32(n_prompt, logits_spy):
    """25 tokens (the prefill's and 24 decoded) against the reference's full
    forward over prompt + generated tokens: logits, position by position. A
    prompt of 70 is padded to a bucket of 80 (padding the state must not see),
    one of 33 is a page and a row of the next, one of 2 is shorter than the
    convolution's reach. float32 throughout, to SERVED."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    toks = eng.generate(prompt, max_tokens=25)["tokens"]
    jax.effects_barrier()
    got = np.stack([r[0] for r in logits_spy][:25]).astype(np.float32)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL))[0, n_prompt - 1:]
    assert got.shape == want.shape == (25, CFG.vocab_size)
    np.testing.assert_allclose(got, want, atol=SERVED, rtol=SERVED)
    # the state-space kind's pools are a state and a tail a slot, the shapes the kind gives; the softmax
    # kind's are pages of tokens
    state, tails, k_pages, _ = eng.cache
    assert state.shape == (6, 2, 32, 4 * 16) and state.dtype == jnp.float32 and tails.shape == (6, 2, 3 * 128)
    assert k_pages.shape == (2, 2, eng.ec.total_pages * PS, 16) and k_pages.dtype == jnp.float32
    assert set(eng.pool_bytes) == {"attention", "mamba"}
    assert eng.pool_bytes["mamba"] == 6 * 2 * 32 * 64 * 4 + 6 * 2 * 3 * 128 * 4  # the three inputs end to end in one row


@pytest.mark.parametrize("n_prompt,bucket", [(40, 48), (90, 96)])
def test_a_prompt_on_a_rung_between_doublings_leaves_the_state_of_its_own_length(n_prompt, bucket, logits_spy):
    """Buckets of 3 and of 6 pages, the rungs the engine puts between 32, 64 and
    128: the prefill's token and 12 decoded ones against the reference's full
    forward, so the state and the convolution tail the prefill left are those
    of the prompt's own length, not the bucket's."""
    params, prompt = _params(), _tokens(n_prompt, seed=n_prompt)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (32, 64)}))
    assert eng.buckets == (32, 48, 64, 96, 128)
    toks = eng.generate(prompt, max_tokens=13)["tokens"]
    jax.effects_barrier()
    assert eng.trace_snapshot()["requests"][0]["bucket"] == bucket
    got = np.stack([r[0] for r in logits_spy][:13]).astype(np.float32)
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL))[0, n_prompt - 1:]
    np.testing.assert_allclose(got, want, atol=SERVED, rtol=SERVED)


def test_a_state_rounded_to_bfloat16_misses_the_tolerance(logits_spy):
    """Why the pool is float32, seen from the logits: the state a prefill left
    rounded to bfloat16 once, and the decoded logits miss the reference's by
    more than SERVED."""
    params, prompt = _params(), _tokens(70, seed=70)
    eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "decode_block": 1}))
    eng.add_request("r", prompt, max_tokens=6)
    eng.step()  # the prefill, and one decode step dispatched behind it
    state, *rest = eng.cache
    eng.cache = (state.astype(jnp.bfloat16).astype(jnp.float32), *rest)
    toks = _run(eng)["r"]
    got = np.stack([r[0] for r in logits_spy][2:6]).astype(np.float32)  # the steps dispatched on the rounded state
    full = jnp.asarray([list(prompt) + toks[:-1]])
    want = np.asarray(ref.logits(params, full, MODEL))[0, 70 + 1:70 + 5]
    assert float(np.abs(got - want).max()) > 3 * SERVED


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
        want = np.asarray(ref.logits(params, full, MODEL))[0, n:]
        got = np.stack([r[slot[rid]] for r in decode[:budget[rid] - 1]])
        np.testing.assert_allclose(got, want, atol=SERVED, rtol=SERVED)
    # the third request's tokens are what it gives alone (greedy, float32)
    solo = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    assert done["next"] == solo.generate(prompts["next"], max_tokens=budget["next"])["tokens"]
    full = jnp.asarray([list(prompts["next"]) + done["next"][:-1]])
    want = np.asarray(ref.logits(params, full, MODEL))[0, len(prompts["next"]) - 1:]
    assert [int(t) for t in np.argmax(want, axis=-1)] == done["next"]


def test_one_prompt_through_two_buckets_leaves_the_same_state_and_tail():
    """A prompt of 23 padded to a bucket of 32 and to one of 80: the state and
    the convolution tail its prefill leaves are those of its own length (the
    reference's, computed over the 23 positions alone), whatever the padding."""
    params, prompt = _params(), _tokens(23, seed=9)
    ints = lambda *x: jnp.asarray(x, jnp.int32)
    left = []
    for bucket in (32, 80):
        eng = LLMEngine(CFG, params=params, engine_config=EngineConfig(**{**ENGINE_KW, "prefill_buckets": (bucket,)}))
        padded = np.zeros((1, bucket), np.int32)
        padded[0, :23] = prompt
        cache, _ = eng._prefill(bucket, 1)(
            eng.params, eng.cache, jnp.asarray(padded), ints(23), jnp.zeros((1, bucket // PS), jnp.int32),
            jax.random.PRNGKey(0), jnp.zeros(1), jnp.ones(1), ints(0), ints(1))  # into slot 1
        left.append(cache[:2])
        assert not np.asarray(cache[0][:, 0]).any() and not np.asarray(cache[1][:, 0]).any()  # slot 0 was not written
    for a, b in zip(*left):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-6, rtol=1e-5)
    # the reference's state after 23 positions, layer by layer, from the reference's own hidden states
    state, tails = left[0]
    x = 12.0 * params["embed"][jnp.asarray(prompt)[None]]
    allowed = jnp.tril(jnp.ones((23, 23), bool))[None]
    seen = {"attention": 0, "mamba": 0}
    for kind in MODEL["layer_types"]:
        i = seen[kind]
        seen[kind] += 1
        lp = {k: v[i] for k, v in params["kind_layers"][kind].items()}
        h = ref._norm(x, lp["attn_norm"], 1e-5)
        if kind == "attention":
            x = x + 0.22 * ref.attention(h, lp, allowed, 0.125)
        else:
            _, xs, Bm, Cm, dt, a = ref.mamba_inputs(h, lp, MODEL)
            s = ref.selective_scan(xs, Bm, Cm, dt, a)[1][0]  # [H, P, N]; the pool keeps [N, H x P]
            np.testing.assert_allclose(np.asarray(state[i, 1]), np.asarray(jnp.transpose(s, (2, 0, 1)).reshape(32, 64)),
                                       atol=2e-5, rtol=1e-4)
            u = (h @ lp["w_in"].T)[0, 20:23, 64:-4]  # the convolution's last three inputs
            np.testing.assert_allclose(np.asarray(tails[i, 1]), np.asarray(u).reshape(-1), atol=2e-5, rtol=1e-4)
            x = x + 0.22 * ref.mamba(h, lp, MODEL, 1e-5)
        x = x + 0.22 * ref.ffn(ref._norm(x, lp["ffn_norm"], 1e-5), lp)


def test_an_empty_slots_state_is_bit_for_bit_what_it_was_after_decode_blocks():
    """Slot 1 never holds a request: decode blocks on slot 0 leave its state
    and its tail (set to a pattern first) bit for bit, and the step record
    counts one rewritten state a step, not two."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    state, tails, k_pages, v_pages = eng.cache
    marked = (state.at[:, 1].set(jnp.arange(64, dtype=jnp.float32)), tails.at[:, 1].set(0.5))
    eng.cache = (*marked, k_pages, v_pages)
    want = [np.asarray(a[:, 1]) for a in marked]
    eng.generate(_tokens(20, seed=4), max_tokens=13)
    for got, a in zip(eng.cache[:2], want):
        assert (np.asarray(got[:, 1]) == a).all()
    assert np.asarray(eng.cache[0][:, 0]).any()  # slot 0's moved
    steps = eng.trace_snapshot()["steps"]
    blocks = [s for s in steps if s["block"]]
    assert blocks and all(s["state_rows"] == s["block"] * 1 == s["block"] * s["active"] for s in blocks)
    assert sum(s["n_prefill"] for s in steps) == 1


def test_admission_budgets_pages_for_the_layers_that_keep_every_token():
    """A request's pages are its tokens over the page size, whatever the
    state-space layers are: their state does not grow with the context."""
    eng = LLMEngine(CFG, params=_params(), engine_config=EngineConfig(**ENGINE_KW))
    dense = dataclasses.replace(CFG, layer_pattern=(), n_layers=2)
    other = LLMEngine(dense, engine_config=EngineConfig(**ENGINE_KW))
    assert eng._pages_needed(70, 20) == other._pages_needed(70, 20) == -(-(70 + 20 + 4) // PS)
    assert eng.pool_bytes["attention"] == 2 * 2 * 2 * eng.ec.total_pages * PS * 16 * 4  # two layers of eight hold pages


def test_pool_rows_wider_than_a_head_give_the_same_tokens(monkeypatch):
    """A paged pool whose rows are wider than a head (on a TPU: whole lane
    tiles, so that the paged kernel takes the pool as it lies): prefill pads
    the rows it writes, the paged call pads q and the token's rows and keeps
    the head's own scale, and the tokens are those of the narrow pool."""
    params, prompt = _params(), _tokens(37, seed=5)
    narrow = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    want = narrow.generate(prompt, max_tokens=14)["tokens"]
    monkeypatch.setattr(cache_rules, "kv_row_width", lambda head_dim: 2 * head_dim)
    wide = LLMEngine(CFG, params=params, engine_config=EngineConfig(**ENGINE_KW))
    assert wide.cache[2].shape[-1] == 32 and narrow.cache[2].shape[-1] == 16
    assert wide.generate(prompt, max_tokens=14)["tokens"] == want
    assert not np.asarray(wide.cache[2][..., 16:]).any() and np.asarray(wide.cache[2][..., :16]).any()


# ---------------------------------------------------------------------------
# every other model's program is what it was
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
_EXPERTS = dict(n_experts=8, experts_held=4, expert_d_ff=16, n_shared_experts=1, router_score="sigmoid",
                attention_impl="reference")
# the toy forms of the configurations the benchmark had before this kind, each with the primitives of its forward's
# jaxpr as the commit before this kind counted them (sha1 of the sorted counts, and their sum); the three with held
# experts as PR 60 left them (a routed layer's two copies are two custom-VJP calls and the row plan divides by
# ``lax.div``: 8 primitives fewer a routed layer, the arithmetic what it was)
OTHER_MODELS = {
    "dense_f32": (TransformerConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=48,
                                    max_seq_len=128, **_F32), "81b910a0bb0b", 129),
    "dense_bf16": (TransformerConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=4, n_kv_heads=2, d_ff=48,
                                     max_seq_len=128), "507cc76c6bd9", 153),
    "latent_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=3, n_heads=4, d_ff=48, max_seq_len=128, attention_kind="latent",
        q_lora_rank=16, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8, sandwich_norm=True,
        n_dense_layers=1, expert_top_k=2, **_EXPERTS, **_F32), "28038231f2d1", 675),
    "window_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=5, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
        layer_pattern=(_FULL, _SLIDING, _SLIDING, _SLIDING), attn_gate="per_head", n_dense_layers=1, expert_top_k=2,
        **_EXPERTS, **_F32), "e1e8af932c33", 2203),
    "delta_experts": (TransformerConfig(
        vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=128,
        layer_pattern=(_GQA, _KDA, _KDA, _KDA), attn_gate="elementwise", expert_top_k=3, first_expert=2, norm_eps=1e-5,
        **_EXPERTS, **_F32), "9f94454d8ec2", 3014),
}


@pytest.mark.parametrize("name", sorted(OTHER_MODELS))
def test_the_defaults_leave_another_models_forward_the_program_it_was(name):
    """The four scalars and the tie at their defaults add no operation to a
    model without them: the forward's jaxpr holds the primitives it held
    before this kind was written, count by count (a platform's arithmetic is
    then bit for bit what it was: compared once on this machine, on logits,
    weights and served tokens, CHANGES.md PR 46), and the weights' tree has
    its own head."""
    cfg, digest, total = OTHER_MODELS[name]
    params = init_params(jax.random.PRNGKey(3), cfg)
    assert "lm_head" in params and params["embed"].shape == (96, 32)
    toks = jnp.asarray(_tokens(40, batch=2))
    counts = _primitives(jax.make_jaxpr(lambda p, t: forward(p, t, cfg))(params, toks).jaxpr, collections.Counter())
    assert sum(counts.values()) == total
    assert hashlib.sha1(repr(sorted(counts.items())).encode()).hexdigest()[:12] == digest


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference, and its key mapping
# ---------------------------------------------------------------------------

def _bench_architecture():
    path = os.path.join(ROOT, "benchmarks", "architectures", "granite_hybrid.py")
    spec = importlib.util.spec_from_file_location("bench_granite_hybrid", path)
    bench = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(bench)
    return bench


PUBLISHED = dict(
    MODEL, hidden_size=32, head_dim=16, num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
    shared_intermediate_size=48, num_hidden_layers=8, vocab_size=96, max_position_embeddings=128,
    position_embedding_type="nope", num_local_experts=0, attention_bias=False, mamba_proj_bias=False,
    mamba_conv_bias=True, mamba_d_conv=4, mamba_expand=2, tie_word_embeddings=True,
    transformer=dict(dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="reference"))


def test_the_benchmarks_copy_and_the_repos_reference_give_equal_logits():
    bench = _bench_architecture()
    params, toks = _params(), jnp.asarray(_tokens(70, batch=2, seed=4))
    with jax.default_matmul_precision("highest"):  # as the serve check calls it
        got = bench.logits(params, toks, PUBLISHED)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref.logits(params, toks, MODEL)), atol=1e-5, rtol=1e-5)


def test_the_benchmarks_key_mapping_builds_this_configuration():
    """The published keys -> the TransformerConfig the tests above run."""
    assert TransformerConfig(**_bench_architecture().transformer_kwargs(PUBLISHED)) == CFG


def test_the_published_configuration_is_whole_and_counts_3_19_g_parameters():
    """benchmarks/configs/granite-4.0-h-micro.json through the key mapping: 40
    layers in four periods of ten, attention at 5, 15, 25, 35; the tree's
    shapes (nothing allocated) count what the architecture file counts."""
    bench = _bench_architecture()
    with open(os.path.join(ROOT, "benchmarks", "configs", "granite-4.0-h-micro.json")) as f:
        model = json.load(f)
    assert model["reduced"] == []
    cfg = TransformerConfig(**bench.transformer_kwargs(model))
    assert cfg.n_layers == 40 and len(cfg.layer_pattern) == 10 and cfg.n_periods == 4
    assert [l for l in range(40) if not cfg.kind_of(l).recurrent] == [5, 15, 25, 35]
    mamba = cfg.kind_of(0)
    assert (mamba.n_heads, mamba.head_width, mamba.state_size, mamba.n_groups, mamba.conv_size) == (64, 64, 128, 1, 4)
    assert (cfg.embed_multiplier, cfg.residual_multiplier, cfg.attention_multiplier, cfg.logits_divisor) == (
        12.0, 0.22, 0.015625, 8.0) and cfg.tie_embeddings and jnp.dtype(cfg.param_dtype) == jnp.bfloat16
    cfg = dataclasses.replace(cfg, param_dtype=jnp.bfloat16)  # the file says it by name
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    total = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
    counts = bench.param_counts(model)
    assert total == counts["total"] == 3_191_396_096
    assert counts["lm_head"] == 0 and counts["matmul"] == counts["resident_matmul"]
    assert bench.decode_kernels(model) == {"paged_attn": 4, "ssd_step": 36}


# ---------------------------------------------------------------------------
# what is refused, each with a message that names the mechanism
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("engine_kw,message", [
    (dict(prefix_cache=True), "prefix_cache is not written for ssd layers: a hit copies pages.*ROADMAP M4"),
    (dict(chunked_prefill=16), "chunked_prefill is not written for ssd layers: a chunk would have to start.*ROADMAP M4"),
    (dict(tensor_parallel=2), "tensor_parallel > 1 is not written for ssd layers: the state pool.*ROADMAP M4"),
])
def test_the_engine_refuses_what_a_state_cannot_do(engine_kw, message):
    with pytest.raises(ValueError, match=message):
        LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW, **engine_kw))


def test_an_ssd_kind_without_its_sizes_and_a_window_beside_it_are_refused():
    with pytest.raises(AssertionError, match="an ssd layer has a short convolution, a head width, a state size"):
        dataclasses.replace(CFG, layer_pattern=(ATT, LayerKind("mamba", 4, mixer="ssd", conv_size=4)))
    with pytest.raises(AssertionError, match="groups that divide its heads"):
        dataclasses.replace(CFG, layer_pattern=(ATT, dataclasses.replace(SSD, n_groups=3)))
    sliding = LayerKind("sliding", 4, window=32)
    with pytest.raises(ValueError, match="window layers beside ssd layers are not written"):
        LLMEngine(dataclasses.replace(CFG, n_layers=3, layer_pattern=(ATT, sliding, SSD)),
                  engine_config=EngineConfig(**ENGINE_KW))
