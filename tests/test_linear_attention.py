"""The gated delta rule with a decay a channel (ops/linear_attention.py) at toy
widths on the CPU: the chunk's arithmetic against the rule a position at a
time and the one-token step, the two Pallas kernels in interpret mode, what a
position with beta 0 and no decay leaves alone, an empty slot's state, and how
far a state or decays kept in bfloat16 would be off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import reference_linear_moe as ref
from ray_tpu.ops import linear_attention as la

TOL = 1e-4


def _inputs(B, S, H, K, V, seed=0):
    """q of length K^-1/2 and k of length 1 a head, log decays around
    -e^-2 (a few a sequence near -3), betas over the whole of (0, 2)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q, k = jax.random.normal(ks[0], (B, S, H, K)), jax.random.normal(ks[1], (B, S, H, K))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) / K ** 0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, S, H, V))
    g = -jnp.exp(1.5 * jax.random.normal(ks[3], (B, S, H, K)) - 2.0)
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(ks[4], (B, S, H)))
    return q, k, v, g, beta


def _close(a, b, tol=TOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol)


@pytest.mark.parametrize("S", [3 * la.CHUNK + 37, la.CHUNK, 21])
def test_the_chunked_form_the_scan_and_the_step_agree_in_float32(S):
    """Three chunks and a ragged tail (and one whole chunk, and less than
    one), betas above 1 among them: the chunk's arithmetic, the rule a
    position at a time here and in the plain reference, and the one-token
    step fed a position at a time."""
    q, k, v, g, beta = args = _inputs(2, S, 3, 32, 32, seed=S)
    assert float(beta.max()) > 1.5 and float(beta.min()) < 0.5
    o_scan, s_scan = la.kda_scan_reference(*args)
    o_chunk, s_chunk = la.kda_chunk_reference(*args)
    _close(o_chunk, o_scan), _close(s_chunk, s_scan)
    o_ref, s_ref = ref.delta_rule(*args)
    _close(o_ref, o_scan), _close(s_ref, s_scan)
    pool = jnp.zeros((2, 2, 3, 32, 32), jnp.float32)  # two layers' states; layer 1 is stepped
    live, outs = jnp.ones(2, bool), []
    for t in range(S):
        o, pool = la.kda_step_reference(q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t], pool, 1, live)
        outs.append(o)
    _close(jnp.stack(outs, axis=1), o_scan), _close(pool[1], s_scan)
    assert not np.asarray(pool[0]).any()


def test_a_prompt_continues_from_a_state_and_a_masked_tail_leaves_it_alone():
    """The first 70 positions, then the rest from the state they left, give
    the whole's outputs and state; positions with beta 0 and g 0 behind a
    length change neither (how a bucket's padding is masked)."""
    q, k, v, g, beta = args = _inputs(1, 150, 2, 32, 32, seed=5)
    o_all, s_all = la.kda_chunk_reference(*args)
    o_a, s_a = la.kda_chunk_reference(*(a[:, :70] for a in args))
    o_b, s_b = la.kda_chunk_reference(*(a[:, 70:] for a in args), state=s_a)
    _close(jnp.concatenate([o_a, o_b], axis=1), o_all), _close(s_b, s_all)
    real = (jnp.arange(150) < 70)[None, :, None]
    o_m, s_m = la.kda_chunk_reference(q, k, v, jnp.where(real[..., None], g, 0.0), jnp.where(real, beta, 0.0))
    _close(s_m, s_a, 1e-6), _close(o_m[:, :70], o_a, 1e-6)


def test_strong_decays_underflow_and_do_not_overflow():
    """A channel that decays by e^-2.5 a position (e^-160 a chunk, e^-40 a
    sub-block: the most the repo's weights reach) is exact in the chunked
    form: the split around a sub-block's start keeps every factor finite."""
    q, k, v, g, beta = _inputs(1, 2 * la.CHUNK, 2, 32, 32, seed=7)
    g = -2.5 * jax.random.uniform(jax.random.PRNGKey(8), g.shape) ** 0.25  # most channels near -2.5 a position
    args = (q, k, v, g, beta)
    assert float(g.min()) < -2.45 and float(jnp.sum(g[:, :la.SUB], axis=1).min()) < -30
    o_scan, s_scan = la.kda_scan_reference(*args)
    o_chunk, s_chunk = la.kda_chunk_reference(*args)
    assert np.isfinite(np.asarray(o_chunk)).all()
    _close(o_chunk, o_scan), _close(s_chunk, s_scan)


@pytest.mark.parametrize("S", [la.CHUNK + 9, 2 * la.CHUNK])
def test_the_chunk_kernel_in_interpret_mode_matches_the_scan(S):
    args = _inputs(2, S, 2, 128, 128, seed=11)
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(1), (2, 2, 128, 128))
    o, s = la.kda_chunk(*args, state, interpret=True)
    o_scan, s_scan = la.kda_scan_reference(*args, state)
    assert o.shape == o_scan.shape and o.dtype == jnp.float32
    _close(o, o_scan), _close(s, s_scan)
    assert la.kda_chunk(*args, out_dtype=jnp.bfloat16, interpret=True)[0].dtype == jnp.bfloat16


@pytest.mark.parametrize("H,groups", [(8, 1), (16, 2), (3, 1), (12, 1)],
                         ids=["one_group", "two_groups_of_8", "3_heads_the_limit_does_not_divide", "12_heads_no_whole_tiles"])
def test_the_chunk_kernel_takes_several_heads_a_step_from_the_operands_as_the_mixer_writes_them(H, groups):
    """A grid step is one chunk of ``groups``' worth of heads: q, k and g in
    float32, v in bfloat16 and beta [B, S, H] go in as ``_delta_mixer`` leaves
    them, a state is carried in, and S is no whole number of chunks. Against
    the rule a position at a time on the same (rounded) v; among this many
    draws a decay passes e^-80 a position, outside what the split around a
    sub-block's start takes (the module's docstring), so they stop at e^-4."""
    S = la.CHUNK + 9
    q, k, v, g, beta = _inputs(2, S, H, 128, 128, seed=H)
    v, g = v.astype(jnp.bfloat16), jnp.maximum(g, -4.0)
    assert H // la._heads_a_chunk(H) == groups
    state = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (2, H, 128, 128))
    o, s = la.kda_chunk(q, k, v, g, beta, state, out_dtype=jnp.float32, interpret=True)
    o_scan, s_scan = la.kda_scan_reference(q, k, v, g, beta, state)
    assert o.shape == (2, S, H, 128) and s.shape == state.shape
    _close(o, o_scan), _close(s, s_scan)


def _equations(jaxpr):
    """Equations of a jaxpr, those of its inner jaxprs counted once each."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    n += _equations(inner)
    return n


def test_the_chunk_kernels_body_stays_small_at_every_count_of_heads_a_step():
    """What every start of a replica pays, compile cache or not: a prefill
    program traces and lowers this body once a call, three calls a program,
    so its size is `setup_warmup_s`. PR 42 paid 5 s a call a program for a
    body of over 2,000 equations (the paged kernel's) and was refused for its
    set-up alone; PR 43 got the same gain at 332-340. This body counted 196 at
    commit 8e31e4e (one head a step). The heads of a step are the batch of
    its ``dot_general``s, so the count is the same whatever ``kda_chunk``
    chooses for H: 8 heads a step, or all 12, 3 or 1 of an H that is no whole
    tiles of 8."""
    def body(H):
        seq = jnp.zeros((1, 512, H, 128), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda q, k, v, g, beta: la.kda_chunk(q, k, v, g, beta, out_dtype=v.dtype, interpret=True))(
            seq, seq, seq.astype(jnp.bfloat16), seq, jnp.zeros((1, 512, H), jnp.float32))
        calls = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1 and calls[0].params["grid_mapping"].grid[1] == H // la._heads_a_chunk(H)
        return _equations(calls[0].params["jaxpr"])

    sizes = {H: body(H) for H in (64, 12, 3, 1)}
    assert max(sizes.values()) < 400, sizes
    assert len(set(sizes.values())) == 1, sizes


def _mixer_lines(window, taps, Hk, H):
    """models/transformer.py ``_delta_mixer``'s own lines between its
    projections and its rule as they stood before ``delta_prep`` (commit
    4f75096, `:1198-1203`), kept here word for word."""
    S, Hd, dt = window.shape[1] - taps.shape[0] + 1, window.shape[-1], window.dtype
    taps = taps.astype(jnp.float32)
    y = sum(window[:, j:j + S].astype(jnp.float32) * taps[j] for j in range(taps.shape[0]))
    q, k, v = (y[:, :, i] for i in range(3)) if Hk == H else (y[:, :, :Hk], y[:, :, Hk:2 * Hk], y[:, :, 2 * Hk:])
    q, k, v = jax.nn.silu(q), jax.nn.silu(k), jax.nn.silu(v).astype(dt)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + la.L2_EPS) * Hd ** -0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + la.L2_EPS)
    return q, k, v


PREP_CASES = {
    # Hk, H (Hk = H: three stacked; fewer key heads: one axis of 2 Hk + H), S, T, a tail that is given, dtype
    "stacked_three_blocks_from_a_sequences_start": (4, 4, 3 * la.PREP_BLOCK, 4, False, jnp.bfloat16),
    "stacked_S_no_whole_blocks_behind_a_tail": (4, 4, la.PREP_BLOCK + 5, 4, True, jnp.bfloat16),
    "stacked_S_under_a_block": (3, 3, 21, 4, True, jnp.bfloat16),
    "stacked_S_under_T_minus_1_behind_a_tail": (4, 4, 2, 4, True, jnp.bfloat16),
    "stacked_float32_two_taps": (2, 2, la.PREP_BLOCK + 1, 2, True, jnp.float32),
    "one_axis_two_blocks_from_a_sequences_start": (2, 4, 2 * la.PREP_BLOCK, 4, False, jnp.bfloat16),
    "one_axis_S_no_whole_blocks_behind_a_tail": (2, 8, 2 * la.PREP_BLOCK + 13, 4, True, jnp.bfloat16),
    "one_axis_S_under_T_minus_1_behind_a_tail": (2, 4, 1, 4, True, jnp.bfloat16),
    "one_axis_six_taps": (4, 8, la.PREP_BLOCK + 7, 6, True, jnp.bfloat16),
}


@pytest.mark.parametrize("case", sorted(PREP_CASES))
def test_delta_prep_in_interpret_mode_is_the_mixers_lines_to_the_last_bit(case):
    """``delta_prep`` (interpret mode) and ``delta_prep_reference`` against
    the lines ``_delta_mixer`` had: q, k (float32) and v EQUAL TO THE LAST BIT
    (a zero's sign aside: the lines start their sum of taps at +0), here on
    the CPU, where kernel and lines run the same float32 operations in the
    same order: the T taps oldest first, SiLU, the sum of squares over a
    head's Hd channels, L2_EPS under the root, q times Hd^-1/2 last. That
    holds for bfloat16 inputs, as the serve cells' are: an input times a tap
    is then exact in float32, so it is the same whether a compiler contracts
    it with the sum behind it or not. With float32 inputs that is the
    compiler's choice a fusion, and the one such case is held to 1e-7 of
    heads of length 1. Both window forms, a sequence's start (zeros in front)
    and a tail that is given, S no whole number of blocks, under a block and
    under T - 1."""
    Hk, H, S, T, given, dtype = PREP_CASES[case]
    Hd, B = 128, 2
    heads = (3, H) if Hk == H else (2 * Hk + H,)
    ks = jax.random.split(jax.random.PRNGKey(S + T), 3)
    u = (2.0 * jax.random.normal(ks[0], (B, S, *heads, Hd))).astype(dtype)
    tail = jax.random.normal(ks[1], (B, T - 1, *heads, Hd)).astype(dtype) if given else jnp.zeros((B, T - 1, *heads, Hd), dtype)
    window = jnp.concatenate([tail, u], axis=1)
    taps = (0.5 * jax.random.normal(ks[2], (T, *heads, Hd))).astype(dtype)
    want = _mixer_lines(window, taps, Hk, H)
    for got in (la.delta_prep(window, taps, Hk, interpret=True),
                la.delta_prep_reference(window, taps, Hk)):
        for a, b, heads_out, dt in zip(got, want, (Hk, Hk, H), (jnp.float32, jnp.float32, dtype)):
            assert a.shape == b.shape == (B, S, heads_out, Hd) and a.dtype == b.dtype == dt
            a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32))
            if dtype == jnp.bfloat16:
                np.testing.assert_array_equal(a, b)
            else:
                np.testing.assert_allclose(a, b, atol=1e-7, rtol=1e-6)
    norms = np.linalg.norm(np.asarray(want[1]), axis=-1)
    assert abs(norms - 1).max() < 1e-3 and abs(np.linalg.norm(np.asarray(want[0]), axis=-1) * Hd ** 0.5 - 1).max() < 1e-3
    with pytest.raises(RuntimeError, match="delta_prep needs a TPU backend"):
        la.delta_prep(window, taps, Hk)


def test_delta_preps_body_stays_small_at_both_window_forms():
    """What a warm start pays a prefill program for each delta layer's call
    (ROADMAP S12): the body is one trip of a loop over PREP_ROWS positions,
    q~, k~ and v~ in turn, whatever the heads, the block and the prompt: 97
    equations as counted here (``kda_chunk``'s body: 332-340), bounded a tenth
    above."""
    def body(window, taps, Hk):
        jaxpr = jax.make_jaxpr(lambda w, t: la.delta_prep(w, t, Hk, interpret=True))(window, taps)
        calls = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1 and calls[0].params["grid_mapping"].grid == (1, 4096 // la.PREP_BLOCK)
        return _equations(calls[0].params["jaxpr"])

    bf = jnp.bfloat16
    sizes = {"solar": body(jnp.zeros((1, 4099, 3, 64, 128), bf), jnp.zeros((4, 3, 64, 128), bf), 64),
             "gigachat": body(jnp.zeros((1, 4099, 128, 128), bf), jnp.zeros((4, 128, 128), bf), 32)}
    assert max(sizes.values()) <= 107 and len(set(sizes.values())) == 1, sizes


def test_the_step_kernel_takes_no_step_for_an_empty_slot_and_leaves_its_state_bit_for_bit():
    """Slots 1 and 3 hold no request: the grid is the two live slots' (the
    kernel's scalar-prefetched list leads with them), their states of layer 1
    move as the reference moves them, and every other block of the pool,
    the empty slots' and the other layers', is bit for bit what it was."""
    B, H, K = 4, 4, 128
    q, k, v, g, beta = (a[:, 0] for a in _inputs(B, 1, H, K, K, seed=3))
    pool = jax.random.normal(jax.random.PRNGKey(9), (3, B, H, K, K))
    live = jnp.array([True, False, True, False])
    o_ref, pool_ref = la.kda_step_reference(q, k, v, g, beta, pool, 1, live)
    o, pool_new = la.kda_step(q, k, v, g, beta, pool, 1, live, interpret=True)
    _close(o, o_ref, 1e-5), _close(pool_new, pool_ref, 1e-5)
    assert not np.asarray(o[1]).any() and not np.asarray(o[3]).any()
    untouched = np.ones((3, B), bool)
    untouched[1, [0, 2]] = False
    assert (np.asarray(pool_new)[untouched] == np.asarray(pool)[untouched]).all()
    assert (np.asarray(pool_ref)[untouched] == np.asarray(pool)[untouched]).all()
    with pytest.raises(RuntimeError, match="kda_step needs a TPU backend"):
        la.kda_step(q, k, v, g, beta, pool, 1, live)


def test_a_state_or_decays_in_bfloat16_are_outside_the_tolerance():
    """What the float32 state and decays buy, in the units of the first
    test's tolerance (1e-4): the rule with its decays rounded to bfloat16, and
    with its state rounded to bfloat16 after every position, over 229
    positions: 2.5e-4 and 1.2e-3 of outputs no larger than 0.5, 2.5 and 12
    tolerances off."""
    q, k, v, g, beta = args = _inputs(2, 3 * la.CHUNK + 37, 3, 32, 32, seed=229)
    o_scan, _ = la.kda_scan_reference(*args)
    rounded = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
    o_g, _ = la.kda_scan_reference(q, k, v, rounded(g), beta)

    def one(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t)))[..., None, :]
        s = rounded(s)
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    _, o_s = jax.lax.scan(one, jnp.zeros((2, 3, 32, 32)), tuple(jnp.moveaxis(a, 1, 0) for a in args))
    off_g = float(jnp.abs(o_g - o_scan).max())
    off_s = float(jnp.abs(jnp.moveaxis(o_s, 0, 1) - o_scan).max())
    assert off_g > 2 * TOL and off_s > 10 * TOL, (off_g, off_s)
