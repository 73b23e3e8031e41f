"""The selective state-space recurrence with a scalar decay a head
(ops/ssd.py) at toy widths on the CPU: the chunk's arithmetic against the rule
a position at a time (here and in the plain reference's own scan) and the
one-token step, the two Pallas kernels in interpret mode, what a position with
dt 0 and no decay leaves alone, an initial state, an empty slot's state, heads
in more than one group, and how far a state kept in bfloat16 would be off."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import reference_ssm_hybrid as ref
from ray_tpu.ops import ssd

# float32 sums of up to 300 terms of size one in another order: 2e-4 of the outputs' largest (about 90)
TOL = 2e-4


def _inputs(B, S, H, P, G, N, seed=0, dtype=jnp.float32):
    """x, B and C of size one a column, step sizes softplus(N(-2, 1)) (0.13
    at the median, a few near 2) and a decay exp(-A dt) with A in 1 .. 16 a
    head: some heads forget within a few positions, some keep hundreds."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, S, H, P), dtype)
    Bm, Cm = jax.random.normal(ks[1], (B, S, G, N), dtype), jax.random.normal(ks[2], (B, S, G, N), dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, S, H)) - 2.0)
    g = -jnp.exp(jax.random.uniform(ks[4], (H,), minval=0.0, maxval=np.log(16.0))) * dt
    return x, Bm, Cm, g, dt


def _close(a, b, tol=TOL):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=tol, rtol=tol)


@pytest.mark.parametrize("S", [2 * 32 + 21, 32, 5])
def test_the_chunked_form_the_scan_and_the_step_agree_in_float32(S):
    """Two chunks and a ragged tail (and one whole chunk, and less than one):
    the chunk's arithmetic, the rule a position at a time here and in the
    plain reference, and the one-token step fed a position at a time."""
    x, Bm, Cm, g, dt = args = _inputs(2, S, 4, 16, 1, 32, seed=S)
    y_scan, s_scan = ssd.ssd_scan_reference(*args)
    y_chunk, s_chunk = ssd.ssd_chunk_reference(*args, chunk=32)
    _close(y_chunk, y_scan), _close(s_chunk, s_scan)
    y_ref, s_ref = ref.selective_scan(x, Bm, Cm, dt, jnp.exp(g))  # the state a head [P, N] there, [N, H x P] here
    _close(y_ref, y_scan), _close(jnp.transpose(s_ref, (0, 3, 1, 2)).reshape(s_scan.shape), s_scan)
    pool = jnp.zeros((2, 2, 32, 4 * 16), jnp.float32)  # two layers' states; layer 1 is stepped
    live, outs = jnp.ones(2, bool), []
    for t in range(S):
        y, pool = ssd.ssd_step_reference(x[:, t], Bm[:, t], Cm[:, t], g[:, t], dt[:, t], pool, 1, live)
        outs.append(y)
    _close(jnp.stack(outs, axis=1), y_scan), _close(pool[1], s_scan)
    assert not np.asarray(pool[0]).any()


def test_a_prompt_continues_from_a_state_and_a_masked_tail_leaves_it_alone():
    """The first 70 positions, then the rest from the state they left, give
    the whole's outputs and state; positions with dt 0 and g 0 behind a length
    change neither (how a bucket's padding is masked)."""
    args = _inputs(1, 150, 4, 16, 1, 32, seed=5)
    y_all, s_all = ssd.ssd_chunk_reference(*args, chunk=32)
    y_a, s_a = ssd.ssd_chunk_reference(*(a[:, :70] for a in args), chunk=32)
    y_b, s_b = ssd.ssd_chunk_reference(*(a[:, 70:] for a in args), s_a, chunk=32)
    _close(jnp.concatenate([y_a, y_b], axis=1), y_all), _close(s_b, s_all)
    x, Bm, Cm, g, dt = args
    behind = (jnp.arange(150) < 101)[None, :, None]
    masked = (x, Bm, Cm, jnp.where(behind, g, 0.0), jnp.where(behind, dt, 0.0))
    _, s_masked = ssd.ssd_chunk_reference(*masked, chunk=32)
    _, s_cut = ssd.ssd_chunk_reference(*(a[:, :101] for a in args), chunk=32)
    _close(s_masked, s_cut, 1e-6)  # the same sums: the padding adds exact zeros to them


def test_heads_in_two_groups_read_their_own_b_and_c():
    args = _inputs(2, 45, 4, 16, 2, 32, seed=2)
    y_scan, s_scan = ssd.ssd_scan_reference(*args)
    y_chunk, s_chunk = ssd.ssd_chunk_reference(*args, chunk=16)
    _close(y_chunk, y_scan), _close(s_chunk, s_scan)
    x, Bm, Cm, g, dt = args
    first = ssd.ssd_scan_reference(x[:, :, :2], Bm[:, :, :1], Cm[:, :, :1], g[:, :, :2], dt[:, :, :2])[0]
    _close(y_scan[:, :, :2], first)  # the first group's heads, from the first group's B and C alone
    with pytest.raises(ValueError, match="ssd_chunk is written for one group of heads"):
        ssd.ssd_chunk(*args, interpret=True)


@pytest.mark.parametrize("shape", [(2, 150, 4, 16, 32), (1, 300, 8, 64, 128), (1, 130, 16, 64, 128)],
                         ids=["toy_heads_in_one_lane_tile", "two_heads_a_lane_tile", "a_step_of_16_heads"])
def test_the_chunk_kernel_matches_the_scan_in_interpret_mode(shape):
    """The Pallas kernel (chunks of 128, heads by the lane tile, an initial
    state, a ragged last chunk) against the rule a position at a time."""
    B, S, H, P, N = shape
    args = _inputs(B, S, H, P, 1, N, seed=S)
    s0 = jax.random.normal(jax.random.PRNGKey(9), (B, N, H * P))
    y_scan, s_scan = ssd.ssd_scan_reference(*args, s0)
    y, s = ssd.ssd_chunk(*args, s0, interpret=True)
    assert y.shape == y_scan.shape and y.dtype == jnp.float32 and s.dtype == jnp.float32
    _close(y, y_scan), _close(s, s_scan)


def test_the_chunk_kernel_takes_bfloat16_operands_and_keeps_its_state_in_float32():
    """x, B and C in bfloat16 (one pass of the MXU, decays and sums float32):
    within bfloat16's rounding of the float32 scan over the same rounded
    operands, 2^-8 of the largest output and state."""
    args = _inputs(1, 260, 8, 64, 1, 128, seed=3, dtype=jnp.bfloat16)
    y_scan, s_scan = ssd.ssd_scan_reference(*args)
    y, s = ssd.ssd_chunk(*args, interpret=True)
    assert y.dtype == jnp.bfloat16 and s.dtype == jnp.float32
    assert float(jnp.abs(y.astype(jnp.float32) - y_scan).max()) < float(jnp.abs(y_scan).max()) / 128
    assert float(jnp.abs(s - s_scan).max()) < float(jnp.abs(s_scan).max()) / 256


def test_the_step_kernel_leaves_a_dead_slot_bit_for_bit():
    """Five slots, two without a request, layer 1 of three: the kernel in
    interpret mode against the ``jax.numpy`` step and the scan's one position;
    the dead slots' states and the other layers' are bit for bit what they
    were, their outputs zeros."""
    B, H, P, N = 5, 8, 64, 128
    x, Bm, Cm, g, dt = (a[:, 0] for a in _inputs(B, 1, H, P, 1, N, seed=3))
    pool = jax.random.normal(jax.random.PRNGKey(4), (3, B, N, H * P))
    live = jnp.array([True, False, True, True, False])
    y_ref, pool_ref = ssd.ssd_step_reference(x, Bm, Cm, g, dt, pool, 1, live)
    y, new = ssd.ssd_step(x, Bm, Cm, g, dt, pool, 1, live, interpret=True)
    _close(y, y_ref, 1e-4), _close(new, pool_ref, 1e-6)
    y_scan, s_scan = ssd.ssd_scan_reference(x[:, None], Bm[:, None], Cm[:, None], g[:, None], dt[:, None], pool[1])
    _close(y[live], y_scan[:, 0][live], 1e-4), _close(new[1][live], s_scan[live], 1e-6)
    for dead in (1, 4):
        assert (np.asarray(new[1, dead]) == np.asarray(pool[1, dead])).all() and not np.asarray(y[dead]).any()
        assert (np.asarray(pool_ref[1, dead]) == np.asarray(pool[1, dead])).all()
    assert (np.asarray(new[0]) == np.asarray(pool[0])).all() and (np.asarray(new[2]) == np.asarray(pool[2])).all()


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


def _body(call, *operands):
    """(equations of the one pallas_call's body, its grid) in a call's jaxpr."""
    calls = [eqn for eqn in jax.make_jaxpr(call)(*operands).jaxpr.eqns if eqn.primitive.name == "pallas_call"]
    assert len(calls) == 1
    return _equations(calls[0].params["jaxpr"]), calls[0].params["grid_mapping"].grid


@pytest.mark.parametrize("S", [512, 4096])
def test_the_chunk_kernels_body_does_not_grow_with_the_prompt(S):
    """What a start pays once a shape signature (PR 58: the call is a jit with
    one identity, so the nine equal layers of a period and a bucket's group
    sizes share ONE trace of this body) and every prefill program still pays
    a call in lowering: at `granite`'s widths (64 heads of 64 columns, 128 of
    state, bfloat16) a step takes 32 heads, two to a lane tile, whose 16 tiles
    are unrolled: 1,262 equations as counted here, whatever the prompt, and 338
    at 8 heads a step. Bounded a tenth above."""
    def chunk(H):
        x, bc = jnp.zeros((1, S, H, 64), jnp.bfloat16), jnp.zeros((1, S, 1, 128), jnp.bfloat16)
        h = jnp.zeros((1, S, H), jnp.float32)
        n, grid = _body(lambda x, bc, h: ssd.ssd_chunk(x, bc, bc, h, h, interpret=True), x, bc, h)
        assert grid == (1, H // ssd._heads_a_chunk(H, 64), S // ssd.CHUNK)
        return n

    assert chunk(64) <= 1390 and chunk(8) <= 372


def test_the_step_kernels_body_is_a_pass_a_block_of_columns():
    """A decode block traces this body once a block size: `granite`'s slot
    state (128 x 4,096 float32, 2 MB) is one grid step of 8 passes over 512
    columns, 110 equations as counted here, whatever the slots and the layers;
    a toy state of one pass counts 33."""
    def step(B, H, P, N, L):
        x, bc = jnp.zeros((B, H, P), jnp.bfloat16), jnp.zeros((B, 1, N), jnp.bfloat16)
        h, pool = jnp.zeros((B, H), jnp.float32), jnp.zeros((L, B, N, H * P), jnp.float32)
        n, grid = _body(lambda x, bc, h, pool, layer, live: ssd.ssd_step(x, bc, bc, h, h, pool, layer, live,
                                                                         interpret=True),
                        x, bc, h, pool, jnp.int32(1), jnp.ones(B, bool))
        assert grid[1] == H * P // ssd._columns_a_step(H * P, N)
        return n

    assert step(32, 64, 64, 128, 36) == step(4, 64, 64, 128, 2) <= 121 and step(4, 4, 16, 32, 3) <= 37


def test_the_kernels_refuse_another_backend_without_interpret():
    args = _inputs(1, 8, 4, 16, 1, 32)
    with pytest.raises(RuntimeError, match="ssd_chunk needs a TPU backend"):
        ssd.ssd_chunk(*args)
    with pytest.raises(RuntimeError, match="ssd_step needs a TPU backend"):
        ssd.ssd_step(*(a[:, 0] for a in args), jnp.zeros((1, 1, 32, 64)), 0, jnp.ones(1, bool))
    assert ssd.ssd_rule() == (ssd.ssd_chunk_reference, ssd.ssd_step_reference)  # what the CPU runs


def test_a_state_kept_in_bfloat16_would_be_off_by_more_than_the_tolerance():
    """Why the pool is float32: the same rule with the state rounded to
    bfloat16 after every position misses the float32 scan by far more than
    TOL, so a test at TOL tells the two apart."""
    x, Bm, Cm, g, dt = _inputs(1, 120, 4, 16, 1, 32, seed=7)
    y_scan, _ = ssd.ssd_scan_reference(x, Bm, Cm, g, dt)
    s, off = jnp.zeros((1, 32, 64), jnp.float32), 0.0
    for t in range(120):
        y, s = ssd.ssd_scan_reference(x[:, t:t + 1], Bm[:, t:t + 1], Cm[:, t:t + 1], g[:, t:t + 1], dt[:, t:t + 1], s)
        s = s.astype(jnp.bfloat16).astype(jnp.float32)
        off = max(off, float(jnp.abs(y[:, 0] - y_scan[:, t]).max()))
    assert off > 20 * TOL
