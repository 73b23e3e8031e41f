"""The selective state-space recurrence with a scalar decay a head (Mamba-2's
state-space duality), for TPU: a chunked call for prompts and a one-token call
for decode.

A head of width P keeps a state S [P, N] in float32 and no token rows. A token
with input x [P], step size dt >= 0, log decay g <= 0 (both scalars a head),
and the vectors B and C [N] that the heads of its group share turns it into

    S <- exp(g) S + dt x B^T;   y = S C

A position with dt 0 and g 0 leaves the state as it was: how a caller masks
the padding behind a prompt's length. The skip D x, the gate and the norm are
the mixer's (models/transformer.py), not the rule's.

How a slot's state lies: ``[N, H x P]``, the transposed states of all heads
side by side along the lanes. A head of 64 columns then fills no lane tile
half, the step's x, dt and y are rows [1, H x P] as the mixer has them, B and
C are one column [N, 1] for every head of a group, and no operation of either
kernel cuts a lane tile: they see columns, told apart by head only where the
decay and dt are spread over a head's columns.

``ssd_chunk`` takes a prompt CHUNK positions of several heads a grid step
(matrix products). With a the running sum of g inside the chunk,

    y_t = e^(a_t) C_t S0 + sum_(s <= t) (C_t . B_s) e^(a_t - a_s) dt_s x_s
    S1  = e^(a_last) S0 + sum_s e^(a_last - a_s) dt_s B_s x_s^T

a_t - a_s <= 0 for s <= t, so every factor is at most 1 and is computed as it
stands (masked before the exponential). C S0 and the state's update are one
product each for all the step's heads (N deep); the middle term is a product
[CHUNK, CHUNK] x [CHUNK, lane tile] a head, the lane tile's other heads'
columns zeroed (a 64-wide operand would take the MXU as long). The running sum
and the two layouts of it a head's table needs (a column and a row) are made
outside the call by XLA: they are [S, H] float32. Products take their operands
in x's dtype (bfloat16: one pass of the MXU, float32 sums; float32: the
highest precision), the decays and the state are float32. CHUNK is 128, a lane
tile and the engine's page: a model's own chunk size is a hint to a kernel,
and no part of the function.

``ssd_step`` takes one token a live slot (bound by reading and writing a
slot's N x H x P float32 a layer): the state pool [L, slots, N, H x P] stays in
HBM, the layer is an operand of the index maps, the pool is aliased to the
output, and the grid is the live slots' (a runtime value): a slot without a
request has no step, and its state is bit for bit what it was.

Both kernels are written for one group (B and C shared by all heads); the
``jax.numpy`` forms beside them take any: ``ssd_chunk_reference`` runs the
chunk's arithmetic a sequence at a time under ``vmap``, ``ssd_scan_reference``
is the rule a position at a time, and other backends run them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.linear_attention import _column, _needs_tpu

CHUNK = 128  # positions a grid step of ssd_chunk
HEADS_A_CHUNK = 32  # heads of one chunk a grid step of ssd_chunk, at most
LANES = 128
COLUMNS_A_PASS = 512  # columns of a slot's state ssd_step holds in registers at a time
F32 = jnp.float32
NEVER = -1e30  # under the exponential: a position that is not behind this one


def state_shape(heads: int, width: int, state_size: int) -> tuple:
    """A slot's state as both calls keep it: [N, H x P]."""
    return (state_size, heads * width)


def _initial(state, B, H, P, N):
    return jnp.zeros((B, *state_shape(H, P, N)), F32) if state is None else state.astype(F32)


def _by_head(a, H):
    """B or C [..., G, N] as the heads read it: [..., H, N], head h its group h // (H / G)'s."""
    return jnp.repeat(a, H // a.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# Reference implementations (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def ssd_scan_reference(x, Bm, Cm, g, dt, state=None):
    """The rule a position at a time, float32. x: [B, S, H, P]; Bm, Cm:
    [B, S, G, N]; g, dt: [B, S, H]; state: [B, N, H x P] or None (zeros)
    -> (y [B, S, H, P], the state after the last position)."""
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    s0 = _initial(state, B, H, P, N).reshape(B, N, H, P)

    def one(s, at):
        x_t, b_t, c_t, g_t, dt_t = at
        s = s * jnp.exp(g_t)[:, None, :, None] + jnp.einsum("bhn,bhp->bnhp", b_t, x_t * dt_t[..., None])
        return s, jnp.einsum("bnhp,bhn->bhp", s, c_t, precision="highest")

    f = lambda a: jnp.moveaxis(a.astype(F32), 1, 0)
    s, y = lax.scan(one, s0, (f(x), f(_by_head(Bm, H)), f(_by_head(Cm, H)), f(g), f(dt)))
    return jnp.moveaxis(y, 0, 1), s.reshape(B, N, H * P)


def _chunk_math(x, Bm, Cm, g, dt, s0):
    """One chunk of one sequence, every head: x [Q, H, P], Bm, Cm [Q, G, N],
    g, dt [Q, H], s0 [N, H x P], all float32 -> (y [Q, H, P], s1)."""
    Q, H, P = x.shape
    N = Bm.shape[-1]
    dot = functools.partial(jnp.einsum, precision="highest")
    a = jnp.cumsum(g, axis=0)  # its own position counted
    Bh, Ch = _by_head(Bm, H), _by_head(Cm, H)
    behind = jnp.tril(jnp.ones((Q, Q), bool))
    table = jnp.exp(jnp.where(behind, a.T[:, :, None] - a.T[:, None, :], NEVER))  # [H, t, s]
    xdt = x * dt[..., None]
    s = s0.reshape(N, H, P)
    y = dot("hts,shp->thp", dot("thn,shn->hts", Ch, Bh) * table, xdt)
    y = y + dot("thn,nhp->thp", Ch, s) * jnp.exp(a)[..., None]
    s1 = s * jnp.exp(a[-1])[None, :, None] + dot("shn,shp->nhp", Bh, xdt * jnp.exp(a[-1] - a)[..., None])
    return y, s1.reshape(N, H * P)


def _whole_chunks(ops, chunk):
    """The operands [B, S, ...] padded to whole chunks with positions that
    leave the state alone (dt 0, g 0)."""
    pad = -ops[0].shape[1] % chunk
    if not pad:
        return ops
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in ops)


def ssd_chunk_reference(x, Bm, Cm, g, dt, state=None, *, out_dtype=F32, chunk=CHUNK):
    """``ssd_chunk`` in ``jax.numpy``: the chunk's arithmetic (``_chunk_math``)
    under vmap over sequences, a scan over chunks. Arguments and results as
    ``ssd_scan_reference``; y in `out_dtype`."""
    B, S, H, P = x.shape
    chunk = min(chunk, S)
    ops = _whole_chunks(tuple(a.astype(F32) for a in (x, Bm, Cm, g, dt)), chunk)
    n = ops[0].shape[1] // chunk
    xs = tuple(jnp.moveaxis(a.reshape(B, n, chunk, *a.shape[2:]), 1, 0) for a in ops)  # [n, B, chunk, ...]
    every_sequence = jax.vmap(_chunk_math)

    def one(s, at):
        y, s = every_sequence(*at, s)
        return s, y

    s, y = lax.scan(one, _initial(state, B, H, P, Bm.shape[-1]), xs)  # y [n, B, chunk, H, P]
    return jnp.moveaxis(y, 0, 1).reshape(B, n * chunk, H, P)[:, :S].astype(out_dtype), s


def ssd_step_reference(x, Bm, Cm, g, dt, pool, layer, live):
    """One token a slot, ``jax.numpy``. x: [B, H, P]; Bm, Cm: [B, G, N]; g,
    dt: [B, H]; pool: [L, B, N, H x P] float32, every layer's states; layer:
    which of the L; live: [B] bool -> (y [B, H, P] float32, pool), the states
    of the live slots advanced in place in a donated or loop-carried pool, a
    slot that is not live left as it was and its y zeros."""
    B, H, P = x.shape
    N = Bm.shape[-1]
    f = lambda a: a.astype(F32)
    s = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    s1 = s.reshape(B, N, H, P) * jnp.exp(f(g))[:, None, :, None] + jnp.einsum(
        "bhn,bhp->bnhp", f(_by_head(Bm, H)), f(x) * f(dt)[..., None])
    y = jnp.einsum("bnhp,bhn->bhp", s1, f(_by_head(Cm, H)), precision="highest")
    s1 = jnp.where(live[:, None, None], s1.reshape(s.shape), s)
    pool = lax.dynamic_update_slice(pool, s1[None], (layer, 0, 0, 0))
    return jnp.where(live[:, None, None], y, 0.0), pool


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _one_group(what: str, Bm) -> None:
    if Bm.shape[-2] != 1:
        raise ValueError(f"{what} is written for one group of heads (B and C shared by all of them); "
                         f"these have {Bm.shape[-2]}: ssd_chunk_reference / ssd_step_reference take any")


def _heads_a_chunk(H: int, P: int) -> int:
    """The heads of a grid step of ``ssd_chunk``: H's largest divisor up to
    HEADS_A_CHUNK that is whole sublane tiles of 8 heads (a block of the
    decays' rows) and whole lane tiles of columns, or all of H."""
    return next((n for n in range(min(HEADS_A_CHUNK, H), 7, -1) if H % n == 0 and n % 8 == 0 and n * P % LANES == 0), H)


def _chunk_kernel(x_ref, b_ref, c_ref, dt_ref, a_col_ref, a_row_ref, s0_ref, y_ref, s_ref, s_scr, *, width, pack):
    """Grid (B, H / heads, chunks): chunk ``n`` of ``heads`` heads. x and y
    are blocks [CHUNK, heads x P] of the arrays as the mixer has them, b and
    c [CHUNK, N]; dt and the running sum a [CHUNK, heads] (a head a column),
    a again [heads, CHUNK] (a head a row); ``s_scr`` [N, heads x P] carries
    the heads' states from a chunk to the next. The columns go a lane tile of
    ``pack`` heads at a time."""
    from jax.experimental import pallas as pl

    n = pl.program_id(2)

    @pl.when(n == 0)
    def _first_chunk():
        s_scr[...] = s0_ref[...]

    Q, cols = x_ref.shape
    tile = pack * width  # columns a pass
    mm = x_ref.dtype if x_ref.dtype == jnp.bfloat16 else F32
    precision = None if mm == jnp.bfloat16 else lax.Precision.HIGHEST

    def dot(a, b, dims):
        return lax.dot_general(a.astype(mm), b.astype(mm), (dims, ((), ())), precision=precision,
                               preferred_element_type=F32)

    b, c = b_ref[...], c_ref[...]
    cb = dot(c, b, ((1,), (1,)))  # [t, s]: C_t . B_s, the same for every head
    behind = lax.broadcasted_iota(jnp.int32, (Q, Q), 0) >= lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    head_of = lax.broadcasted_iota(jnp.int32, (1, tile), 1) // width  # a column's head among the pass's

    def spread(rows, first):
        """rows [n, heads] -> [n, tile]: a head's column over that head's columns."""
        out = rows[:, first:first + 1]
        for k in range(1, pack):
            out = jnp.where(head_of == k, rows[:, first + k:first + k + 1], out)
        return jnp.broadcast_to(out, (rows.shape[0], tile))

    for i in range(cols // tile):
        first, lanes = i * pack, slice(i * tile, (i + 1) * tile)
        a = spread(a_col_ref[...], first)
        # the running sum at the chunk's end, [1, tile]: its least, since no g is above 0 (a reduction, where a
        # slice of one row would be folded with a's own broadcast into one over both dimensions, which Mosaic refuses)
        last = jnp.min(a, axis=0, keepdims=True)
        xdt = x_ref[:, lanes].astype(F32) * spread(dt_ref[...], first)
        s = s_scr[:, lanes]
        y = dot(c, s, ((1,), (0,))) * jnp.exp(a)
        for k in range(pack):
            h = first + k
            table = jnp.exp(jnp.where(behind, a_col_ref[:, h:h + 1] - a_row_ref[h:h + 1, :], NEVER))
            y = y + dot(cb * table, jnp.where(head_of == k, xdt, 0.0) if pack > 1 else xdt, ((1,), (0,)))
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        s_scr[:, lanes] = s * jnp.exp(last) + dot(b, xdt * jnp.exp(last - a), ((0,), (0,)))

    @pl.when(n == pl.num_programs(2) - 1)
    def _last_chunk():
        s_ref[...] = s_scr[...]


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("out_dtype", "interpret"))
def ssd_chunk(x, Bm, Cm, g, dt, state=None, *, out_dtype=None, interpret=False):
    """The rule over a prompt, CHUNK positions of several heads a grid step
    (the Pallas kernel; arguments and results as ``ssd_scan_reference``, y in
    `out_dtype` or x's). Grid (B, H / heads, chunks), the chunks in order
    with the heads' states in VMEM between them; x and y are read and written
    where they lie. Runs on a TPU backend, or anywhere with interpret=True,
    and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("ssd_chunk", interpret)
    _one_group("ssd_chunk", Bm)
    B, S, H, P = x.shape
    N = Bm.shape[-1]
    heads = _heads_a_chunk(H, P)
    pack = next(k for k in range(min(heads, max(LANES // P, 1)), 0, -1) if heads % k == 0)  # heads a lane tile
    x, Bm, Cm, g, dt = _whole_chunks((x, Bm, Cm, g.astype(F32), dt.astype(F32)), CHUNK)
    n = x.shape[1] // CHUNK
    a = jnp.cumsum(g.reshape(B, n, CHUNK, H), axis=2).reshape(B, n * CHUNK, H)  # inside a chunk, its own position counted
    by_step = lambda v: jnp.swapaxes(v.reshape(B, n * CHUNK, H // heads, heads), 1, 2)  # [B, H / heads, S, heads]
    cols = heads * P
    rows = lambda: pl.BlockSpec((None, CHUNK, cols), lambda b, j, c: (b, c, j))
    shared = lambda: pl.BlockSpec((None, CHUNK, N), lambda b, j, c: (b, c, 0))
    a_head = lambda: pl.BlockSpec((None, None, CHUNK, heads), lambda b, j, c: (b, j, c, 0))
    whole = lambda: pl.BlockSpec((None, N, cols), lambda b, j, c: (b, 0, j))
    y, s = pl.pallas_call(
        functools.partial(_chunk_kernel, width=P, pack=pack),
        grid=(B, H // heads, n),
        in_specs=[rows(), shared(), shared(), a_head(), a_head(),
                  pl.BlockSpec((None, heads, CHUNK), lambda b, j, c: (b, j, c)), whole()],
        out_specs=[rows(), whole()],
        out_shape=[jax.ShapeDtypeStruct((B, n * CHUNK, H * P), out_dtype or x.dtype),
                   jax.ShapeDtypeStruct((B, N, H * P), F32)],
        scratch_shapes=[pltpu.VMEM((N, cols), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024,  # two blocks of x, y and of the states in and out, beside a pass's tables
        ),
        interpret=interpret,
        name="ssd_chunk",
    )(x.reshape(B, n * CHUNK, H * P), Bm[:, :, 0], Cm[:, :, 0], by_step(dt), by_step(a), jnp.swapaxes(a, 1, 2),
      _initial(state, B, H, P, N))
    # The call's results stand alone: where a consumer stacks a layer's state beside its neighbours' the TPU compiler
    # made the call part of that consumer's fusion, whose instruction no trace reads as a kernel (8 of a period's 9
    # calls; my chip run, PR 46). A state's copy into the stack is 2 MB a layer.
    y, s = lax.optimization_barrier((y, s))
    return y[:, :S].reshape(B, S, H, P), s


def _step_kernel(layer_ref, slots_ref, u_ref, decay_ref, b_ref, c_ref, _pool_in, y_ref, s_ref):
    """Grid (live slots, column blocks): slot ``slots_ref[t]``. ``u_ref``
    (dt x) and ``decay_ref`` (e^g, a head's over its columns) [1, columns];
    b and c [1, N]; ``s_ref`` [N, columns]: the slot's state, in the block of
    the pool that the input block aliases."""
    b, c = _column(b_ref[...]), _column(c_ref[...])  # [N, 1]
    cols = s_ref.shape[1]
    a_pass = min(COLUMNS_A_PASS, cols)
    for at in range(0, cols, a_pass):
        lanes = slice(at, at + a_pass)
        s = _pool_in[:, lanes] * decay_ref[:, lanes] + b * u_ref[:, lanes]
        s_ref[:, lanes] = s
        y_ref[:, lanes] = jnp.sum(s * c, axis=0, keepdims=True)


def _columns_a_step(M: int, N: int) -> int:
    """The columns of a slot's state a grid step of ``ssd_step`` takes: all,
    or the largest whole passes that divide them with 2 MB of state a block."""
    limit = max((2 << 20) // (4 * N), COLUMNS_A_PASS)
    if M <= limit:
        return M
    return next((n for n in range(limit // COLUMNS_A_PASS * COLUMNS_A_PASS, 0, -COLUMNS_A_PASS) if M % n == 0), M)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def ssd_step(x, Bm, Cm, g, dt, pool, layer, live, *, interpret=False):
    """One token a live slot (the Pallas kernel; arguments and results as
    ``ssd_step_reference``). The pool is aliased to the call's output and
    only the live slots' blocks of layer ``layer`` move; a slot that is not
    live takes no grid step."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("ssd_step", interpret)
    _one_group("ssd_step", Bm)
    B, H, P = x.shape
    N, M = pool.shape[-2:]
    cols = _columns_a_step(M, N)
    row = lambda v: v.astype(F32).reshape(B, 1, -1)
    u = row(x.astype(F32) * dt.astype(F32)[..., None])
    decay = row(jnp.repeat(jnp.exp(g.astype(F32)), P, axis=-1))
    # the live slots first (a stable sort on one bit), and how many they are
    slots = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32).reshape(1)
    a_row = lambda W: pl.BlockSpec((None, 1, W), lambda t, j, layer, slots: (slots[t], 0, 0))
    a_block = lambda: pl.BlockSpec((None, 1, cols), lambda t, j, layer, slots: (slots[t], 0, j))
    state = lambda: pl.BlockSpec((None, None, N, cols), lambda t, j, layer, slots: (layer[0], slots[t], 0, j))
    y, pool = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(count[0], M // cols),  # the first a runtime value
            in_specs=[a_block(), a_block(), a_row(N), a_row(N), state()],
            out_specs=[a_block(), state()],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, 1, M), F32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={6: 1},  # operands count the two scalar-prefetch arrays
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024,  # two blocks of state in, two out
        ),
        interpret=interpret,
        name="ssd_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, u, decay, row(Bm), row(Cm), pool)
    # a slot that is not live had no step and its row was never written
    return jnp.where(live[:, None, None], y.reshape(B, H, P), 0.0), pool


def ssd_rule():
    """(over a prompt, one token a slot): the kernels on a TPU backend, their
    ``jax.numpy`` forms elsewhere."""
    if jax.default_backend() == "tpu":
        return ssd_chunk, ssd_step
    return ssd_chunk_reference, ssd_step_reference
