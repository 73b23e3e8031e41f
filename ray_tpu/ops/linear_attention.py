"""The gated delta rule with a decay a channel (Kimi delta attention), for TPU:
a chunked call for prompts, a one-token call for decode, and the call that
prepares a prompt's q, k and v for the first (``kda_chunk``, ``kda_step``,
``delta_prep``).

A head keeps a state S [K, V] in float32 and no token rows. A token with
query q and key k [K], value v [V], log decay g [K] (<= 0) and step size beta
turns it into

    S' = Diag(exp g) S;   S <- S' + beta k (v - S'^T k)^T;   o = S^T q

(the same as S <- (I - beta k k^T) Diag(exp g) S + beta k v^T). beta above 1,
which a model that allows negative eigenvalues reaches, is nothing special
here. A position with beta 0 and g 0 leaves the state as it was: how a
caller masks the padding behind a prompt's length.

``kda_chunk`` takes a prompt 64 positions of several heads a grid step
(compute-bound: matrix products, triangular inside a chunk). With G the
running sum of g inside the chunk, the chunk's pseudo-values U solve
(I + A) U = beta (V - (K e^G) S0), A[i, j] = beta_i sum_c k_ic k_jc e^(G_ic - G_jc)
below the diagonal, and

    o = (Q e^G) S0 + P U,  P[i, j] = sum_c q_ic k_jc e^(G_ic - G_jc), j <= i
    S1 = Diag(e^(G_last)) S0 + (K e^(G_last - G))^T U.

e^(G_i - G_j) is split as e^(G_i - r) e^(r - G_j) around the running sum r at
the start of i's sub-block of 16 rows, so that the first factor is at most 1
for every row and the second at most 1 for every column of an earlier
sub-block: a decay however strong underflows to the 0 it is. Inside a
sub-block the second factor is e^(what the sub-block's earlier rows decayed),
cut at e^80: a channel that decays by more than e^-80 inside 16 positions is
the one case the split gets wrong (the weights this repo makes stay under
e^-40). U is solved a row block of 16 at a time, U_i = (I + D_i)^-1 (R_i -
A_i U) over the blocks before i: the 16 x 16 diagonal blocks D of A are
nilpotent, (I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8), and the four of a
chunk go side by side as one [16, 64] operand, so that a product of the
inverse or of the solve takes 16 rows through the MXU and not 64.

Everything is float32, matrix products at the highest precision (six bfloat16
passes on the MXU; three would leave out 2^-17 of a term, which six delta
layers of a float32 model show, PERF.md section 6, PR 45). The one product
that takes fewer rounds nothing: the running sum is the triangle, exact in
bfloat16, times g's three bfloat16 parts, a pass each.

A grid step of ``kda_chunk`` is one chunk of 8 heads, (B, H / 8, chunks) with
the chunks in order and the heads' states [heads, K, V] in VMEM between them.
q, k, v, g and o are blocks [64, heads, width] of the arrays as the mixer
writes and reads them ([B, S, H, width], whatever their dtypes), turned by
head inside the kernel, and beta [B, S, H] is multiplied in there: nothing is
copied by head in HBM on either side of the call. The heads are a block's
second-minor dimension, so they are whole tiles of 8 or, where H is no
multiple of 8, all of H in one step. They are the batch of every
``dot_general``, so one head's products run while another's wait, and the body
is as long at 8 heads as at 1.

``kda_step`` takes one token a live slot (bound by reading and writing a
slot's 64 x 128 x 128 float32 a layer): the state pool [L, slots, H, K, V]
stays in HBM, the layer is an operand of the index maps, the pool is aliased
to the output, and the grid is the live slots' (a runtime value): a slot
without a request has no step, and its state is bit for bit what it was.

``delta_prep`` is what a delta layer does between its projections and the
rule over a prompt, as ONE pass (bound by reading the window once, 2 bytes a
value, and writing q, k in float32 and v: 201 + 335 MB a layer of 64 heads at
4,096 positions; 1.03 ms a call in the serve cell's prefill programs on a v5e,
where the ``jax.numpy`` lines' three fusions took 3.8, PERF.md section 6, PR
56): the short convolution's T taps, SiLU, q and k normed to length 1 a head. A grid step is a block of
positions of EVERY head, (B, blocks), so the window [B, T - 1 + S, heads..,
Hd] is read where it lies, whichever of its two forms it has (q~, k~, v~
stacked, or along one axis of 2 Hk + H heads), and a position's T inputs are T
rows from its own on: the positions are a major dimension of a block, so a
shift by a tap is an address and no shuffle. The T - 1 rows behind a block
come as a second, small block of the same array. Inside, a loop takes
PREP_ROWS positions a trip through float32 and never holds more: the
convolution's result, which the lines wrote once in float32 and read twice
more, stays in registers.

Each call has its ``jax.numpy`` form beside it (``kda_chunk_reference`` runs
the chunk's own arithmetic, ``_chunk``, a sequence's heads at once under
``vmap``; ``kda_scan_reference`` is the rule a position at a time;
``delta_prep_reference`` is the mixer's own lines, which a decode step's one
position keeps on every backend), which other backends run.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

CHUNK = 64  # positions a grid step of kda_chunk
SUB = 16  # rows of a sub-block, whose decays share a reference point
CLAMP = 80.0  # the largest exponent inside a sub-block
HEADS_A_STEP = 16  # heads of one slot a grid step of kda_step: 1 MB of state in, 1 MB out
HEADS_A_CHUNK = 8  # heads of one chunk a grid step of kda_chunk: a float32 tile's rows, since the heads are a block's second-minor dimension
PREP_BLOCK = 32  # positions of every head a grid step of delta_prep
PREP_ROWS = 4  # positions a trip of the loop inside it: what a trip holds stays near the register file
L2_EPS = 1e-6  # under the root of a delta layer's query and key norms
F32, BF16 = jnp.float32, jnp.bfloat16

_NN = ((2,), (1,))  # a @ b, a head (the leading dimension of both)
_NT = ((2,), (2,))  # a @ b^T
_TN = ((1,), (1,))  # a^T @ b


def _pass(a, b, dims):
    """One pass of the MXU a head: bfloat16 operands, float32 sums."""
    return lax.dot_general(a, b, (dims, ((0,), (0,))), preferred_element_type=F32)


def _dot(a, b, dims=_NN):
    """a @ b a head, float32 at the highest precision (six passes on the MXU)."""
    return lax.dot_general(a, b, (dims, ((0,), (0,))), precision=lax.Precision.HIGHEST, preferred_element_type=F32)


def _column(row):
    """row [..., 1, n] -> [..., n, 1] without a transpose: the diagonal of its
    broadcast, summed along the lanes."""
    n = row.shape[-1]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=-1, keepdims=True)


def _chunk(q, k, v, g, beta, s0):
    """One chunk of N heads: q, k, g [N, C, K] float32, v [N, C, V], beta
    [N, C, 1], s0 [N, K, V] float32 -> (o [N, C, V], s1 [N, K, V]), float32.
    Every product is a ``dot_general`` with the heads as its batch, so the
    heads' chains are independent of each other inside one body. Written with
    what both ``jax.numpy`` under vmap and a Mosaic kernel's body can run."""
    N, C, K = k.shape
    sub = min(SUB, C)
    blocks = C // sub
    kb, vb = k * beta, v.astype(F32) * beta
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    # the running sum, its own position counted: the triangle is exact in bfloat16 and g is its three parts
    g_hi = g.astype(BF16)
    g_mid = (g - g_hi.astype(F32)).astype(BF16)
    g_lo = (g - g_hi.astype(F32) - g_mid.astype(F32)).astype(BF16)
    tri = jnp.broadcast_to((row >= col).astype(BF16), (N, C, C))
    G = _pass(tri, g_hi, _NN) + (_pass(tri, g_mid, _NN) + _pass(tri, g_lo, _NN))
    # a sub-block's reference point: the sum before its first row
    refs = [jnp.zeros((N, 1, K), F32)] + [G[:, i - 1:i] for i in range(sub, C, sub)]
    near = jnp.exp(G - jnp.concatenate([jnp.broadcast_to(r, (N, sub, K)) for r in refs], axis=1))  # at most 1
    # row block i of A and P against the columns up to its own: [N x blocks, 2 sub, K] x [N x blocks, C, K]
    by_block = lambda a: a.reshape(N * blocks, sub, K)
    lhs = jnp.concatenate([by_block(kb * near), by_block(q * near)], axis=1)
    far = []
    for i, ref in enumerate(refs):
        n = (i + 1) * sub  # the rows a sub-block keeps
        far.append(k[:, :n] * jnp.exp(jnp.minimum(ref - G[:, :n], CLAMP)))
        if n < C:
            far[i] = jnp.concatenate([far[i], jnp.zeros((N, C - n, K), F32)], axis=1)
    both = _dot(lhs, jnp.stack(far, axis=1).reshape(N * blocks, C, K), _NT).reshape(N, blocks, 2 * sub, C)
    A = jnp.where(row > col, both[:, :, :sub].reshape(N, C, C), 0.0)
    P = jnp.where(row >= col, both[:, :, sub:].reshape(N, C, C), 0.0)
    # (I + D)^-1 of A's diagonal blocks D, which are nilpotent: (I - D)(I + D^2)(I + D^4) ..., D^sub = 0. A matrix
    # of diagonal blocks alone goes as [N, sub, C], its blocks side by side, and a product from it takes sub rows
    own = row // sub == col // sub
    beside = lambda X: functools.reduce(jnp.add, [X[:, i:i + sub] for i in range(0, C, sub)])
    diagonal = lambda X: jnp.where(own, jnp.concatenate([X] * blocks, axis=1), 0.0)
    Xd = jnp.where(own, A, 0.0)
    T, X, n = beside((row == col).astype(F32) - Xd), beside(Xd), 2
    while n < sub:
        X = _dot(X, Xd)
        Xd = diagonal(X)
        T = T + _dot(T, Xd)
        n *= 2
    decayed = jnp.exp(G)
    from_s0 = _dot(jnp.concatenate([kb * decayed, q * decayed], axis=1), s0)  # [N, 2 C, V]
    # (I + A) U = R a row block at a time, U_i = T_i (R_i - A_i U) with the blocks before i in U and zeros after
    # them; a block's rows stand at their own place among zeros, where T's blocks side by side meet their own
    R = vb - from_s0[:, :C]
    zeros = lambda rows: [jnp.zeros((N, rows, R.shape[2]), F32)] if rows else []
    placed = lambda x, i: jnp.concatenate(zeros(i) + [x] + zeros(C - sub - i), axis=1)
    U = placed(_dot(T, placed(R[:, :sub], 0)), 0)
    for i in range(sub, C, sub):
        U = U + placed(_dot(T, placed(R[:, i:i + sub] - _dot(A[:, i:i + sub], U), i)), i)
    o = from_s0[:, C:] + _dot(P, U)
    last = G[:, C - 1:C]
    s1 = s0 * _column(jnp.exp(last)) + _dot(k * jnp.exp(last - G), U, _TN)
    return o, s1


def _whole_chunks(q, k, v, g, beta, chunk):
    """The operands as they are, padded to whole chunks with positions that
    leave the state alone (beta 0, g 0)."""
    pad = -q.shape[1] % chunk
    if not pad:
        return q, k, v, g, beta
    return tuple(jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)) for a in (q, k, v, g, beta))


def _initial(state, B, H, K, V):
    return jnp.zeros((B, H, K, V), F32) if state is None else state.astype(F32)


def _heads_a_step(H, limit):
    """The most heads a grid step takes: H's largest divisor up to `limit`."""
    return next(n for n in range(min(limit, H), 0, -1) if H % n == 0)


def _heads_a_chunk(H):
    """The heads of a grid step of ``kda_chunk``, the second-minor dimension
    of its blocks [CHUNK, heads, width]: whole tiles of 8 rows, or all of H
    where those do not divide it (a block may span a whole dimension whatever
    its length)."""
    return HEADS_A_CHUNK if H % HEADS_A_CHUNK == 0 else H


# ---------------------------------------------------------------------------
# Reference implementations (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def kda_scan_reference(q, k, v, g, beta, state=None):
    """The rule a position at a time, float32. q, k, g: [B, S, H, K]; v:
    [B, S, H, V]; beta: [B, S, H]; state: [B, H, K, V] or None (zeros)
    -> (o [B, S, H, V], the state after the last position)."""
    B, S, H, K = q.shape
    s0 = _initial(state, B, H, K, v.shape[-1])

    def one(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t, precision="highest"))
        s = s + k_t[..., None] * u[..., None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t, precision="highest")

    xs = tuple(jnp.moveaxis(a.astype(F32), 1, 0) for a in (q, k, v, g, beta))
    s, o = lax.scan(one, s0, xs)
    return jnp.moveaxis(o, 0, 1), s


def kda_chunk_reference(q, k, v, g, beta, state=None, *, out_dtype=F32, chunk=CHUNK):
    """``kda_chunk`` in ``jax.numpy``: the chunk's arithmetic (``_chunk``, every
    head of a sequence at once) under vmap over sequences, a scan over chunks.
    Arguments and results as ``kda_scan_reference``; o in `out_dtype`."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    chunk = min(chunk, -(-S // SUB) * SUB)
    ops = _whole_chunks(q.astype(F32), k.astype(F32), v, g.astype(F32), beta.astype(F32)[..., None], chunk)
    n = ops[0].shape[1] // chunk
    xs = tuple(jnp.transpose(a.reshape(B, n, chunk, H, a.shape[-1]), (1, 0, 3, 2, 4)) for a in ops)  # [n, B, H, chunk, W]
    every_sequence = jax.vmap(_chunk)

    def one(s, x):
        o, s = every_sequence(*x, s)
        return s, o

    s, o = lax.scan(one, _initial(state, B, H, K, V), xs)  # o [n, B, H, chunk, V]
    o = jnp.transpose(o, (1, 0, 3, 2, 4)).reshape(B, n * chunk, H, V)[:, :S]
    return o.astype(out_dtype), s


def kda_step_reference(q, k, v, g, beta, pool, layer, live):
    """One token a slot, ``jax.numpy``. q, k, g: [B, H, K]; v: [B, H, V];
    beta: [B, H]; pool: [L, B, H, K, V] float32, every layer's states; layer:
    which of the L; live: [B] bool -> (o [B, H, V] float32, pool), the states
    of the live slots advanced in place in a donated or loop-carried pool, a
    slot that is not live left as it was and its o zeros."""
    s = lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)
    f = lambda a: a.astype(F32)
    s1 = s * jnp.exp(f(g))[..., None]
    u = f(beta)[..., None] * (f(v) - jnp.einsum("bhkv,bhk->bhv", s1, f(k), precision="highest"))
    s1 = s1 + f(k)[..., None] * u[..., None, :]
    o = jnp.einsum("bhkv,bhk->bhv", s1, f(q), precision="highest")
    s1 = jnp.where(live[:, None, None, None], s1, s)
    pool = lax.dynamic_update_slice(pool, s1[None], (layer, 0, 0, 0, 0))
    return jnp.where(live[:, None, None], o, 0.0), pool


def _prep_parts(window, key_heads):
    """(Hk, H, what picks q~, k~ and v~ out of the head axes of a window or of
    its taps): the middle one of [.., 3, H, Hd], or ranges of the one axis of
    2 Hk + H heads in [.., 2 Hk + H, Hd]."""
    if window.ndim == 5:
        return window.shape[3], window.shape[3], tuple((i,) for i in range(3))
    Hk, G = key_heads, window.shape[2]
    return Hk, G - 2 * Hk, ((slice(0, Hk),), (slice(Hk, 2 * Hk),), (slice(2 * Hk, G),))


def _unit_heads(y):
    """y [..., Hd] float32, each head divided by its length."""
    return y * lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + L2_EPS)


def delta_prep_reference(window, taps, key_heads):
    """What a delta layer does between its projections and its rule, in
    ``jax.numpy``: the short convolution, SiLU and the head norms. window: the
    convolution's inputs q~, k~, v~ behind the T - 1 before position 0,
    [B, T - 1 + S, 3, H, Hd], or [B, T - 1 + S, 2 Hk + H, Hd] where
    `key_heads` = Hk key heads serve H value heads; taps [T, ...] alike, the
    oldest input's first -> (q, k [B, S, Hk, Hd] float32, each head of length
    1 and q times Hd^-1/2 besides; v [B, S, H, Hd] in the window's dtype)."""
    T, S, Hd = taps.shape[0], window.shape[1] - taps.shape[0] + 1, window.shape[-1]
    taps = taps.astype(F32)
    y = sum(window[:, j:j + S].astype(F32) * taps[j] for j in range(T))
    q, k, v = (y[(slice(None), slice(None), *part)] for part in _prep_parts(window, key_heads)[2])
    q, k, v = jax.nn.silu(q), jax.nn.silu(k), jax.nn.silu(v).astype(window.dtype)
    return _unit_heads(q) * Hd ** -0.5, _unit_heads(k), v


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _needs_tpu(what: str, interpret: bool) -> None:
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(f"{what} needs a TPU backend (or interpret=True); this process runs on "
                           f"{jax.default_backend()!r}")


def _chunk_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, s0_ref, o_ref, s_ref, s_scr):
    """Grid (B, H / heads, chunks): chunk ``n`` of ``heads`` heads. The
    operands are blocks [CHUNK, heads, width] of the arrays as the mixer wrote
    them, turned by head here, and beta [CHUNK, H], of which the step's heads
    are picked by a mask; ``s_scr`` [heads, K, V] carries the heads' states
    from a chunk to the next."""
    from jax.experimental import pallas as pl

    j, n = pl.program_id(1), pl.program_id(2)
    heads = s_scr.shape[0]

    @pl.when(n == 0)
    def _first_chunk():
        s_scr[...] = s0_ref[...]

    by_head = lambda ref: jnp.swapaxes(ref[...].astype(F32), 0, 1)  # [CHUNK, heads, W] -> [heads, CHUNK, W]
    b = jnp.broadcast_to(beta_ref[...].astype(F32), (heads, *beta_ref.shape))  # [heads, CHUNK, H]
    mine = lax.broadcasted_iota(jnp.int32, b.shape, 2) == lax.broadcasted_iota(jnp.int32, b.shape, 0) + j * heads
    beta = jnp.sum(jnp.where(mine, b, 0.0), axis=2, keepdims=True)
    o, s = _chunk(by_head(q_ref), by_head(k_ref), by_head(v_ref), by_head(g_ref), beta, s_scr[...])
    o_ref[...] = jnp.swapaxes(o, 0, 1).astype(o_ref.dtype)
    s_scr[...] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _last_chunk():
        s_ref[...] = s


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("out_dtype", "interpret"))
def kda_chunk(q, k, v, g, beta, state=None, *, out_dtype=None, interpret=False):
    """The rule over a prompt, CHUNK positions of several heads a grid step
    (the Pallas kernel; arguments and results as ``kda_scan_reference``, o in
    `out_dtype` or q's). Grid (B, H / heads, chunks), the chunks in order with
    the heads' states in VMEM between them; q, k, v, g, beta and o are read
    and written where they lie, in whatever dtypes they have. Runs on a TPU
    backend, or anywhere with interpret=True, and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("kda_chunk", interpret)
    B, S, H, K = q.shape
    V = v.shape[-1]
    heads = _heads_a_chunk(H)
    q, k, v, g, beta = _whole_chunks(q, k, v, g, beta, CHUNK)
    n = q.shape[1] // CHUNK
    rows = lambda W: pl.BlockSpec((None, CHUNK, heads, W), lambda b, j, c: (b, c, j, 0))
    whole = lambda: pl.BlockSpec((None, heads, K, V), lambda b, j, c: (b, j, 0, 0))
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid=(B, H // heads, n),
        in_specs=[rows(K), rows(K), rows(V), rows(K), pl.BlockSpec((None, CHUNK, H), lambda b, j, c: (b, c, 0)), whole()],
        out_specs=[rows(V), whole()],
        out_shape=[jax.ShapeDtypeStruct((B, n * CHUNK, H, V), out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((B, H, K, V), F32)],
        scratch_shapes=[pltpu.VMEM((heads, K, V), F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024,  # two blocks of every operand and of the states, beside the heads' temporaries
        ),
        interpret=interpret,
        name="kda_chunk",
    )(q, k, v, g, beta, _initial(state, B, H, K, V))
    return o[:, :S], s


def _prep_kernel(x_ref, behind_ref, taps_ref, q_ref, k_ref, v_ref, rows, *, parts, scale):
    """Grid (B, blocks of positions): one block of every head. ``x_ref``
    [block, heads.., Hd] are the window's rows from the block's first position
    on and ``behind_ref`` the few behind them, of which the block's last T - 1
    positions read theirs; ``rows`` holds both end to end, so that a
    position's T inputs are T rows from its own on. A trip of the loop takes
    PREP_ROWS positions of q~, of k~ and of v~ through the taps, SiLU and the
    norm in float32, in the order ``delta_prep_reference`` does."""
    from jax.experimental import pallas as pl

    block, T = x_ref.shape[0], taps_ref.shape[0]
    rows[:block] = x_ref[...]
    rows[block:] = behind_ref[...]

    def trip(i, carry):
        at = pl.multiple_of(i * PREP_ROWS, PREP_ROWS)
        for part, out in zip(parts, (q_ref, k_ref, v_ref)):
            x = rows[(pl.ds(at, PREP_ROWS + T - 1), *part)].astype(F32)
            y = functools.reduce(jnp.add, [x[j:j + PREP_ROWS] * taps_ref[(j, *part)] for j in range(T)])
            y = jax.nn.silu(y)
            if out is not v_ref:
                y = _unit_heads(y)
            if out is q_ref:
                y = y * scale
            out[pl.ds(at, PREP_ROWS)] = y.astype(out.dtype)
        return carry

    lax.fori_loop(0, block // PREP_ROWS, trip, 0)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("key_heads", "interpret"))
def delta_prep(window, taps, key_heads, *, interpret=False):
    """``delta_prep_reference`` as ONE pass over a prompt (the Pallas kernel;
    arguments and results as there): the window is read once, where it lies
    and in its own dtype, a block of positions of every head a grid step with
    the T - 1 rows behind it, and q, k (float32) and v are written as
    ``kda_chunk`` reads them; the float32 convolution, its two further reads
    for the norms and for SiLU and the casts never reach HBM. Runs on a TPU
    backend, or anywhere with interpret=True, and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("delta_prep", interpret)
    B, W, Hd = window.shape[0], window.shape[1], window.shape[-1]
    T = taps.shape[0]
    S = W - T + 1
    Hk, H, parts = _prep_parts(window, key_heads)
    heads = window.shape[2:-1]  # (3, H) or (2 Hk + H,)
    behind = max(PREP_ROWS, 1 << (T - 2).bit_length())  # rows of the block behind: a power of two that holds T - 1
    block = min(PREP_BLOCK, -(-S // behind) * behind)
    n = -(-S // block)
    last = (W - 1) // behind  # the last block of `behind` rows that starts inside the window
    zeros = (0,) * (len(heads) + 1)
    out = lambda Hn: pl.BlockSpec((None, block, Hn, Hd), lambda b, i: (b, i, 0, 0))
    q, k, v = pl.pallas_call(
        functools.partial(_prep_kernel, parts=parts, scale=Hd ** -0.5),
        grid=(B, n),
        in_specs=[pl.BlockSpec((None, block, *heads, Hd), lambda b, i: (b, i, *zeros)),
                  pl.BlockSpec((None, behind, *heads, Hd),
                               lambda b, i: (b, jnp.minimum((i + 1) * (block // behind), last), *zeros)),
                  pl.BlockSpec(taps.shape, lambda b, i: (0, *zeros))],
        out_specs=[out(Hk), out(Hk), out(H)],
        out_shape=[jax.ShapeDtypeStruct((B, n * block, Hk, Hd), F32), jax.ShapeDtypeStruct((B, n * block, Hk, Hd), F32),
                   jax.ShapeDtypeStruct((B, n * block, H, Hd), window.dtype)],
        scratch_shapes=[pltpu.VMEM((block + behind, *heads, Hd), window.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=32 * 1024 * 1024,  # two blocks of the window and of q, k and v, beside the rows
        ),
        interpret=interpret,
        name="delta_prep",
    )(window, window, taps.astype(F32))
    return q[:, :S], k[:, :S], v[:, :S]


# rows of a head's operand tile in kda_step: q, k, v, g and beta (along the lanes), the rest unused
_Q, _K, _V, _G, _BETA, _TILE = 0, 1, 2, 3, 4, 8


def _step_kernel(layer_ref, slots_ref, x_ref, _pool_in, o_ref, s_ref, *, heads):
    """Grid (live slots, H / heads): ``heads`` heads of slot
    ``slots_ref[t]``. ``x_ref`` [heads, 8, K]: a head's q, k, v, g, beta as
    rows of one tile; ``s_ref`` [heads, K, V]: their states, in the block of
    the pool that the input block aliases."""
    def one(h, carry):
        x = x_ref[h]  # [8, K]
        k = _column(x[_K:_K + 1])
        s = _pool_in[h] * _column(jnp.exp(x[_G:_G + 1]))
        u = x[_BETA:_BETA + 1] * (x[_V:_V + 1] - jnp.sum(s * k, axis=0, keepdims=True))  # [1, V]
        s = s + k * u
        s_ref[h] = s
        o_ref[h] = jnp.sum(s * _column(x[_Q:_Q + 1]), axis=0, keepdims=True)
        return carry

    lax.fori_loop(0, heads, one, 0)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("interpret",))
def kda_step(q, k, v, g, beta, pool, layer, live, *, interpret=False):
    """One token a live slot (the Pallas kernel; arguments and results as
    ``kda_step_reference``). The pool is aliased to the call's output and
    only the live slots' blocks of layer ``layer`` move; a slot that is not
    live takes no grid step. K = V here (a head's operands ride one tile)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("kda_step", interpret)
    B, H, K = q.shape
    if v.shape[-1] != K:
        raise ValueError(f"kda_step packs a head's operands into one tile: key and value widths differ ({K}, {v.shape[-1]})")
    heads = _heads_a_step(H, HEADS_A_STEP)
    rows = [a.astype(F32) for a in (q, k, v, g, jnp.broadcast_to(beta[..., None], q.shape))]
    x = jnp.stack(rows + [jnp.zeros_like(rows[0])] * (_TILE - len(rows)), axis=2)  # [B, H, 8, K]
    # the live slots first (a stable sort on one bit), and how many they are
    slots = jnp.argsort(jnp.where(live, 0, 1), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32).reshape(1)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(count[0], H // heads),  # the first a runtime value
        in_specs=[
            pl.BlockSpec((None, heads, _TILE, K), lambda t, j, layer, slots: (slots[t], j, 0, 0)),
            pl.BlockSpec((None, None, heads, K, K), lambda t, j, layer, slots: (layer[0], slots[t], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, heads, 1, K), lambda t, j, layer, slots: (slots[t], j, 0, 0)),
            pl.BlockSpec((None, None, heads, K, K), lambda t, j, layer, slots: (layer[0], slots[t], j, 0, 0)),
        ],
    )
    o, pool = pl.pallas_call(
        functools.partial(_step_kernel, heads=heads),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((B, H, 1, K), F32), jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
        input_output_aliases={3: 1},  # operands count the two scalar-prefetch arrays
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=32 * 1024 * 1024,  # two blocks of state in, two out, beside a head's temporaries
        ),
        interpret=interpret,
        name="kda_step",
    )(jnp.asarray(layer, jnp.int32).reshape(1), slots, x, pool)
    # a slot that is not live had no step and its row was never written
    return jnp.where(live[:, None, None], o[:, :, 0], 0.0), pool


def delta_rule():
    """(over a prompt, one token a slot): the kernels on a TPU backend, their
    ``jax.numpy`` forms elsewhere."""
    if jax.default_backend() == "tpu":
        return kda_chunk, kda_step
    return kda_chunk_reference, kda_step_reference
