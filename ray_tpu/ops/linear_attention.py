"""The gated delta rule with a decay a channel (Kimi delta attention), for TPU:
a chunked call for prompts and a one-token call for decode.

A head keeps a state S [K, V] in float32 and no token rows. A token with
query q and key k [K], value v [V], log decay g [K] (<= 0) and step size beta
turns it into

    S' = Diag(exp g) S;   S <- S' + beta k (v - S'^T k)^T;   o = S^T q

(the same as S <- (I - beta k k^T) Diag(exp g) S + beta k v^T). beta above 1,
which a model that allows negative eigenvalues reaches, is nothing special
here. A position with beta 0 and g 0 leaves the state as it was: how a
caller masks the padding behind a prompt's length.

``kda_chunk`` takes a prompt 64 positions a grid step (compute-bound: matrix
products, triangular inside a chunk). With G the running sum of g inside the
chunk, the chunk's pseudo-values U solve (I + A) U = beta (V - (K e^G) S0),
A[i, j] = beta_i sum_c k_ic k_jc e^(G_ic - G_jc) below the diagonal, and

    o = (Q e^G) S0 + P U,  P[i, j] = sum_c q_ic k_jc e^(G_ic - G_jc), j <= i
    S1 = Diag(e^(G_last)) S0 + (K e^(G_last - G))^T U.

e^(G_i - G_j) is split as e^(G_i - r) e^(r - G_j) around the running sum r at
the start of i's sub-block of 16 rows, so that the first factor is at most 1
for every row and the second at most 1 for every column of an earlier
sub-block: a decay however strong underflows to the 0 it is. Inside a
sub-block the second factor is e^(what the sub-block's earlier rows decayed),
cut at e^80: a channel that decays by more than e^-80 inside 16 positions is
the one case the split gets wrong (the weights this repo makes stay under
e^-40). (I + A)^-1 is a product of matrix powers: the 16 x 16 diagonal blocks
D are nilpotent, (I + D)^-1 = (I - D)(I + D^2)(I + D^4)(I + D^8), and what is
left, M = (I + D)^-1 (A - D), is nilpotent by blocks. Everything is float32,
matrix products at the highest precision.

``kda_step`` takes one token a live slot (bound by reading and writing a
slot's 64 x 128 x 128 float32 a layer): the state pool [L, slots, H, K, V]
stays in HBM, the layer is an operand of the index maps, the pool is aliased
to the output, and the grid is the live slots' (a runtime value): a slot
without a request has no step, and its state is bit for bit what it was.

Each call has its ``jax.numpy`` form beside it (``kda_chunk_reference`` runs
the chunk's own arithmetic under ``vmap``; ``kda_scan_reference`` is the rule
a position at a time), which other backends run.
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
F32 = jnp.float32


def _dot(a, b, dims=((1,), (0,))):
    return lax.dot_general(a, b, (dims, ((), ())), precision=lax.Precision.HIGHEST, preferred_element_type=F32)


_NT = ((1,), (1,))  # a @ b^T
_TN = ((0,), (0,))  # a^T @ b


def _column(row):
    """row [1, n] -> [n, 1] without a transpose: the diagonal of its
    broadcast, summed along the lanes."""
    n = row.shape[1]
    eye = lax.broadcasted_iota(jnp.int32, (n, n), 0) == lax.broadcasted_iota(jnp.int32, (n, n), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _chunk(q, k, kb, vb, g, s0):
    """One chunk of one head, float32: q, k, kb (= beta k), g [C, K], vb
    (= beta v) [C, V], s0 [K, V] -> (o [C, V], s1 [K, V]). Written with what
    both ``jax.numpy`` under vmap and a Mosaic kernel's body can run."""
    C, K = k.shape
    sub = min(SUB, C)
    row = lax.broadcasted_iota(jnp.int32, (C, C), 0)
    col = lax.broadcasted_iota(jnp.int32, (C, C), 1)
    G = _dot((row >= col).astype(F32), g)  # the running sum, its own position counted
    at = lax.broadcasted_iota(jnp.int32, (C, K), 0)
    rows_a, rows_p = [], []
    for i in range(0, C, sub):
        ref = G[i - 1:i] if i else jnp.zeros((1, K), F32)
        near = jnp.exp(G[i:i + sub] - ref)  # at most 1
        far = jnp.where(at < i + sub, k * jnp.exp(jnp.minimum(ref - G, CLAMP)), 0.0)
        both = _dot(jnp.concatenate([kb[i:i + sub] * near, q[i:i + sub] * near], axis=0), far, _NT)  # [2 sub, C]
        rows_a.append(both[:sub])
        rows_p.append(both[sub:])
    A = jnp.where(row > col, jnp.concatenate(rows_a, axis=0), 0.0)
    P = jnp.where(row >= col, jnp.concatenate(rows_p, axis=0), 0.0)
    eye = (row == col).astype(F32)
    D = jnp.where(row // sub == col // sub, A, 0.0)
    T, X, n = eye - D, D, 2
    while n < sub:  # (I - D)(I + D^2)(I + D^4) ...: D^sub = 0
        X = _dot(X, X)
        T = _dot(T, eye + X)
        n *= 2
    if C > sub:
        M = _dot(T, A - D)
        Tm, X, n = eye - M, M, 2
        while n < C // sub:  # M^(C / sub) = 0
            X = _dot(X, X)
            Tm = _dot(Tm, eye + X)
            n *= 2
        T = _dot(Tm, T)
    decayed = jnp.exp(G)
    U = _dot(T, vb - _dot(kb * decayed, s0))
    o = _dot(q * decayed, s0) + _dot(P, U)
    last = G[C - 1:C]
    s1 = s0 * _column(jnp.exp(last)) + _dot(k * jnp.exp(last - G), U, _TN)
    return o, s1


def _by_head(x):
    """[B, S, H, W] -> [B, H, S, W] in float32."""
    return jnp.swapaxes(x, 1, 2).astype(F32)


def _chunk_operands(q, k, v, g, beta, chunk):
    """The chunk call's operands, by head and padded to whole chunks with
    positions that leave the state alone (beta 0, g 0)."""
    S = q.shape[1]
    pad = -S % chunk
    b = beta.astype(F32)[..., None]
    ops = [_by_head(q), _by_head(k), _by_head(k.astype(F32) * b), _by_head(v.astype(F32) * b), _by_head(g)]
    if pad:
        ops = [jnp.pad(a, ((0, 0), (0, 0), (0, pad), (0, 0))) for a in ops]
    return ops


def _initial(state, B, H, K, V):
    return jnp.zeros((B, H, K, V), F32) if state is None else state.astype(F32)


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
    """``kda_chunk`` in ``jax.numpy``: the chunk's arithmetic (``_chunk``)
    under vmap over sequences and heads, a scan over chunks. Arguments and
    results as ``kda_scan_reference``; o in `out_dtype`."""
    B, S, H, K = q.shape
    V = v.shape[-1]
    chunk = min(chunk, -(-S // SUB) * SUB)
    ops = _chunk_operands(q, k, v, g, beta, chunk)
    n = ops[0].shape[2] // chunk
    xs = tuple(jnp.moveaxis(a.reshape(B, H, n, chunk, a.shape[-1]), 2, 0) for a in ops)
    every_head = jax.vmap(jax.vmap(_chunk))

    def one(s, x):
        o, s = every_head(*x, s)
        return s, o

    s, o = lax.scan(one, _initial(state, B, H, K, V), xs)  # o [n, B, H, chunk, V]
    o = jnp.moveaxis(o, 0, 2).reshape(B, H, n * chunk, V)[:, :, :S]
    return jnp.swapaxes(o, 1, 2).astype(out_dtype), s


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


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _needs_tpu(what: str, interpret: bool) -> None:
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(f"{what} needs a TPU backend (or interpret=True); this process runs on "
                           f"{jax.default_backend()!r}")


def _chunk_kernel(q_ref, k_ref, kb_ref, vb_ref, g_ref, s0_ref, o_ref, s_ref, s_scr):
    from jax.experimental import pallas as pl

    n = pl.program_id(2)

    @pl.when(n == 0)
    def _first_chunk():
        s_scr[...] = s0_ref[...]

    o, s = _chunk(q_ref[...], k_ref[...], kb_ref[...], vb_ref[...], g_ref[...], s_scr[...])
    o_ref[...] = o.astype(o_ref.dtype)
    s_scr[...] = s

    @pl.when(n == pl.num_programs(2) - 1)
    def _last_chunk():
        s_ref[...] = s


def kda_chunk(q, k, v, g, beta, state=None, *, out_dtype=None, interpret=False):
    """The rule over a prompt, CHUNK positions a grid step (the Pallas
    kernel; arguments and results as ``kda_scan_reference``, o in `out_dtype`
    or q's). Grid (B, H, chunks), the chunks in order with the head's state
    in VMEM between them. Runs on a TPU backend, or anywhere with
    interpret=True, and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    _needs_tpu("kda_chunk", interpret)
    B, S, H, K = q.shape
    V = v.shape[-1]
    ops = _chunk_operands(q, k, v, g, beta, CHUNK)
    n = ops[0].shape[2] // CHUNK
    rows = lambda W: pl.BlockSpec((None, None, CHUNK, W), lambda b, h, c: (b, h, c, 0))
    whole = lambda: pl.BlockSpec((None, None, K, V), lambda b, h, c: (b, h, 0, 0))
    o, s = pl.pallas_call(
        _chunk_kernel,
        grid=(B, H, n),
        in_specs=[rows(K), rows(K), rows(K), rows(V), rows(K), whole()],
        out_specs=[rows(V), whole()],
        out_shape=[jax.ShapeDtypeStruct((B, H, n * CHUNK, V), out_dtype or q.dtype),
                   jax.ShapeDtypeStruct((B, H, K, V), F32)],
        scratch_shapes=[pltpu.VMEM((K, V), F32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="kda_chunk",
    )(*ops, _initial(state, B, H, K, V))
    return jnp.swapaxes(o[:, :, :S], 1, 2), s


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
    heads = next(n for n in range(min(HEADS_A_STEP, H), 0, -1) if H % n == 0)
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
