"""Grouped matmul over the experts a chip holds: rows sorted by expert, each
row multiplied by its own expert's matrix.

A routed FFN over held experts (models/transformer.py ``_held_experts_ffn``,
served and trained) sorts its (token, expert) pairs by expert and multiplies
each group by that expert's weights: none dropped, whatever the imbalance.
``group_rows`` lays the
pairs out so that every tile of ``tm`` rows belongs to one expert (a group
starts on a tile boundary; the rows between a group's end and the next
boundary are padding nobody reads back), and ``expert_gmm`` walks the live
tiles only: an expert with no token costs nothing, not a DMA, and an expert's
weights are read once a tile of its rows.

Kernel shape: grid (live tiles, N blocks, K blocks), the first a runtime
value; the tile's expert comes through scalar prefetch and picks the weight
block; f32 accumulator in VMEM across the K blocks.

Trained, ``expert_gmm`` has a custom VJP: dx is the same walk with the weight
block contracted over its columns (``expert_gmm_dx``: dy of a tile times its
expert's matrix transposed, rows of dead tiles zeros), and dW is a second
kernel (``expert_tgmm``: grid (K blocks, N blocks, live tiles), an expert's
consecutive tiles' x^T dy summed in float32 in VMEM and written once an
expert, zeros for an expert no pair chose). ``expert_gmm_reference`` is
differentiated by JAX and gives the same gradients.
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


logger = logging.getLogger(__name__)


class RowPlan(NamedTuple):
    """Where ``group_rows`` put the pairs. M rows in tiles of ``tm``:

    - ``token_of_row`` [M]: the token a row holds (0 for a padding row);
    - ``row_of_pair`` [T, K]: the row of each (token, choice) pair (0 where
      the chosen expert is not held: mask with ``held``);
    - ``held`` [T, K] bool: the pair landed on an expert held here;
    - ``tile_expert`` [M // tm]: the local expert of each tile (a dead tile
      names a valid expert all the same);
    - ``n_tiles`` [1]: the live tiles, which come first;
    - ``sizes`` [E]: pairs on each held expert;
    - ``pair_of_row`` [M]: the pair a row holds, t * K + k (-1 for a padding
      row): the inverse of ``row_of_pair`` over the held pairs."""
    token_of_row: jax.Array
    row_of_pair: jax.Array
    held: jax.Array
    tile_expert: jax.Array
    n_tiles: jax.Array
    sizes: jax.Array
    pair_of_row: jax.Array


def plan_rows(n_pairs: int, n_held: int, tm: int) -> int:
    """Rows that hold any assignment of n_pairs pairs to n_held experts in
    tile-aligned groups: every pair, and less than a tile of padding a group."""
    return (-(-n_pairs // tm) + n_held) * tm


def group_rows(experts, first: int, n_held: int, tm: int) -> RowPlan:
    """experts: [T, K] int32, the global ids each token chose; the chip holds
    ids first .. first + n_held - 1. Static shapes: ``plan_rows(T * K, ...)``
    rows, of which the live tiles are a prefix. No sort: a pair's place in its
    group is the count of earlier pairs on the same expert (a cumulative sum
    over a [T * K, n_held] one-hot), which keeps tokens in order inside a group."""
    T, K = experts.shape
    M = plan_rows(T * K, n_held, tm)
    local = experts.reshape(-1).astype(jnp.int32) - first  # [T*K]
    held = (local >= 0) & (local < n_held)
    onehot = (local[:, None] == jnp.arange(n_held, dtype=jnp.int32)[None, :]).astype(jnp.int32)
    sizes = jnp.sum(onehot, axis=0)  # [E]
    rank = jnp.sum((jnp.cumsum(onehot, axis=0) - onehot) * onehot, axis=1)  # [T*K]
    tiles = -(-sizes // tm)
    tile_end = jnp.cumsum(tiles)
    row_start = (tile_end - tiles) * tm
    row = jnp.sum(onehot * row_start[None, :], axis=1) + rank
    pair_of_row = jnp.full(M, -1, jnp.int32).at[jnp.where(held, row, M)].set(
        jnp.arange(T * K, dtype=jnp.int32), mode="drop", unique_indices=True)
    token_of_row = lax.div(jnp.maximum(pair_of_row, 0), K)
    tile = jnp.arange(M // tm, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.sum(tile[:, None] >= tile_end[None, :], axis=1), n_held - 1)
    return RowPlan(token_of_row, jnp.where(held, row, 0).reshape(T, K), held.reshape(T, K),
                   tile_expert.astype(jnp.int32), tile_end[-1:].astype(jnp.int32), sizes, pair_of_row)


# ---------------------------------------------------------------------------
# Reference implementation (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def expert_gmm_reference(x, w, layer, tile_expert, n_tiles, *, tm):
    """x: [M, K], rows in tile-aligned groups; w: [L, E, K, N], every layer's
    experts; layer: which of the L (an int or a traced int32 scalar);
    tile_expert: [M // tm]; n_tiles: [1] -> [M, N], row r times
    w[layer, tile_expert[r // tm]]. Rows of tiles past n_tiles are zeros here
    (the kernel leaves them unwritten). One plain matmul an expert, masked:
    for toy sizes."""
    M = x.shape[0]
    w = jax.lax.dynamic_index_in_dim(w, layer, 0, keepdims=False)
    row_expert = jnp.repeat(tile_expert, tm, total_repeat_length=M)
    live = jnp.arange(M) < n_tiles[0] * tm
    out = jnp.zeros((M, w.shape[2]), x.dtype)
    for e in range(w.shape[0]):
        product = jnp.dot(x, w[e].astype(x.dtype), preferred_element_type=jnp.float32).astype(x.dtype)
        out = jnp.where((live & (row_expert == e))[:, None], product, out)
    return out


@functools.cache
def _say_reference(backend: str) -> None:
    logger.warning("expert_gmm: backend %r is no TPU, routed experts run the jax.numpy reference", backend)


def expert_matmul():
    """What a model's routed layer multiplies with: the kernel on a TPU
    backend, the reference elsewhere, which the log then says once (the
    reference is a masked full matmul an expert: toy sizes only)."""
    backend = jax.default_backend()
    if backend == "tpu":
        return expert_gmm
    _say_reference(backend)
    return expert_gmm_reference


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _gmm_kernel(layer_ref, tile_expert_ref, x_ref, w_ref, o_ref, acc_scr, *, n_k, transposed=False):
    from jax.experimental import pallas as pl

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    if transposed:  # the block [tk, tn] as it lies, contracted over its columns
        acc_scr[...] += lax.dot_general(x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                                        preferred_element_type=jnp.float32)
    else:
        acc_scr[...] += jnp.dot(x_ref[...], w_ref[...], preferred_element_type=jnp.float32)

    @pl.when(k == n_k - 1)
    def _store():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def _block(dim: int, target: int) -> int:
    """The largest lane multiple that divides dim and is at most target; dim
    itself where it has none (toy widths)."""
    best = 0
    for b in range(128, min(dim, target) + 1, 128):
        if dim % b == 0:
            best = b
    return best or dim


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("tm", "block_k", "block_n", "interpret", "transposed"))
def _gmm_call(x, w, layer, tile_expert, n_tiles, *, tm, block_k, block_n, interpret, transposed=False):
    """One grouped product over the live tiles. Forward: x [M, K] times
    w[layer, e] [K, N]. ``transposed``: x is a cotangent [M, N] and the weight
    block is read as it lies and contracted over its columns, x times
    w[layer, e]^T -> [M, K] (``expert_gmm_dx``): the same walk, the same DMAs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M = x.shape[0]
    K, N = w.shape[2], w.shape[3]
    tk, tn = _block(K, block_k), _block(N, block_n)
    if transposed:  # grid (tiles, K blocks of the result, N blocks summed over)
        blocks, n_sum, out_cols, out_block = (K // tk, N // tn), N // tn, K, tk
        x_spec = pl.BlockSpec((tm, tn), lambda i, k, j, layer, te: (i, j))
        w_spec = pl.BlockSpec((None, None, tk, tn), lambda i, k, j, layer, te: (layer[0], te[i], k, j))
        out_spec = pl.BlockSpec((tm, tk), lambda i, k, j, layer, te: (i, k))
    else:  # grid (tiles, N blocks of the result, K blocks summed over)
        blocks, n_sum, out_cols, out_block = (N // tn, K // tk), K // tk, N, tn
        x_spec = pl.BlockSpec((tm, tk), lambda i, j, k, layer, te: (i, k))
        w_spec = pl.BlockSpec((None, None, tk, tn), lambda i, j, k, layer, te: (layer[0], te[i], k, j))
        out_spec = pl.BlockSpec((tm, tn), lambda i, j, k, layer, te: (i, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles[0], *blocks),  # the first a runtime value
        in_specs=[x_spec, w_spec],
        out_specs=out_spec,
        scratch_shapes=[pltpu.VMEM((tm, out_block), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=n_sum, transposed=transposed),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, out_cols), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024,  # two weight blocks in flight, beside the tiles
        ),
        interpret=interpret,
        name="expert_gmm_dx" if transposed else "expert_gmm",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, x, w)


def _tgmm_kernel(tile_expert_ref, n_tiles_ref, x_ref, dy_ref, o_ref, acc_scr):
    """One tile's x^T dy added to its expert's sum, which is stored when the
    expert's last tile has been added: the tiles of an expert are consecutive."""
    from jax.experimental import pallas as pl

    t = pl.program_id(2)
    mine = tile_expert_ref[t]
    last = n_tiles_ref[0] - 1

    @pl.when((t == 0) | (tile_expert_ref[jnp.maximum(t - 1, 0)] != mine))
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += lax.dot_general(x_ref[...], dy_ref[...], (((0,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    @pl.when((t == last) | (tile_expert_ref[jnp.minimum(t + 1, last)] != mine))
    def _store():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("n_experts", "tm", "block_k", "block_n", "interpret"))
def expert_tgmm(x, dy, tile_expert, n_tiles, n_experts: int, *, tm, block_k, block_n, interpret):
    """dW [E, K, N] of one layer: expert e's x_tile^T dy_tile summed over its
    live tiles, a float32 sum in VMEM across them, written once an expert. An
    expert with no live tile is not written: the caller lays zeros there."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    K, N = x.shape[1], dy.shape[1]
    tk, tn = _block(K, block_k), _block(N, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(K // tk, N // tn, n_tiles[0]),  # the last a runtime value: an expert's tiles follow one another
        in_specs=[
            pl.BlockSpec((tm, tk), lambda k, j, t, te, nt: (t, k)),
            pl.BlockSpec((tm, tn), lambda k, j, t, te, nt: (t, j)),
        ],
        out_specs=pl.BlockSpec((None, tk, tn), lambda k, j, t, te, nt: (te[t], k, j)),
        scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)],
    )
    return pl.pallas_call(
        _tgmm_kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_experts, K, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024,
        ),
        interpret=interpret,
        name="expert_tgmm",
    )(tile_expert, n_tiles, x, dy)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8))
def _gmm(x, w, layer, tile_expert, n_tiles, tm, block_k, block_n, interpret):
    return _gmm_call(x, w, layer, tile_expert, n_tiles, tm=tm, block_k=block_k, block_n=block_n, interpret=interpret)


def _gmm_fwd(x, w, layer, tile_expert, n_tiles, tm, block_k, block_n, interpret):
    y = _gmm_call(x, w, layer, tile_expert, n_tiles, tm=tm, block_k=block_k, block_n=block_n, interpret=interpret)
    return y, (x, w, layer, tile_expert, n_tiles)


def _gmm_bwd(tm, block_k, block_n, interpret, res, dy):
    """dx over the live tiles through the expert's matrix transposed, the rows
    of dead tiles zeros (the kernel leaves them unwritten, and a caller's
    gather that laid the rows out may scatter every row back, token 0's
    padding among them); dW an expert at a time over its tiles, zeros for an expert none
    chose, laid into zeros of the whole stack's shape at this layer (XLA adds
    that slice to the layer loop's running sum in place: it adds no stack).
    A padding row inside a live tile holds token 0's row going in and a zero
    cotangent coming back, since nothing read its result: it adds nothing."""
    x, w, layer, tile_expert, n_tiles = res
    blocks = dict(tm=tm, block_k=block_k, block_n=block_n, interpret=interpret)
    live = jnp.arange(x.shape[0], dtype=jnp.int32) < n_tiles[0] * tm
    dx = _gmm_call(dy, w, layer, tile_expert, n_tiles, transposed=True, **blocks)
    dx = jnp.where(live[:, None], dx, jnp.zeros((), dx.dtype))
    E = w.shape[1]
    tile = jnp.arange(tile_expert.shape[0], dtype=jnp.int32)
    chosen = jnp.zeros(E, jnp.bool_).at[jnp.where(tile < n_tiles[0], tile_expert, E)].set(True, mode="drop")
    dw = expert_tgmm(x, dy, tile_expert, n_tiles, E, **blocks)
    dw = jnp.where(chosen[:, None, None], dw, jnp.zeros((), dw.dtype)).astype(w.dtype)
    dw = lax.dynamic_update_index_in_dim(jnp.zeros(w.shape, w.dtype), dw, jnp.asarray(layer, jnp.int32).reshape(()), 0)
    return dx, dw, None, None, None


_gmm.defvjp(_gmm_fwd, _gmm_bwd)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("tm", "block_k", "block_n", "interpret"))
def expert_gmm(x, w, layer, tile_expert, n_tiles, *, tm, block_k=2048, block_n=1024, interpret=False):
    """The grouped matmul (the Pallas kernel; arguments as
    ``expert_gmm_reference``; rows of tiles past n_tiles are not written).
    The weights never move: the layer is an operand that the index map adds
    to a block's address, so a caller inside a layer loop hands over the
    whole stack and no slice of it.
    Weight blocks of up to block_k x block_n (3.9 MB of bf16 at the defaults) keep
    the stream of an expert's matrix in few, large DMAs. Runs on a TPU
    backend, or anywhere with interpret=True, and raises elsewhere.
    Differentiable in x and w (``_gmm_bwd``: ``expert_gmm_dx`` and
    ``expert_tgmm``); w's gradient has the shape of the stack handed over and
    x's dtype, so a caller whose parameters are wider than its rows casts
    what it hands over once, outside the loop of its calls, and autodiff
    widens the gradient once (models/transformer.py ``_held_experts_ffn``)."""
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"expert_gmm needs a TPU backend (or interpret=True); this process runs on "
            f"{jax.default_backend()!r}"
        )
    return _gmm(x, w.astype(x.dtype), layer, tile_expert, n_tiles, tm, block_k, block_n, interpret)
