"""Grouped matmul over the experts a chip holds: rows sorted by expert, each
row multiplied by its own expert's matrix.

The serving path of a routed FFN (models/transformer.py ``_held_experts_ffn``)
sorts its (token, expert) pairs by expert and multiplies each group by that
expert's weights: none dropped, whatever the imbalance. ``group_rows`` lays the
pairs out so that every tile of ``tm`` rows belongs to one expert (a group
starts on a tile boundary; the rows between a group's end and the next
boundary are padding nobody reads back), and ``expert_gmm`` walks the live
tiles only: an expert with no token costs nothing, not a DMA, and an expert's
weights are read once a tile of its rows.

Kernel shape: grid (live tiles, N blocks, K blocks), the first a runtime
value; the tile's expert comes through scalar prefetch and picks the weight
block; f32 accumulator in VMEM across the K blocks.
"""
from __future__ import annotations

import functools
import logging
from typing import NamedTuple

import jax
import jax.numpy as jnp


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
    - ``sizes`` [E]: pairs on each held expert."""
    token_of_row: jax.Array
    row_of_pair: jax.Array
    held: jax.Array
    tile_expert: jax.Array
    n_tiles: jax.Array
    sizes: jax.Array


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
    token = jnp.arange(T * K, dtype=jnp.int32) // K
    token_of_row = jnp.zeros(M, jnp.int32).at[jnp.where(held, row, M)].set(
        token, mode="drop", unique_indices=True)
    tile = jnp.arange(M // tm, dtype=jnp.int32)
    tile_expert = jnp.minimum(jnp.sum(tile[:, None] >= tile_end[None, :], axis=1), n_held - 1)
    return RowPlan(token_of_row, jnp.where(held, row, 0).reshape(T, K), held.reshape(T, K),
                   tile_expert.astype(jnp.int32), tile_end[-1:].astype(jnp.int32), sizes)


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

def _gmm_kernel(layer_ref, tile_expert_ref, x_ref, w_ref, o_ref, acc_scr, *, n_k):
    from jax.experimental import pallas as pl

    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

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
@functools.partial(jax.jit, inline=True, static_argnames=("tm", "block_k", "block_n", "interpret"))
def expert_gmm(x, w, layer, tile_expert, n_tiles, *, tm, block_k=2048, block_n=1024, interpret=False):
    """The grouped matmul (the Pallas kernel; arguments as
    ``expert_gmm_reference``; rows of tiles past n_tiles are not written).
    The weights never move: the layer is an operand that the index map adds
    to a block's address, so a caller inside a layer loop hands over the
    whole stack and no slice of it.
    Weight blocks of up to block_k x block_n (3.9 MB of bf16 at the defaults) keep
    the stream of an expert's matrix in few, large DMAs. Runs on a TPU
    backend, or anywhere with interpret=True, and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"expert_gmm needs a TPU backend (or interpret=True); this process runs on "
            f"{jax.default_backend()!r}"
        )
    M, K = x.shape
    N = w.shape[3]
    tk, tn = _block(K, block_k), _block(N, block_n)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles[0], N // tn, K // tk),  # the first a runtime value
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, k, layer, te: (i, k)),
            pl.BlockSpec((None, None, tk, tn), lambda i, j, k, layer, te: (layer[0], te[i], k, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, k, layer, te: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)],
    )
    return pl.pallas_call(
        functools.partial(_gmm_kernel, n_k=K // tk),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((M, N), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=48 * 1024 * 1024,  # two weight blocks in flight, beside the tiles
        ),
        interpret=interpret,
        name="expert_gmm",
    )(jnp.asarray(layer, jnp.int32).reshape(1), tile_expert, x, w.astype(x.dtype))
