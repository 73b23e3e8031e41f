"""Paged decode attention over a latent cache: every query head of a
sequence attends the same cached row, whose leading columns are also the
value.

A latent-attention layer (low-rank key/value projection; models/transformer.py
``attention_kind="latent"``) caches one row a token a layer, ``[c | k_rope]``:
the normed latent ``c`` (``v_width`` columns) and the roped key part all heads
share. In decode the key and value up-projections are absorbed into the query
and the output (the caller's two einsums), so the kernel sees H query rows of
the pool's width per sequence and scores them against the page as it lies:
``s = q . row``, ``ctx = sum p row[:v_width]``. A page is read once for
scores and values alike, and once for all heads.

The walk, the write of the step's own row and the aliasing of the pool are
``ops/paged_attention.py``'s (PRs 25, 33 and 43): a grid of the batch's page
groups (``page_groups``: up to G consecutive pages of one sequence a step, G
from ``group_pages``, and no step for a row of length 0, which is returned as
zeros), the layer an operand, the pool left in HBM and its pages copied by
the kernel itself into one of two buffers while the group before is attended,
the token's row spliced into its page in VMEM and a window of WINDOW_ROWS rows
around it copied back into the pool the output aliases. The kernels stay two:
one chain over H query rows against one shared row wants another body than a
batch of chains over KV heads. This one's chain takes several pages a turn
(``_turn_pages``). From PR 36 to PR 47 a grid step was one page through a
BlockSpec on a walk of its own, with a step for an empty slot too: 0.84 us a
page step for 0.2 us of bytes or of products.

Row width: the pool's last axis is a lane multiple. A 576-wide row (512 + 64)
lies in HBM in 128-lane tiles either way, five of them, so the pool states the
640 it occupies and the query's pad columns are zeros; a roofline that counts
the 1,152 bytes the mathematics needs shows the tenth as lost share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import (
    NEG_INF, WINDOW_ROWS, _page_range, _write_rows, group_pages, page_groups,
)

LANES = 128


def latent_row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """The pool's row: [c | k_rope] padded up to whole lane tiles."""
    return -(-(kv_lora_rank + rope_dim) // LANES) * LANES


# ---------------------------------------------------------------------------
# Reference implementation (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def latent_attention_reference(q, row_new, pool, lengths, page_indices, layer, *, v_width, scale):
    """q: [B, H, W], the absorbed queries laid like a row ([q_nope Wk_h^T |
    q_rope | 0]); row_new: [B, W], the current token's row; pool:
    [L, P_total, ps, W]; lengths: [B], the current token counted (0: the row
    holds no sequence); page_indices: [B, pages_per_seq]; layer: scalar index
    into L -> (ctx [B, H, v_width], pool) with the row written at position
    lengths - 1 of each sequence, and for a row of length 0 nothing written
    and zeros returned."""
    B, H, W = q.shape
    _, _, ps, _ = pool.shape
    ppseq = page_indices.shape[1]
    pos = jnp.maximum(lengths - 1, 0)
    page = page_indices[jnp.arange(B), jnp.minimum(pos // ps, ppseq - 1)]
    # the paged reference's write, the pool seen as one KV head's: a row a sequence, none for a row of length 0
    pool = _write_rows(pool[:, None], row_new[:, None], layer, page, pos % ps, lengths)[:, 0]
    rows = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[page_indices]
    rows = rows.reshape(B, ppseq * ps, W)
    s = jnp.einsum("bhw,bsw->bhs", q, rows).astype(jnp.float32) * scale
    valid = (jnp.arange(ppseq * ps)[None, :] < lengths[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1).astype(q.dtype)
    ctx = jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_width])
    return jnp.where((lengths > 0)[:, None, None], ctx, 0), pool


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

SCORE_TILE_BYTES = 256 << 10  # a turn's scores [H, c * ps] in float32


def _turn_pages(heads: int, page_size: int, group: int) -> int:
    """Pages of a group the kernel's softmax chain takes a turn (the latent
    twin of ``ops/paged_attention._chunk_pages``): as many as make the turn's
    score tile, [heads, c x page_size] in float32, SCORE_TILE_BYTES (4 pages
    of 128 rows at 128 heads), for what a turn pays whatever its width, the
    accumulator's round trip and the running maximum's and sum's, is then
    paid once for them all (the kernel alone on the chip, PERF.md section 6,
    PR 47), and a divisor of the group, whose buffer the last turn must not
    pass."""
    c = max(1, min(SCORE_TILE_BYTES // (heads * page_size * 4), group))
    while group % c:
        c -= 1
    return c


def _latent_kernel(lens_ref, layer_ref, seqs_ref, first_ref, live_ref, where_ref, win_page_ref,
                   win_row_ref, q_ref, new_ref, _pool_in, o_ref, pool_hbm,
                   buf, sems, win_sem, m_scr, l_scr, acc_scr, *, scale, ps, n, c, n_pages, v_width):
    """Grid (count,), the page groups of the batch (``page_groups``): step t
    is pages ``first_ref[t]`` .. of sequence ``seqs_ref[t]``, ``live_ref[t]``
    of them. The pool stays in HBM (``pool_hbm``: the output, which the input
    aliases) and the kernel copies a group's pages itself, a copy a page into
    rows i * ps .. of buffer t % 2 of ``buf`` ([2, n * ps, W]); step t starts
    step t + 1's copies before it waits for its own. One page copy serves
    all H heads, for scores and for values.

    Nothing here is unrolled by n, by c or by H: the copies start in a loop
    over the group's live pages, and a second loop takes the group ``c``
    pages a turn (``_turn_pages``), waits for the turn's pages and folds
    them into the sequence's online softmax: one product for the scores, one
    max / exp / sum, one rescale of the accumulator, one product with the
    value columns. The chain (m, l, acc) lives in the VMEM scratch, through
    a group's turns and between a sequence's groups: carried by the loop as
    values it cost a seventh more a page (the accumulator is [H, v_width]
    float32, the whole register file at 128 heads: PERF.md section 6, PR 47).

    The sequence's last page has the current token's row (``new_ref``, f32)
    spliced in where it lies in the buffer, before it is attended, and the
    WINDOW_ROWS rows around it copied back into the pool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    b = seqs_ref[t]
    j0 = first_ref[t]
    live = live_ref[t]
    length = lens_ref[b]
    layer = layer_ref[0]
    at = t % 2
    turns = (live + c - 1) // c
    _, last = _page_range(length, ps, n_pages)
    newest = j0 + live - 1 == last  # the sequence's last group: the token's

    def rows(i, size=ps):  # place i of a buffer
        return pl.ds(pl.multiple_of(i * ps, ps), size)

    def copy(step, i):  # page i of step's group into that step's buffer
        return pltpu.make_async_copy(
            pool_hbm.at[layer, where_ref[step * n + i]], buf.at[step % 2, rows(i)], sems.at[step % 2, i])

    def each(lo, hi, do):  # do(i) for i in lo .. hi - 1, both runtime values: one body, whatever the count
        def body(i, carry):
            do(i)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def fetch(step):
        each(0, live_ref[step], lambda i: copy(step, i).start())

    win = min(ps, WINDOW_ROWS)
    row = (length - 1) % ps
    top = pl.multiple_of((live - 1) * ps + row // win * win, win)  # the token's rows in the buffer, if newest
    window_copy = pltpu.make_async_copy(  # the token's rows, buffer -> pool
        buf.at[at, pl.ds(top, win)], pool_hbm.at[layer, win_page_ref[t], pl.ds(win_row_ref[t] * win, win)],
        win_sem.at[0])

    @pl.when(t == 0)
    def _first_group():
        fetch(t)

    @pl.when(t + 1 < pl.num_programs(0))
    def _next_group():
        fetch(t + 1)

    if c > 1:
        # The places that fill up the group's last turn were not fetched:
        # their columns are masked below, and their rows, whose value columns
        # p's zeros would multiply, are zeroed first (a buffer may hold
        # anything).
        def no_page(i):
            buf[at, rows(i), :] = jnp.zeros((ps, buf.shape[-1]), buf.dtype)
        each(live, turns * c, no_page)

    q = q_ref[0]  # [H, W]
    ends = jnp.minimum(length, (j0 + live) * ps)  # columns past it: another token's, or a page not fetched

    @pl.when((t == 0) | (seqs_ref[jnp.maximum(t - 1, 0)] != b))
    def _the_sequences_first_group():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def turn(i, carry):
        each(i * c, jnp.minimum(i * c + c, live), lambda k: copy(t, k).wait())

        @pl.when(newest & (i == turns - 1))
        def _write_the_token():
            # Spliced in f32 (bf16 -> f32 -> bf16 is exact): a 32-bit select
            # needs no packed-row mask.
            old = buf[at, pl.ds(top, win), :].astype(jnp.float32)  # [win, W]
            here = jax.lax.broadcasted_iota(jnp.int32, old.shape, 0) == row % win
            buf[at, pl.ds(top, win), :] = jnp.where(here, new_ref[0], old).astype(buf.dtype)
            window_copy.start()

        s = jax.lax.dot_general(
            q, buf[at, rows(i * c, c * ps), :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * scale  # [H, c * ps]
        cols = (j0 + i * c) * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(cols < ends, s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(buf.dtype), buf[at, rows(i * c, c * ps), pl.ds(0, v_width)], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [H, v_width]
        m_scr[...] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_cur, l_scr.shape)
        return carry

    jax.lax.fori_loop(0, turns, turn, 0)

    @pl.when(newest)
    def _the_sequences_last_group():
        # l >= 1: the row's largest score counts exp(0)
        o_ref[0] = (acc_scr[...] / l_scr[:, :1]).astype(o_ref.dtype)
        window_copy.wait()  # before the step after next fetches into this buffer


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("v_width", "scale", "interpret"))
def latent_paged_attention(q, row_new, pool, lengths, page_indices, layer, *, v_width, scale,
                           walk=None, interpret=False):
    """Latent paged decode attention (the Pallas kernel; arguments and result
    as ``latent_attention_reference``). q's head count should be a sublane
    multiple (8) and the pool's row a lane multiple (``latent_row_width``).
    walk: ``page_groups`` of these lengths and this table, for a caller with
    several calls on them (a decode step's layers), with the page group it
    chose (``group_pages`` of one pool of rows); built here without it. The
    returned pool aliases the argument. Runs on a TPU backend, or anywhere
    with interpret=True, and raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"latent_paged_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    B, H, W = q.shape
    ps = pool.shape[2]
    n_pages = page_indices.shape[1]
    if walk is None:
        walk = page_groups(lengths, page_indices, ps, 0, group_pages(1, ps, W, pool.dtype.itemsize, n_pages))
    seqs, first, live, where, win_page, win_row, count = walk
    n = where.shape[0] // seqs.shape[0]  # the walk's page group

    def whole(t, lens, layer, seqs, *_):
        return (seqs[t], 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(count[0],),  # a runtime value: one compiled call serves every batch
        in_specs=[pl.BlockSpec((1, H, W), whole), pl.BlockSpec((1, 1, W), whole), in_hbm],
        out_specs=[pl.BlockSpec((1, H, v_width), whole), in_hbm],
        scratch_shapes=[
            pltpu.VMEM((2, n * ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2, n)),  # the buffer, the page
            pltpu.SemaphoreType.DMA((1,)),  # the token's rows
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, v_width), jnp.float32),
        ],
    )
    kernel = functools.partial(_latent_kernel, scale=scale, ps=ps, n=n, c=_turn_pages(H, ps, n),
                               n_pages=n_pages, v_width=v_width)
    lengths = lengths.astype(jnp.int32)
    # The token's row rounded as the pool stores it, handed over in f32 (a
    # 32-bit select needs no packed-row mask).
    row_new = row_new.astype(pool.dtype).astype(jnp.float32)[:, None, :]
    ctx, pool = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the eight scalar-prefetch arrays: 10 is the pool
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a sequence's groups accumulate into one scratch, and
            # a step fetches the next one's pages
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="latent_attn",
    )(lengths, jnp.asarray(layer, jnp.int32).reshape(1), seqs, first, live, where, win_page, win_row,
      q, row_new, pool)
    # a row without a sequence had no step and was never written
    return jnp.where((lengths > 0)[:, None, None], ctx, 0), pool
