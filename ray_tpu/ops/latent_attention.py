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
``ops/paged_attention.py``'s (PRs 25 and 33): a grid of the batch's live
pages (``live_pages``), the layer an operand of the index maps, the token's
row spliced into its page in VMEM and a window of WINDOW_ROWS rows around it
stored back into the pool the output aliases.

Row width: the pool's last axis is a lane multiple. A 576-wide row (512 + 64)
lies in HBM in 128-lane tiles either way, five of them, so the pool states the
640 it occupies and the query's pad columns are zeros; a roofline that counts
the 1,152 bytes the mathematics needs shows the tenth as lost share.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ray_tpu.ops.paged_attention import NEG_INF, WINDOW_ROWS, _page_range, live_pages

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
    [L, P_total, ps, W]; lengths: [B], the current token counted;
    page_indices: [B, pages_per_seq]; layer: scalar index into L
    -> (ctx [B, H, v_width], pool) with the row written at position
    lengths - 1 of each sequence."""
    B, H, W = q.shape
    _, _, ps, _ = pool.shape
    ppseq = page_indices.shape[1]
    pos = lengths - 1
    page = page_indices[jnp.arange(B), jnp.minimum(pos // ps, ppseq - 1)]
    for b in range(B):  # a row a sequence, in place in a donated or loop-carried pool
        pool = jax.lax.dynamic_update_slice(
            pool, row_new[b].astype(pool.dtype)[None, None, None, :], (layer, page[b], pos[b] % ps, 0))
    rows = jax.lax.dynamic_index_in_dim(pool, layer, 0, keepdims=False)[page_indices]
    rows = rows.reshape(B, ppseq * ps, W)
    s = jnp.einsum("bhw,bsw->bhs", q, rows).astype(jnp.float32) * scale
    valid = (jnp.arange(ppseq * ps)[None, :] < lengths[:, None])[:, None, :]
    p = jax.nn.softmax(jnp.where(valid, s, NEG_INF), axis=-1).astype(q.dtype)
    return jnp.einsum("bhs,bsv->bhv", p, rows[..., :v_width]), pool


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _latent_kernel(lens_ref, layer_ref, slots_ref, pages_ref, where_ref, win_page_ref,
                   win_row_ref, q_ref, new_ref, pool_ref, o_ref, pool_out_ref,
                   m_scr, l_scr, acc_scr, *, scale, ps, n_pages, v_width):
    """Grid (count,), the live pages of the batch: step t is page
    ``pages_ref[t]`` of sequence ``slots_ref[t]``. One page DMA serves all H
    heads, for scores and for values. ``pool_out_ref`` is a window of rows of
    the sequence's newest page in the pool the input aliases."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    b = slots_ref[t]
    j = pages_ref[t]
    length = lens_ref[b]
    _, last = _page_range(length, ps, n_pages)

    @pl.when((t == 0) | (slots_ref[jnp.maximum(t - 1, 0)] != b))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j == last)
    def _write_the_token():
        # As the paged kernel does: into the page in VMEM, where the scores
        # below read it, and into the window that is stored back.
        win = pool_out_ref.shape[1]
        row = (length - 1) % ps
        top = pl.multiple_of(row // win * win, win)
        here = jax.lax.broadcasted_iota(jnp.int32, pool_out_ref.shape[1:], 0) == row - top
        window = pool_ref[0, pl.ds(top, win), :].astype(jnp.float32)
        window = jnp.where(here, new_ref[0], window).astype(pool_out_ref.dtype)
        pool_ref[0, pl.ds(top, win), :] = window
        pool_out_ref[0] = window

    q = q_ref[0]  # [H, W]
    page = pool_ref[0]  # [ps, W]
    s = jax.lax.dot_general(
        q, page, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale  # [H, ps]
    cols = j * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(cols < length, s, NEG_INF)
    m_prev = m_scr[:, 0]
    m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_cur)
    p = jnp.exp(s - m_cur[:, None])
    l_cur = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot_general(
        p.astype(page.dtype), pool_ref[0, :, pl.ds(0, v_width)], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    m_scr[...] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    @pl.when(j == last)
    def _finalize():
        l = l_scr[:, 0]
        o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)[:, None]).astype(o_ref.dtype)


def latent_paged_attention(q, row_new, pool, lengths, page_indices, layer, *, v_width, scale,
                           walk=None, interpret=False):
    """Latent paged decode attention (the Pallas kernel; arguments and result
    as ``latent_attention_reference``). q's head count should be a sublane
    multiple (8) and the pool's row a lane multiple (``latent_row_width``).
    walk: ``live_pages`` of these lengths and this table, for a caller with
    several calls on them (a decode step's layers). The returned pool aliases
    the argument. Runs on a TPU backend, or anywhere with interpret=True, and
    raises elsewhere."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"latent_paged_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    B, H, W = q.shape
    ps = pool.shape[2]
    if walk is None:
        walk = live_pages(lengths, page_indices, ps)
    slots, pages, where, win_page, win_row, count = walk
    win = min(ps, WINDOW_ROWS)

    def whole(t, lens, layer, slots, pages, where, win_page, win_row):
        return (slots[t], 0, 0)

    def page(t, lens, layer, slots, pages, where, win_page, win_row):
        return (layer[0], where[t], 0, 0)

    def token_window(t, lens, layer, slots, pages, where, win_page, win_row):
        return (layer[0], win_page[t], win_row[t], 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(count[0],),  # a runtime value: one compiled call serves every batch
        in_specs=[
            pl.BlockSpec((1, H, W), whole),
            pl.BlockSpec((1, 1, W), whole),
            pl.BlockSpec((None, 1, ps, W), page),  # the layer axis squeezed
        ],
        out_specs=[
            pl.BlockSpec((1, H, v_width), whole),
            pl.BlockSpec((None, 1, win, W), token_window),
        ],
        scratch_shapes=[
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, LANES), jnp.float32),
            pltpu.VMEM((H, v_width), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _latent_kernel, scale=scale, ps=ps, n_pages=page_indices.shape[1], v_width=v_width)
    # The token's row rounded as the pool stores it, handed over in f32 (a
    # 32-bit select needs no packed-row mask).
    row_new = row_new.astype(pool.dtype).astype(jnp.float32)[:, None, :]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, H, v_width), q.dtype),
            jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        ],
        # operands count the seven scalar-prefetch arrays: 9 is the pool
        input_output_aliases={9: 1},
        compiler_params=pltpu.CompilerParams(
            # in order: a sequence's pages accumulate into one scratch
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        name="latent_attn",
    )(lengths.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      slots, pages, where, win_page, win_row, q, row_new, pool)
