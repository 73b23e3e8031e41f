"""Flash attention for TPU: Pallas forward + backward kernels.

Memory-bound op #1 in the transformer. The kernel streams K/V blocks through
VMEM with an online-softmax accumulator so the S×S score matrix never touches
HBM (HBM traffic O(S·D) instead of O(S²)). Forward saves the per-row
log-sum-exp so the backward pass recomputes probabilities blockwise.

Native GQA: K/V carry their own (smaller) head count — the q-head grid maps
onto kv heads through the BlockSpec index maps (q head h reads kv head
h // group), so grouped K/V are NEVER materialized at full head count (the
whole point of GQA is the smaller KV HBM footprint; a jnp.repeat would throw
it away). The dk/dv backward iterates the q-heads of each group in its inner
grid axis, accumulating into one kv-head scratch.

Packed sequences: optional ``segment_ids`` [B, S] masks attention to
same-segment pairs (rows attend only within their segment), composed with the
causal mask — the standard packed-example training contract.

Layout: kernels operate on [B*H, S, D] for Q (and [B*KV, S, D] for K/V);
blocks are (block_q × D)/(block_k × D) with D a lane multiple. Grid iteration
puts the reduction axis innermost ("arbitrary") so f32 accumulators live in
VMEM scratch across steps (pallas_guide.md: Grid and Block Specifications).

Live sub-tiles (PR 53): the blocks are what a grid step fetches and what the
accumulators hold; what it COMPUTES are the block's sub-tiles of ``SUB_TILE``
rows and columns that can hold a live pair (``_walk_live``). A causal call
skips, by scalars alone, a sub-tile wholly above the diagonal, wholly below
the window's band or, with ids, one whose rows' id range [min, max] does not
meet its columns': two [B, S / SUB_TILE] tables made from the ids by one
small reduction outside the kernel and handed in by scalar prefetch. That is
exact for any ids, sorted or not (disjoint ranges hold no equal pair; ranges
that meet and hold none are computed and masked as every computed sub-tile
is, by ``_mask_scores``). Two more tables let the index maps hold a wholly
dead grid step at a block that is fetched anyway, so it moves no bytes. A
skipped sub-tile's every score was masked, so the result differs from the
whole-block kernels' only by the order of the float32 sums. ``live_tiles``
counts what a call visits.

The reference framework has no attention kernels (compute is delegated to
torch/vLLM, SURVEY.md §2.4); functional parity target is the standard flash
attention contract (causal MHA/GQA with LSE residuals + segment masking).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30
# A windowed call's three kernels carry this behind their names (`flash_attn_fwd_win`), so that a trace of a
# model with window and full layers tells the two kinds of call apart.
_WINDOWED = "_win"


# ---------------------------------------------------------------------------
# Reference implementation (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal=True, scale=None, segment_ids=None, window=0):
    """q: [B, S, H, D]; k,v: [B, S, KV, D] (KV divides H) -> [B, S, H, D].
    Softmax in f32. segment_ids: optional [B, S] int; attention is masked to
    same-segment pairs (packed sequences). window (causal only): row i sees
    columns i - window < j <= i; 0 is no window."""
    *_, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    S_q, S_k = s.shape[-2], s.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q)
        if window:
            mask = mask & ~jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q - window)
        s = jnp.where(mask, s, NEG_INF)
    if segment_ids is not None:
        seg = (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
        s = jnp.where(seg, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def flash_supported(seq_len: int) -> bool:
    """Whether ``flash_attention`` can run compiled on this process's backend
    at this sequence length — what an "auto" caller observes to choose
    between the kernel and ``mha_reference``."""
    return jax.default_backend() == "tpu" and seq_len % 128 == 0


def _mask_scores(s, q_start, k_start, causal, seg_q, seg_k, window=0, q_axis=0):
    """Apply causal (inside `window` columns where there is one) + segment
    masks to a block of scores whose axis `q_axis` are the q rows (1: the
    block is transposed, k rows by q columns). seg_q / seg_k: the ids shaped
    to broadcast against it, or None."""
    if causal:
        rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
        cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
        seen = rows >= cols
        if window:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen, s, NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q == seg_k, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# Live sub-tiles: what a causal call computes of a block
# ---------------------------------------------------------------------------

# A causal call walks a grid step's block in sub-tiles of this many rows and columns (a block that is smaller, or
# that it does not divide, in the largest part of it that does) and computes those that can hold a live pair.
# Chosen on the chip (PERF.md section 6, PR 53): at 256 a sub-tile's fixed cost, which goes with its rows, eats
# what the finer skipping saves; at 512 the train cell's three kernels take 0.71 of the whole-block bodies' time.
SUB_TILE = 512


def _sub_tile(block_q, block_k, sub_tile):
    """The sub-tile of a call's blocks: `sub_tile`, cut to what divides both."""
    return math.gcd(math.gcd(block_q, block_k), sub_tile)


def _segment_ranges(segment_ids, sub: int):
    """[B, S] ids (numpy or jax) -> the least and the largest id of each run of `sub` positions, each [B, S // sub]."""
    B, S = segment_ids.shape
    runs = segment_ids.reshape(B, S // sub, sub)
    return runs.min(-1), runs.max(-1)


def _live_sub_tiles(segment_ids, S, sub, window):
    """bool [B, S // sub, S // sub] (B = 1 without ids; numpy or jax as the ids are): the sub-tiles of a causal
    score matrix that can hold a live pair. Not wholly above the diagonal, not wholly below the band and, with ids,
    the rows' id range meets the columns': exact for any ids, since disjoint ranges hold no equal pair (ranges that
    meet and hold none are computed and masked)."""
    rows, cols = np.arange(0, S, sub)[:, None], np.arange(0, S, sub)[None, :]
    live = cols <= rows + sub - 1
    if window:
        live &= cols + sub - 1 > rows - window
    if segment_ids is None:
        return live[None]
    lo, hi = _segment_ranges(segment_ids, sub)
    return live[None] & (lo[:, :, None] <= hi[:, None, :]) & (lo[:, None, :] <= hi[:, :, None])


def live_tiles(segment_ids, S, block_q, block_k, window=0):
    """(live, causal): the sub-tiles the causal kernels compute for these ids ([B, S] numpy, or None) at these
    blocks, and the sub-tiles on or under the diagonal, both summed over the rows. The kernels' tables are built
    from the same `_live_sub_tiles`, so this is the record of what they visit."""
    sub = _sub_tile(min(block_q, S), min(block_k, S), SUB_TILE)
    ids = None if segment_ids is None else np.asarray(segment_ids)
    causal = _live_sub_tiles(None, S, sub, 0).sum() * (1 if ids is None else len(ids))
    return int(_live_sub_tiles(ids, S, sub, window).sum()), int(causal)


def _walk_tables(seg, S, block_q, block_k, sub, window, hold):
    """The scalar tables of a causal call, each flat int32 for SMEM. First the two an index map reads to hold a
    wholly dead grid step at a block that is fetched anyway, so that it moves no bytes: for each q block the first
    and the last k block with a live sub-tile (hold "k": the forward's and dq's K/V), or for each k block the first
    and the last q block (hold "q": dk/dv's q/do); [B * blocks] with ids, [blocks] without. Then, with ids (seg
    [B, 8, S]), the two the body reads: `_segment_ranges` of each run of `sub` positions, [B * S // sub]."""
    ids = None if seg is None else seg[:, 0, :]
    live = _live_sub_tiles(ids, S, sub, window)
    blocks = live.reshape(-1, S // block_q, block_q // sub, S // block_k, block_k // sub).any((2, 4))
    if hold == "q":
        blocks = blocks.swapaxes(1, 2)
    tables = [blocks.argmax(-1), blocks.shape[-1] - 1 - blocks[..., ::-1].argmax(-1)]
    if ids is not None:
        tables += _segment_ranges(ids, sub)
    return tuple(jnp.asarray(t, jnp.int32).reshape(-1) for t in tables)


def _div(a, b: int):
    """a // b of a traced scalar that is not negative. `//` floors, which lowers to a dozen operations and two
    `sign`s, each traced anew wherever a body or an index map is lowered (every warm start of every program that
    holds a flash call); the truncating division is one."""
    return lax.div(a, jnp.int32(b))


def _held(step, block, tables, batch, n_blocks):
    """The block an index map fetches at a grid step whose own is `step`, for block `block` of the other side:
    `step` held inside that block's live range (`_walk_tables`; batch: the call's row, 0 without ids)."""
    at = batch * n_blocks + block
    return lax.min(lax.max(step, tables[0][at]), tables[1][at])


def _band_columns(row0, n_rows, col0, sub, n_sub, window):
    """Of `n_sub` runs of `sub` columns from `col0` on, the range [lo, hi) that rows row0 .. row0 + n_rows - 1 of a
    causal score matrix can see: not wholly above the diagonal nor, with a window, wholly below the band."""
    zero = jnp.int32(0)
    hi = lax.min(_div(lax.max(row0 + n_rows - 1 - col0 + sub, zero), sub), jnp.int32(n_sub))
    lo = _div(lax.max(row0 - window + 1 - col0, zero), sub) if window else zero
    return lo, hi


def _band_rows(col0, n_cols, row0, sub, n_sub, window):
    """The mirror: of `n_sub` runs of `sub` rows from `row0` on, the range [lo, hi) that can see any of columns
    col0 .. col0 + n_cols - 1."""
    zero = jnp.int32(0)
    lo = _div(lax.max(col0 - row0, zero), sub)
    hi = jnp.int32(n_sub)
    if window:
        hi = lax.min(_div(lax.max(col0 + n_cols - 1 + window - row0 + sub - 1, zero), sub), hi)
    return lo, hi


def _walk_live(strip, n_strips, n_tiles, live_range, overlap):
    """The walk of a block's live sub-tiles, strip by strip (a strip: the sub-tiles that share their rows, or in
    dk/dv's kernel their columns): two rolled loops around one sub-tile's body, so the program does not grow with
    the sub-tiles a block holds (a loop of one turn, as where the block is the sub-tile, is no loop). `strip(o)`
    reads what the strip's sub-tiles share and answers `tile(n)`, which computes sub-tile n of the strip into the
    accumulators. Of a strip's `n_tiles` only `live_range(o)` = [lo, hi) is walked, the part inside the causal
    band, and of that only the sub-tiles whose id ranges meet (`overlap(o, n)`; None: no ids)."""
    from jax.experimental import pallas as pl

    def strips(o, _):
        lo, hi = live_range(o)

        @pl.when(lo < hi)
        def _():
            tile = strip(o)

            def tiles(n, _):
                if overlap is None:
                    tile(n)
                else:
                    pl.when(overlap(o, n))(functools.partial(tile, n))
                return 0

            if n_tiles == 1:
                tiles(0, 0)
            else:
                lax.fori_loop(lo, hi, tiles, 0)

        return 0

    if n_strips == 1:
        strips(0, 0)
    else:
        lax.fori_loop(0, n_strips, strips, 0)


def _first(i, sub):
    """The first row (column) of run i of `sub`: aligned, which the compiler is told where i is a loop's index."""
    from jax.experimental import pallas as pl

    return i * sub if isinstance(i, int) else pl.multiple_of(i * sub, sub)


def _ranges_meet(ranges, b, heads, S, sub_q, sub_k, q_start, k_start):
    """meet(i, j): whether the id range of run i of the block's q rows meets that of run j of its k columns (SMEM
    reads and scalar compares), from the (min, max) tables of `_walk_tables`; None for a call without ids. b, heads:
    the grid's first index and the heads it folds a row; q_start, k_start: the block's first row and column."""
    if not ranges:
        return None
    lo_ref, hi_ref = ranges
    batch = _div(b, heads)
    q_run0 = batch * (S // sub_q) + _div(q_start, sub_q)
    k_run0 = batch * (S // sub_k) + _div(k_start, sub_k)

    def meet(i, j):
        q_run, k_run = q_run0 + i, k_run0 + j
        return (lo_ref[q_run] <= hi_ref[k_run]) & (lo_ref[k_run] <= hi_ref[q_run])

    return meet


def _lanes(x, n):
    """x [rows, 128], a value a row repeated along the lanes -> [rows, n], the same."""
    from jax.experimental.pallas import tpu as pltpu

    if n == x.shape[1]:
        return x
    if n % x.shape[1] == 0:
        return pltpu.repeat(x, n // x.shape[1], 1)
    return jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def band_blocks(S: int, block_q: int, block_k: int, window: int) -> int:
    """K blocks a q block of a windowed causal forward visits: the grid's
    innermost length. A q block's rows see columns q_start - window + 1 ..
    q_start + block_q - 1; the most k blocks those lie in, over the q blocks
    (2 at blocks of 512 and a window of 512, whatever S). Without a window,
    every k block."""
    if not window:
        return -(-S // block_k)
    return max((q_start + block_q - 1) // block_k - max(q_start - window + 1, 0) // block_k + 1
               for q_start in range(0, S, block_q))


def _first_band_block(qi, block_q, block_k, window):
    """The first k block a q block's band touches."""
    return _div(lax.max(qi * block_q - window + 1, jnp.int32(0)), block_k)


def _walk_of(causal, seg, S, block_q, block_k, window, hold, sub_tile):
    """(sub_q, sub_k, tables) of a call: a causal call's sub-tile and scalar tables; a call that is not causal
    skips nothing, so its sub-tile is the block and it has no table."""
    if not causal:
        return block_q, block_k, ()
    sub = _sub_tile(block_q, block_k, sub_tile)
    return sub, sub, _walk_tables(seg, S, block_q, block_k, sub, window, hold)


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, block_q, block_k, sub_q, sub_k, n_k, causal, has_seg, n_tables, H, S, window=0):
    """Grid (BH, q blocks, k steps). Without a window the k steps are the k
    blocks. With one they are the `band_blocks` blocks from the band's first
    on: a block wholly outside the band is no grid step (a step past the
    diagonal, which the last q blocks of a short band have none of, is held
    at the diagonal's block by the index maps). Inside a step only the live
    sub-tiles are computed (`_walk_live`). m, l and the q rows' ids are kept
    a value a row repeated along 128 lanes, so a row's statistics never
    change layout between sub-tiles."""
    from jax.experimental import pallas as pl

    ranges, refs = refs[2:n_tables], refs[n_tables:]
    if has_seg:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, segq_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        sq_ref = sk_ref = segq_scr = None

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)
        if has_seg:  # relaid once a q block, from along the lanes to a row a sublane
            segq_scr[:, :] = jnp.broadcast_to(sq_ref[0, 0, :][:, None], segq_scr.shape)

    q_start = qi * block_q
    k_start = (ki + _first_band_block(qi, block_q, block_k, window) if window else ki) * block_k

    def strip(i):
        r0 = _first(i, sub_q)
        rows = pl.ds(r0, sub_q)
        q = q_ref[0, rows, :]

        def tile(j):
            c0 = _first(j, sub_k)
            cols = pl.ds(c0, sub_k)
            s = jax.lax.dot_general(
                q, k_ref[0, cols, :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [sub_q, sub_k]
            seg_q = _lanes(segq_scr[rows, :], sub_k) if has_seg else None
            seg_k = sk_ref[0, 0:1, cols] if has_seg else None
            s = _mask_scores(s, q_start + r0, k_start + c0, causal, seg_q, seg_k, window)
            m_prev = m_scr[rows, :]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - _lanes(m_cur, sub_k))
            l_scr[rows, :] = l_scr[rows, :] * alpha + jnp.sum(p, axis=1, keepdims=True)
            acc_scr[rows, :] = acc_scr[rows, :] * _lanes(alpha, acc_scr.shape[1]) + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[0, cols, :], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[rows, :] = m_cur

        return tile

    n_sk = block_k // sub_k
    live_range = lambda i: _band_columns(q_start + i * sub_q, sub_q, k_start, sub_k, n_sk, window) if causal else (0, n_sk)
    overlap = _ranges_meet(ranges, bh, H, S, sub_q, sub_k, q_start, k_start)
    _walk_live(strip, block_q // sub_q, n_sk, live_range, overlap)

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[:, :]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :] = (acc_scr[:, :] / _lanes(l_safe, acc_scr.shape[1])).astype(o_ref.dtype)
        lse = (m_scr[:, :] + jnp.log(l_safe))[:, 0]  # [bq]
        # lse is materialized as [BH, 8, S] (8 sublanes to satisfy the
        # (8, 128) min-tile rule); broadcast the row across sublanes.
        lse_ref[0, :, :] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "group", "H", "interpret", "window", "sub_tile"))
def _fwd_pallas(q, k, v, seg, *, causal, scale, block_q, block_k, group, H, interpret, sub_tile, window=0):
    """q: [BH, S, D]; k,v: [BKV, S, D]; seg: [B, 8, S] i32 or None
    -> (o [BH, S, D], lse [BH, S] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    n_q = pl.cdiv(S, block_q)
    n_k = band_blocks(S, block_q, block_k, window)  # the grid's k steps
    has_seg = seg is not None
    sub_q, sub_k, tables = _walk_of(causal, seg, S, block_q, block_k, window, "k", sub_tile)

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, sub_q=sub_q, sub_k=sub_k, n_k=n_k,
        causal=causal, has_seg=has_seg, n_tables=len(tables), H=H, S=S, window=window,
    )

    def k_block(b, qi, ki, tables):
        """The k block of a grid step: the step itself, or with a window the
        band's first block plus the step; a causal call's held inside the q
        block's live range, so that a wholly dead step fetches nothing new."""
        if not tables:
            return ki
        step = _first_band_block(qi, block_q, block_k, window) + ki if window else ki
        return _held(step, qi, tables, _div(b, H) if has_seg else 0, n_q)

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki, *_: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki, *t: (_div(b, group), k_block(b, qi, ki, t), 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki, *t: (_div(b, group), k_block(b, qi, ki, t), 0)),
    ]
    inputs = [q, k, v]
    scratch_shapes = [
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, 128), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, *_: (_div(b, H), 0, qi)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki, *t: (_div(b, H), 0, k_block(b, qi, ki, t))),
        ]
        inputs += [seg, seg]
        scratch_shapes.append(pltpu.VMEM((block_q, 128), jnp.int32))
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(BH, n_q, n_k),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, D), lambda b, qi, ki, *_: (b, qi, 0)),
                pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, *_: (b, 0, qi)),
            ],
            scratch_shapes=scratch_shapes,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_fwd" + _WINDOWED * bool(window),
    )(*tables, *inputs)


# ---------------------------------------------------------------------------
# Pallas backward (dk/dv kernel + dq kernel)
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(*refs, scale, block_q, block_k, sub_q, sub_k, n_q, group, causal, has_seg, n_tables, KV, S,
                    window=0):
    """Grid: (B*KV, n_k, group*n_q) — the inner axis walks every (q-head of
    the group) × (q-block), accumulating this kv head's dk/dv in scratch.
    A strip is sub_k columns, and the scores are computed transposed,
    [sub_k, sub_q]: the q rows' lse, delta and ids are used as they lie
    (along the lanes), and neither p nor ds is transposed for its product."""
    from jax.experimental import pallas as pl

    ranges, refs = refs[2:n_tables], refs[n_tables:]
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        sq_ref = sk_ref = None

    bkv = pl.program_id(0)
    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = lax.rem(t, jnp.int32(n_q))

    @pl.when(t == 0)
    def _init():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def strip(j):
        c0 = _first(j, sub_k)
        cols = pl.ds(c0, sub_k)
        k = k_ref[0, cols, :]
        v = v_ref[0, cols, :]
        seg_k = sk_ref[0, 0, cols][:, None] if has_seg else None

        def tile(i):
            r0 = _first(i, sub_q)
            rows = pl.ds(r0, sub_q)
            q = q_ref[0, rows, :]
            do = do_ref[0, rows, :]
            s = jax.lax.dot_general(
                k, q, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [sub_k, sub_q]
            seg_q = sq_ref[0, 0:1, rows] if has_seg else None
            s = _mask_scores(s, q_start + r0, k_start + c0, causal, seg_q, seg_k, window, q_axis=1)
            p = jnp.exp(s - lse_ref[0, 0:1, rows])  # [sub_k, sub_q] f32
            # dv += p^T @ do, p held transposed
            dv_scr[cols, :] += jax.lax.dot_general(
                p.astype(do.dtype), do, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            # dp = do @ v^T ; ds = p * (dp - delta), both held transposed
            dp = jax.lax.dot_general(v, do, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
            ds = p * (dp - delta_ref[0, 0:1, rows]) * scale
            dk_scr[cols, :] += jax.lax.dot_general(
                ds.astype(q.dtype), q, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )

        return tile

    n_sq = block_q // sub_q
    live_range = lambda j: _band_rows(k_start + j * sub_k, sub_k, q_start, sub_q, n_sq, window) if causal else (0, n_sq)
    meet = _ranges_meet(ranges, bkv, KV, S, sub_q, sub_k, q_start, k_start)
    _walk_live(strip, block_k // sub_k, n_sq, live_range, meet and (lambda j, i: meet(i, j)))

    @pl.when(t == group * n_q - 1)
    def _finalize():
        dk_ref[0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, block_q, block_k, sub_q, sub_k, n_k, causal, has_seg, n_tables, H, S, window=0):
    from jax.experimental import pallas as pl

    ranges, refs = refs[2:n_tables], refs[n_tables:]
    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        sq_ref = sk_ref = None

    bh = pl.program_id(0)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:, :] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def strip(i):
        r0 = _first(i, sub_q)
        rows = pl.ds(r0, sub_q)

        def tile(j):
            c0 = _first(j, sub_k)
            cols = pl.ds(c0, sub_k)
            k = k_ref[0, cols, :]
            do = do_ref[0, rows, :]
            s = jax.lax.dot_general(
                q_ref[0, rows, :], k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale
            seg_q = sq_ref[0, 0, rows][:, None] if has_seg else None
            seg_k = sk_ref[0, 0:1, cols] if has_seg else None
            s = _mask_scores(s, q_start + r0, k_start + c0, causal, seg_q, seg_k, window)
            p = jnp.exp(s - lse_ref[0, 0, rows][:, None])
            dp = jax.lax.dot_general(
                do, v_ref[0, cols, :], (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            )
            ds = p * (dp - delta_ref[0, 0, rows][:, None]) * scale
            dq_scr[rows, :] += jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )

        return tile

    n_sk = block_k // sub_k
    live_range = lambda i: _band_columns(q_start + i * sub_q, sub_q, k_start, sub_k, n_sk, window) if causal else (0, n_sk)
    overlap = _ranges_meet(ranges, bh, H, S, sub_q, sub_k, q_start, k_start)
    _walk_live(strip, block_q // sub_q, n_sk, live_range, overlap)

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0, :, :] = dq_scr[:, :].astype(dq_ref.dtype)


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "scale", "block_q", "block_k", "group", "H", "KV", "interpret", "window", "sub_tile"))
def _bwd_pallas(res, g, *, causal, scale, block_q, block_k, group, H, KV, interpret, sub_tile, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse, seg = res
    do = g
    BH, S, D = q.shape
    BKV = k.shape[0]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(S, block_k)
    has_seg = seg is not None
    sub_q, sub_k, k_tables = _walk_of(causal, seg, S, block_q, block_k, window, "k", sub_tile)
    q_tables = _walk_of(causal, seg, S, block_q, block_k, window, "q", sub_tile)[2]
    walk = dict(scale=scale, block_q=block_q, block_k=block_k, sub_q=sub_q, sub_k=sub_k, causal=causal,
                has_seg=has_seg, n_tables=len(k_tables), S=S, window=window)

    def q_block(b, ki, t, tables):
        """dk/dv's q block of a grid step, a causal call's held inside the k block's live range."""
        qi = lax.rem(t, jnp.int32(n_q))
        return _held(qi, ki, tables, _div(b, KV) if has_seg else 0, n_k) if tables else qi

    def k_block(b, qi, ki, tables):
        """dq's k block of a grid step, held likewise inside the q block's."""
        return _held(ki, qi, tables, _div(b, H) if has_seg else 0, n_q) if tables else ki

    delta_row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta_row[:, None, :], (BH, 8, S))  # sublane-tiled like lse

    # dk/dv: grid over kv heads; inner axis covers (group member g, q block).
    # q-head for (kv-fold index b, inner step t): batch*H + kv*group + g.
    def qhead(b, t):
        return _div(b, KV) * H + lax.rem(b, jnp.int32(KV)) * group + _div(t, n_q)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, ki, t, *tb: (qhead(b, t), q_block(b, ki, t, tb), 0)),
        pl.BlockSpec((1, block_k, D), lambda b, ki, t, *_: (b, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, ki, t, *_: (b, ki, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, ki, t, *tb: (qhead(b, t), q_block(b, ki, t, tb), 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, ki, t, *tb: (qhead(b, t), 0, q_block(b, ki, t, tb))),
        pl.BlockSpec((1, 8, block_q), lambda b, ki, t, *tb: (qhead(b, t), 0, q_block(b, ki, t, tb))),
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, ki, t, *tb: (_div(b, KV), 0, q_block(b, ki, t, tb))),
            pl.BlockSpec((1, 8, block_k), lambda b, ki, t, *_: (_div(b, KV), 0, ki)),
        ]
        dkv_inputs += [seg, seg]
    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, n_q=n_q, group=group, KV=KV, **walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(q_tables),
            grid=(BKV, n_k, group * n_q),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, block_k, D), lambda b, ki, t, *_: (b, ki, 0)),
                pl.BlockSpec((1, block_k, D), lambda b, ki, t, *_: (b, ki, 0)),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_k, D), jnp.float32),
                pltpu.VMEM((block_k, D), jnp.float32),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, S, D), v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_dkv" + _WINDOWED * bool(window),
    )(*q_tables, *dkv_inputs)
    dk, dv = dkv

    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki, *_: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki, *tb: (_div(b, group), k_block(b, qi, ki, tb), 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki, *tb: (_div(b, group), k_block(b, qi, ki, tb), 0)),
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki, *_: (b, qi, 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, *_: (b, 0, qi)),
        pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, *_: (b, 0, qi)),
    ]
    dq_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki, *_: (_div(b, H), 0, qi)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki, *tb: (_div(b, H), 0, k_block(b, qi, ki, tb))),
        ]
        dq_inputs += [seg, seg]
    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, n_k=n_k, H=H, **walk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(k_tables),
            grid=(BH, n_q, n_k),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki, *_: (b, qi, 0)),
            scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_dq" + _WINDOWED * bool(window),
    )(*k_tables, *dq_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash_folded(q, k, v, seg, causal, scale, block_q, block_k, group, H, KV, interpret, window, sub_tile):
    o, _ = _fwd_pallas(
        q, k, v, seg, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        group=group, H=H, interpret=interpret, window=window, sub_tile=sub_tile,
    )
    return o


def _flash_fwd(q, k, v, seg, causal, scale, block_q, block_k, group, H, KV, interpret, window, sub_tile):
    o, lse = _fwd_pallas(
        q, k, v, seg, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        group=group, H=H, interpret=interpret, window=window, sub_tile=sub_tile,
    )
    return o, (q, k, v, o, lse, seg)


def _flash_bwd(causal, scale, block_q, block_k, group, H, KV, interpret, window, sub_tile, res, g):
    dq, dk, dv = _bwd_pallas(
        res, g, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        group=group, H=H, KV=KV, interpret=interpret, window=window, sub_tile=sub_tile,
    )
    seg = res[5]
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_folded.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=True, scale=None, segment_ids=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, window=0):
    """Flash attention. q: [B, S, H, D]; k,v: [B, S, KV, D] -> [B, S, H, D].

    ``window`` (causal only; 0 is none): row i sees columns i - window < j <=
    i. The forward visits the k blocks of a q block's band and no other
    (``band_blocks`` grid steps a q block, not S / block_k); the backward
    kernels' grids walk every block. All three compute, of a block, only the
    sub-tiles that can hold a live pair (module docstring), and a wholly dead
    grid step fetches nothing.

    KV may be smaller than H (GQA): kv heads are shared across groups of
    H // KV query heads inside the kernel — no repeat/materialization.
    ``segment_ids`` [B, S] masks attention to same-segment pairs (packed
    sequences). These are the Pallas kernels: they run on a TPU backend, or
    anywhere with interpret=True (the CPU test path), and raise elsewhere —
    a caller that may land on another backend chooses ``mha_reference``
    itself from what it observes. S must be a multiple of 128 (callers pad);
    D should be a lane multiple (64/128/256).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"n_heads {H} not divisible by kv_heads {KV}")
    if S % 128:
        raise ValueError(f"flash_attention needs S % 128 == 0, got S={S}")
    if window and not causal:
        raise ValueError("flash_attention: a window is written for causal attention")
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"flash_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # Blocks must divide S exactly: Pallas pads out-of-bounds block reads with
    # undefined data, and the non-causal path applies no mask that would
    # neutralize padded key columns. S is a multiple of 128 here, so halving
    # always converges to a divisor.
    while S % block_q:
        block_q //= 2
    while S % block_k:
        block_k //= 2
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, S, D)  # [B,S,h,D] -> [B*h,S,D]
    seg = None
    if segment_ids is not None:
        seg = jnp.broadcast_to(
            segment_ids.astype(jnp.int32)[:, None, :], (B, 8, S)
        )  # sublane-tiled like lse
    # SUB_TILE is read HERE, at the call: what a trace depends on is in its key, or the cache serves another's
    o = _flash_folded(
        fold(q), fold(k), fold(v), seg, causal, scale, block_q, block_k,
        group, H, KV, interpret, int(window), SUB_TILE,
    )
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
