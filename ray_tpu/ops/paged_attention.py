"""Paged decode attention for TPU: single-token GQA queries against a
block-paged KV cache, with the token's own K/V written into the cache by the
same call.

The serving engine's KV cache is a pool of fixed-size pages, every layer's
in one array ([L, KV, P_total, page_size, D]); each sequence owns a page list
(its page table row). Decode attention must therefore gather a sequence's
keys from non-contiguous pages. An XLA gather would materialize the whole
per-sequence KV every step (HBM copy of the entire working set per token);
the Pallas kernel instead walks the page table through scalar prefetch — the
BlockSpec index map reads the NEXT page index while the current page is in
flight, so pages stream through VMEM exactly once with no materialized gather.

The pool never moves. A caller that slices its layer out first moves that
layer's whole pool every call (100 MB a layer in the serve cells), and one
that writes the token's K/V beside the kernel pays an operation a slot
(PERF.md section 6, PR 25). So the layer is an operand that the index maps
add to each page's address, and the pools are aliased to the call's outputs:
the token's row is spliced into its page where the page lies in VMEM for
attention anyway, and the few rows around it are stored back.

Kernel shape: a grid of one axis whose steps are the batch's live pages,
sequence after sequence and each one's pages in ascending order
(``live_pages``: every step's sequence, page and addresses and their count,
through scalar prefetch; the count is the grid's length, a runtime value). A
call costs what the tokens in the cache cost, ceil(length / page_size) steps
a sequence, and a page beyond a sequence's length costs nothing: no step, no
DMA, no predicate. Before PR 33 the grid was the whole table, (B,
pages_per_seq) = 32 x 16 or 32 x 32 in the serve cells, and a step past a
sequence's length skipped its arithmetic and still cost 0.4 us: 0.21 ms a
call with 41 live steps of 512, 44-46% of the device's busy time in every
serve cell (PERF.md section 6, PR 33). The online-softmax accumulator lives
in VMEM scratch across a sequence's steps.

A layer with an attention window (``window`` > 0: position i sees j with
i - window < j <= i) keeps no page table. Its pool holds, a sequence, a ring
of ``ring`` pages (``ring_pages(window, page_size)``: ceil(window / page_size)
+ 1, the window's pages and the one being written), sequence b's at pool pages
b * ring .. b * ring + ring - 1, and page j of a sequence lies at ring page
j % ring: the token written at position p replaces the one at p - ring *
page_size, which no later query sees. The walk (``live_pages``) starts at the
page that holds position length - window, so a call costs the window's pages
whatever the context, and the kernel masks that first page's older columns.
Such a call is named ``window_attn`` in the trace, the others ``paged_attn``.

The reference framework delegates paged KV to vLLM
(llm/_internal/serve/engines/vllm/vllm_engine.py:174); this is the TPU-native
equivalent for our own engine.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference implementation (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def paged_attention_reference(q, k_new, v_new, k_pages, v_pages, lengths, page_indices,
                              layer, scale=None):
    """q: [B, H, D]; k_new/v_new: [B, KV, D], the current token's;
    k_pages/v_pages: [L, KV, P_total, ps, D]; lengths: [B] (valid token count
    per sequence, INCLUDING the current position); page_indices:
    [B, pages_per_seq]; layer: scalar index into L
    -> (o [B, H, D], k_pages, v_pages) with the token written at position
    lengths - 1 of each sequence."""
    B, H, D = q.shape
    _, KV, _, ps, _ = k_pages.shape
    group = H // KV
    ppseq = page_indices.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = lengths - 1
    page = page_indices[jnp.arange(B), jnp.minimum(pos // ps, ppseq - 1)]
    for b in range(B):  # a row a sequence, in place in a donated or loop-carried pool
        at = (layer, 0, page[b], pos[b] % ps, 0)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, k_new[b].astype(k_pages.dtype)[None, :, None, None, :], at)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, v_new[b].astype(v_pages.dtype)[None, :, None, None, :], at)
    k_layer = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False)
    v_layer = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False)
    # [KV, B, ppseq, ps, D] -> [B, KV, S_virt, D]
    k = k_layer[:, page_indices].transpose(1, 0, 2, 3, 4).reshape(B, KV, ppseq * ps, D)
    v = v_layer[:, page_indices].transpose(1, 0, 2, 3, 4).reshape(B, KV, ppseq * ps, D)
    qg = q.reshape(B, KV, group, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k).astype(jnp.float32) * scale
    valid = (jnp.arange(ppseq * ps)[None, :] < lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v)
    return o.reshape(B, H, D), k_pages, v_pages


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _page_range(length, ps, n_pages, window=0):
    """(first, last): the pages of its table a sequence of `length` tokens
    (>= 1) attends, both ends included: last - first + 1 page steps, which is
    ceil(length / ps) while the sequence is inside its table. ``last`` holds
    the current position, the last of `length` (a sequence run past its table
    stays inside its last page); ``first`` is 0, or with an attention window
    the page of position length - window, the oldest the current one sees
    (the kernel masks that page's older columns). Never empty, whatever
    `length`: a sequence with no step would leave its row of the output
    unwritten."""
    first = jnp.zeros_like(length)
    last = jnp.clip((length - 1) // ps, first, n_pages - 1)
    if window:
        first = jnp.minimum(jnp.maximum(length - window, 0) // ps, last)
    return first, last


def ring_pages(window: int, page_size: int) -> int:
    """Pages of a window layer's ring, a sequence: those a window can touch
    (ceil(window / page_size) + 1 when it straddles page boundaries), the page
    being written among them. A decode step writes one row and then reads, so
    a block of steps needs no page beyond these."""
    return -(-window // page_size) + 1


def window_attention_reference(q, k_new, v_new, k_pages, v_pages, lengths, layer, window, scale=None):
    """``paged_attention_reference`` for a layer with a window, over rings:
    k_pages/v_pages [L, KV, B * ring, ps, D], sequence b's ring at pages
    b * ring ..; the token written at ring row (lengths - 1) % (ring * ps),
    then every ring row attended whose position, the newest one congruent to
    it at most lengths - 1, lies inside the window. -> (o, k_pages, v_pages)."""
    B, H, D = q.shape
    _, KV, n_ring, ps, _ = k_pages.shape
    rows = n_ring // B * ps  # a sequence's ring, in rows
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = lengths - 1
    for b in range(B):
        at = (layer, 0, b * (rows // ps) + pos[b] % rows // ps, pos[b] % ps, 0)
        k_pages = jax.lax.dynamic_update_slice(
            k_pages, k_new[b].astype(k_pages.dtype)[None, :, None, None, :], at)
        v_pages = jax.lax.dynamic_update_slice(
            v_pages, v_new[b].astype(v_pages.dtype)[None, :, None, None, :], at)
    k = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False).reshape(KV, B, rows, D)
    v = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False).reshape(KV, B, rows, D)
    r = jnp.arange(rows)[None, :]
    held = pos[:, None] - (pos[:, None] - r) % rows  # [B, rows]: the position a ring row holds
    valid = (held >= 0) & (held > pos[:, None] - window)
    s = jnp.einsum("bkgd,kbsd->bkgs", q.reshape(B, KV, group, D), k).astype(jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,kbsd->bkgd", p, v)
    return o.reshape(B, H, D), k_pages, v_pages


WINDOW_ROWS = 16  # of the token's page, stored back: one packed tile of bf16


def live_pages(lengths, page_indices, page_size, window=0):
    """The kernel's walk, from lengths [B] (the current token counted) and
    the page table [B, n_pages]: six int32 arrays, the first five of
    B * n_pages entries. With a window the table's width alone is read (how
    many pages a sequence may reach) and a page's place in the pool is its
    place in its sequence's ring. Entry t < count is the t-th page step:

    - ``slots[t]``, ``pages[t]``: page ``pages[t]`` of sequence ``slots[t]``'s
      table, sequences in order and each one's pages ascending over its
      ``_page_range``;
    - ``where[t]``: that page in the pool;
    - ``win_page[t]``, ``win_row[t]``: where the sequence's current token
      goes, as the pool page and the block of WINDOW_ROWS rows in it;
    - ``count`` [1]: the number of steps.

    Everything an index map needs is an entry here, so a grid step's address
    arithmetic is a handful of scalar loads (worth 5-14% of a call beside
    maps that derive it from lengths and table; PERF.md section 6, PR 33).
    Plain jnp, and the same for every layer of a decode step: a caller with
    several calls on the same lengths and table builds it once and hands it
    to each."""
    B, n_pages = page_indices.shape
    lengths, table = lengths.astype(jnp.int32), page_indices.astype(jnp.int32)
    first, last = _page_range(lengths, page_size, n_pages, window)
    j = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    if window:
        ring = ring_pages(window, page_size)
        table = jnp.arange(B, dtype=jnp.int32)[:, None] * ring + j % ring
    live = (first[:, None] <= j) & (j <= last[:, None])  # [B, n_pages]
    win_page = jnp.sum(jnp.where(j == last[:, None], table, 0), axis=1)  # table[b, last[b]]
    win_row = (lengths - 1) % page_size // min(page_size, WINDOW_ROWS)

    def of_its_sequence(x):  # [B] -> an entry a table entry
        return jnp.broadcast_to(x[:, None], table.shape).reshape(-1)

    # The table's live entries first, in the table's order (a stable sort on
    # one bit, the lists riding along: no gather, which costs a TPU program
    # megabytes of temporaries for arrays this small). Past count come the
    # dead entries, which nothing visits and which are valid all the same.
    _, entry, where, win_page, win_row = jax.lax.sort(
        (jnp.where(live, 0, 1).reshape(-1), jnp.arange(B * n_pages, dtype=jnp.int32),
         table.reshape(-1), of_its_sequence(win_page), of_its_sequence(win_row)),
        num_keys=1, is_stable=True,
    )
    count = jnp.sum(live, dtype=jnp.int32).reshape(1)
    return entry // n_pages, entry % n_pages, where, win_page, win_row, count


def _paged_kernel(lens_ref, layer_ref, slots_ref, pages_ref, where_ref, win_page_ref,
                  win_row_ref, q_ref, kn_ref, vn_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref,
                  m_scr, l_scr, acc_scr, *, scale, ps, n_pages, kv, window=0):
    """Grid (count,), the live pages of the batch (``live_pages``): step t is
    page ``pages_ref[t]`` of sequence ``slots_ref[t]``. ONE page DMA carries
    ALL kv heads (page ids are shared across heads in the pool layout), and
    the head loop unrolls statically inside the step — 4-8x fewer, larger
    DMAs than a per-head grid.

    ``ko_ref`` / ``vo_ref`` are a window of rows of the sequence's newest
    page in the pools the inputs alias: stored once a sequence, with the
    current token's row (``kn_ref`` / ``vn_ref``, f32) spliced in.
    ``layer_ref``, ``where_ref`` and the window's two lists are read by the
    index maps alone."""
    from jax.experimental import pallas as pl

    t = pl.program_id(0)
    b = slots_ref[t]
    j = pages_ref[t]
    length = lens_ref[b]
    start = j * ps
    _, last = _page_range(length, ps, n_pages)

    @pl.when((t == 0) | (slots_ref[jnp.maximum(t - 1, 0)] != b))
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j == last)
    def _write_the_token():
        # The token's row goes into its window of `win` rows twice: into the
        # page as it lies in VMEM, where the attention below reads it, and
        # into the output block, which is all that is stored back. Spliced in
        # f32 (bf16 -> f32 -> bf16 is exact): a 32-bit select needs no
        # packed-row mask, and the row comes as a plain f32 sublane.
        win = ko_ref.shape[2]
        row = (length - 1) % ps
        top = pl.multiple_of(row // win * win, win)
        here = jax.lax.broadcasted_iota(jnp.int32, ko_ref.shape[2:], 0) == row - top
        for page_ref, new_ref, out_ref in ((k_ref, kn_ref, ko_ref), (v_ref, vn_ref, vo_ref)):
            for h in range(kv):
                window = page_ref[h, 0, pl.ds(top, win), :].astype(jnp.float32)
                window = jnp.where(here, new_ref[0, pl.ds(h, 1), :], window).astype(out_ref.dtype)
                page_ref[h, 0, pl.ds(top, win), :] = window
                out_ref[h, 0] = window

    for h in range(kv):  # static unroll: kv is small (2-8)
        q = q_ref[0, h]  # [Gp, D]
        k = k_ref[h, 0]  # [ps, D]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [Gp, ps]
        cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = cols < length
        if window:  # the first live page's older columns, and a ring page's rows of an earlier turn
            seen = seen & (cols >= length - window)
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m_scr[h, :, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_scr[h, :, 0] * alpha + jnp.sum(p, axis=1)
        acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[h, 0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[h] = jnp.broadcast_to(m_cur[:, None], m_scr.shape[1:])
        l_scr[h] = jnp.broadcast_to(l_cur[:, None], l_scr.shape[1:])

    @pl.when(j == last)
    def _finalize():
        for h in range(kv):
            l = l_scr[h, :, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scr[h] / l_safe[:, None]).astype(o_ref.dtype)


def _paged_pallas(q, k_new, v_new, k_pages, v_pages, lengths, n_pages, layer, walk,
                  *, scale, interpret, window=0):
    """q: [B, KV, Gp, D] (Gp >= 8, sublane-padded); k_new/v_new: f32
    [B, KV, D]; k_pages/v_pages: [L, KV, P_total, ps, D]; n_pages: the
    table's width; layer: int32[1]; walk: ``live_pages`` of lengths and table
    -> (o [B, KV, Gp, D], k_pages, v_pages), the pools aliased to the inputs."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, Gp, D = q.shape
    ps = k_pages.shape[3]
    slots, pages, where, win_page, win_row, count = walk

    def whole(t, lens, layer, slots, pages, where, win_page, win_row):
        return (slots[t], 0, 0, 0)

    def token(t, lens, layer, slots, pages, where, win_page, win_row):
        return (slots[t], 0, 0)

    def page(t, lens, layer, slots, pages, where, win_page, win_row):
        return (layer[0], 0, where[t], 0, 0)

    def token_window(t, lens, layer, slots, pages, where, win_page, win_row):
        return (layer[0], 0, win_page[t], win_row[t], 0)

    # The stored window: one packed tile of rows (16 of bf16, and a multiple
    # of f32's 8), so a sequence's write-back is a sliver of its page.
    win = min(ps, WINDOW_ROWS)
    one_page = (None, KV, 1, ps, D)  # the layer axis squeezed
    one_window = (None, KV, 1, win, D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=7,
        grid=(count[0],),  # a runtime value: one compiled call serves every batch
        in_specs=[
            pl.BlockSpec((1, KV, Gp, D), whole),
            pl.BlockSpec((1, KV, D), token),
            pl.BlockSpec((1, KV, D), token),
            pl.BlockSpec(one_page, page),
            pl.BlockSpec(one_page, page),
        ],
        out_specs=[
            pl.BlockSpec((1, KV, Gp, D), whole),
            pl.BlockSpec(one_window, token_window),
            pl.BlockSpec(one_window, token_window),
        ],
        scratch_shapes=[
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, ps=ps, n_pages=n_pages, kv=KV, window=window
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Gp, D), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operands count the seven scalar-prefetch arrays: 10, 11 are the pools
        input_output_aliases={10: 1, 11: 2},
        compiler_params=pltpu.CompilerParams(
            # in order: a sequence's pages accumulate into one scratch
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        # the trace tells a window layer's call from a full one's by this name
        name="window_attn" if window else "paged_attn",
    )(lengths, layer, slots, pages, where, win_page, win_row, q, k_new, v_new, k_pages, v_pages)


def paged_attention(q, k_new, v_new, k_pages, v_pages, lengths, page_indices, layer,
                    scale=None, interpret=False, mesh=None, head_axis="tensor", walk=None, window=0):
    """Paged decode attention. q: [B, H, D] (one query token per sequence);
    k_new/v_new: [B, KV, D], that token's K and V; k_pages/v_pages:
    [L, KV, P_total, page_size, D], every layer's pool; lengths: [B] valid
    tokens per sequence including the current one (so >= 1); page_indices:
    [B, pages_per_seq] (entries past a sequence's length must still be valid
    page ids — use 0); layer: which of the L pools to attend (an int or a
    traced int32 scalar: the engine's layer loop passes its counter, so one
    compiled call serves every layer); walk: ``live_pages`` of these
    lengths and this table, for a caller that makes several calls on them (a
    decode step's layers) and builds it once; built here without it; window:
    the layer's attention window (0: none), whose pools hold rings
    ([L, KV, B * ring_pages(window, page_size), page_size, D]: the module's
    docstring) and of whose page_indices the width alone is read.

    Returns (o [B, H, D], k_pages, v_pages): the token's K/V lies at position
    lengths - 1 of each sequence's pages in the returned pools, which alias
    the arguments — in place wherever the caller donates the pools or carries
    them through a loop, and nothing but the written pages moves.

    This is the Pallas kernel: it runs on a TPU backend, or anywhere with
    interpret=True, and raises elsewhere — a caller that may land on another
    backend chooses ``paged_attention_reference`` from what it observes.

    mesh: tensor-parallel serving (llm/engine.py) — the head axes (H of q, KV
    of the token's rows and of the page pools) are sharded over
    ``mesh[head_axis]`` and the kernel is shard_map'd: each device attends its
    own head shard against its own KV pool shard (embarrassingly parallel —
    GQA groups never straddle shards because callers validate KV % degree ==
    0). Without the explicit map jax refuses to lower the call: GSPMD cannot
    partition a Mosaic kernel.
    """
    if walk is None:
        walk = live_pages(lengths, page_indices, k_pages.shape[3], window)
    if mesh is not None and mesh.shape.get(head_axis, 1) > 1:
        from jax.sharding import PartitionSpec as P

        if window:
            raise NotImplementedError("paged_attention: a window layer's rings are not sharded over a mesh")

        def inner(*args):  # every device walks the same pages, of its own heads
            return paged_attention(*args[:8], scale=scale, interpret=interpret, walk=args[8:])

        heads, pool = P(None, head_axis, None), P(None, head_axis, None, None, None)
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(heads, heads, heads, pool, pool, P(None), P(None, None), P(),
                      *(P(None),) * len(walk)),
            out_specs=(heads, pool, pool),
            check_vma=False,
        )(q, k_new, v_new, k_pages, v_pages, lengths, page_indices,
          jnp.asarray(layer, jnp.int32), *walk)
    B, H, D = q.shape
    KV = k_pages.shape[1]
    if H % KV:
        raise ValueError(f"n_heads {H} not divisible by kv_heads {KV}")
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"paged_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    # Sublane-pad the group axis up to a multiple of 8, the rows of the
    # kernel's f32 score and accumulator tiles (a group of 9 takes two). q
    # itself may be bf16 (tile 16 rows): Mosaic compiles the 8-row block as
    # is (chip_smoke.py checks it on the chip).
    Gp = -(-group // 8) * 8
    qg = q.reshape(B, KV, group, D)
    if Gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - group), (0, 0)))

    def as_rows(new, pages):  # rounded as the pool stores it, handed over in f32
        return new.astype(pages.dtype).astype(jnp.float32)

    o, k_pages, v_pages = _paged_pallas(
        qg, as_rows(k_new, k_pages), as_rows(v_new, v_pages), k_pages, v_pages,
        lengths.astype(jnp.int32), page_indices.shape[1],
        jnp.asarray(layer, jnp.int32).reshape(1), walk, scale=scale, interpret=interpret, window=window,
    )
    return o[:, :, :group].reshape(B, H, D), k_pages, v_pages
