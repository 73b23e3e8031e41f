"""Paged decode attention for TPU: single-token GQA queries against a
block-paged KV cache, with the token's own K/V written into the cache by the
same call.

The serving engine's KV cache is a pool of fixed-size pages, every layer's
in one array ([L, KV, P_total, page_size, D]); each sequence owns a page list
(its page table row). Decode attention must therefore gather a sequence's
keys from non-contiguous pages. An XLA gather would materialize the whole
per-sequence KV every step (HBM copy of the entire working set per token);
the Pallas kernel instead walks the page table through scalar prefetch and
copies each page out of the pool itself, the next grid step's pages while
the current ones are attended, so pages stream through VMEM exactly once
with no materialized gather.

The pool never moves. A caller that slices its layer out first moves that
layer's whole pool every call (100 MB a layer in the serve cells), and one
that writes the token's K/V beside the kernel pays an operation a slot
(PERF.md section 6, PR 25). So the pools stay in HBM, the layer is an operand
that the kernel adds to each page's address, and the pools are aliased to the
call's outputs: the token's row is spliced into its page where the page lies
in VMEM for attention anyway, and the few rows around it are copied back.

Kernel shape: a grid of one axis whose steps are page groups, up to G
consecutive pages of one sequence (``group_pages``: G from a page's bytes and
a sequence's reach, 8 in the serve cells and a ring of 5 pages in one step),
sequence after sequence and each one's groups in ascending order
(``page_groups``: every step's sequence, first page, live pages and their
addresses, and the count, through scalar prefetch; the count is the grid's
length, a runtime value). A call costs what the tokens in the cache cost:
ceil(ceil(length / page_size) / G) steps a sequence, each as many page copies
as it has pages, and none for a row of length 0, which holds no sequence: no
step, no row written, zeros returned. Pages of one sequence are no neighbours
in the pool, so the kernel copies them itself (one copy a page carries every
KV head into one of two buffers of G pages) and starts the next step's copies
before it waits for its own. The step's body is ONE online-softmax chain
(scores, mask, max / exp / sum, the accumulator's rescale, p @ v) with the KV
heads as the batch dimension of its two ``dot_general``s, inside a
``lax.fori_loop`` over the group's live pages, a chunk of them a turn
(``_chunk_pages``: one page at 8 KV heads, four at the two a chip of four
holds). Its size in equations does not depend on G nor on the KV heads
(tests/test_paged_attention.py holds it under the one-page body's), because
every start of a replica traces and lowers it again, compile cache or not,
once a call a decode program: PR 42's body, a chain a power of two of pages
times a Python loop over heads, cost 5 s a call a program of
``setup_warmup_s`` (PERF.md section 6, PRs 42 and 43). The accumulator lives
in VMEM scratch across a sequence's groups.
Before PR 33 the grid was the whole table, (B, pages_per_seq) = 32 x 16 or
32 x 32 in the serve cells, and a step past a sequence's length still cost
0.4 us: 44-46% of the device's busy time in every serve cell. From PR 33 to
PR 43 a step was one page of one sequence through a BlockSpec, and a slot
without a request took one on dead page 0 so that its output row was written:
1.4-1.6 us a page step for 0.64 us of bytes, and in the open-loop cell 29 of a
call's 39 steps were empty slots'; the latent kernel kept that walk until
PR 47.

A layer with an attention window (``window`` > 0: position i sees j with
i - window < j <= i) keeps no page table. Its pool holds, a sequence, a ring
of ``ring`` pages (``ring_pages(window, page_size)``: ceil(window / page_size)
+ 1, the window's pages and the one being written), sequence b's at pool pages
b * ring .. b * ring + ring - 1, and page j of a sequence lies at ring page
j % ring: the token written at position p replaces the one at p - ring *
page_size, which no later query sees. The walk (``page_groups``) starts at the
page that holds position length - window, so a call costs the window's pages
whatever the context (a ring of 5 pages is one group), and the kernel masks
that first page's older columns.
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

def _write_rows(pages, new, layer, page, row, lengths):
    """new[b] ([KV, D]) written at (layer, :, page[b], row[b]) of `pages`, a
    sequence after the other, in place in a donated or loop-carried pool; a
    row of length 0 holds no sequence and writes nothing."""
    for b in range(new.shape[0]):
        pages = jax.lax.cond(
            lengths[b] > 0,
            lambda pages, b=b: jax.lax.dynamic_update_slice(
                pages, new[b].astype(pages.dtype)[None, :, None, None, :], (layer, 0, page[b], row[b], 0)),
            lambda pages: pages, pages)
    return pages


def paged_attention_reference(q, k_new, v_new, k_pages, v_pages, lengths, page_indices,
                              layer, scale=None):
    """q: [B, H, D]; k_new/v_new: [B, KV, D], the current token's;
    k_pages/v_pages: [L, KV, P_total, ps, D]; lengths: [B] (valid token count
    per sequence, INCLUDING the current position; 0: the row holds no
    sequence); page_indices: [B, pages_per_seq]; layer: scalar index into L
    -> (o [B, H, D], k_pages, v_pages) with the token written at position
    lengths - 1 of each sequence, and for a row of length 0 nothing written
    and zeros returned. A pool's rows may be wider than a head
    (``kv_row_width``)."""
    Hd = q.shape[-1]
    q, k_new, v_new, scale = _to_pool_rows(q, k_new, v_new, k_pages, scale)
    B, H, D = q.shape
    _, KV, _, ps, _ = k_pages.shape
    group = H // KV
    ppseq = page_indices.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = jnp.maximum(lengths - 1, 0)
    page = page_indices[jnp.arange(B), jnp.minimum(pos // ps, ppseq - 1)]

    k_pages = _write_rows(k_pages, k_new, layer, page, pos % ps, lengths)
    v_pages = _write_rows(v_pages, v_new, layer, page, pos % ps, lengths)
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
    o = _head_columns(jnp.einsum("bkgs,bksd->bkgd", p, v).reshape(B, H, D), Hd)
    return jnp.where((lengths > 0)[:, None, None], o, 0), k_pages, v_pages


def _page_range(length, ps, n_pages, window=0):
    """(first, last): the pages of its table a sequence of `length` tokens
    (>= 1) attends, both ends included: last - first + 1 pages, which is
    ceil(length / ps) while the sequence is inside its table. ``last`` holds
    the current position, the last of `length` (a sequence run past its table
    stays inside its last page); ``first`` is 0, or with an attention window
    the page of position length - window, the oldest the current one sees
    (the kernel masks that page's older columns). Never empty, whatever
    `length`: ``page_groups`` gives a row of length 0 no step and does not
    ask."""
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
    Hd = q.shape[-1]
    q, k_new, v_new, scale = _to_pool_rows(q, k_new, v_new, k_pages, scale)
    B, H, D = q.shape
    _, KV, n_ring, ps, _ = k_pages.shape
    rows = n_ring // B * ps  # a sequence's ring, in rows
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    pos = jnp.maximum(lengths - 1, 0)

    page = jnp.arange(B) * (rows // ps) + pos % rows // ps
    k_pages = _write_rows(k_pages, k_new, layer, page, pos % ps, lengths)
    v_pages = _write_rows(v_pages, v_new, layer, page, pos % ps, lengths)
    k = jax.lax.dynamic_index_in_dim(k_pages, layer, 0, keepdims=False).reshape(KV, B, rows, D)
    v = jax.lax.dynamic_index_in_dim(v_pages, layer, 0, keepdims=False).reshape(KV, B, rows, D)
    r = jnp.arange(rows)[None, :]
    held = pos[:, None] - (pos[:, None] - r) % rows  # [B, rows]: the position a ring row holds
    valid = (held >= 0) & (held > pos[:, None] - window)
    s = jnp.einsum("bkgd,kbsd->bkgs", q.reshape(B, KV, group, D), k).astype(jnp.float32) * scale
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = _head_columns(jnp.einsum("bkgs,kbsd->bkgd", p, v).reshape(B, H, D), Hd)
    return jnp.where((lengths > 0)[:, None, None], o, 0), k_pages, v_pages


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

WINDOW_ROWS = 16  # of the token's page, stored back: one packed tile of bf16


def reach_pages(n_pages: int, page_size: int, window: int = 0) -> int:
    """The pages one sequence's walk can hold: its table's width, or with a
    window its ring's length if that is less."""
    return min(n_pages, ring_pages(window, page_size)) if window else n_pages


GROUP_VMEM_BYTES = 8 << 20  # the kernel's two buffers of K and V pages, together
GROUP_MOST = 8  # pages a grid step


def group_pages(kv: int, page_size: int, head_dim: int, itemsize: int, n_pages: int, window: int = 0) -> int:
    """G, the pages of one sequence a grid step takes (a page group), from
    what a call can see: a page's K and V as one device holds them (kv x
    page_size x head_dim, a lane multiple, x itemsize x 2: 524 KB in the
    one-chip serve cells, 131 KB on a chip of four), and the pages a sequence
    can hold (``reach_pages``: its table's width, or its ring's length). The
    reach, as far as its pages fit the kernel's buffers twice over (the group
    being attended and the one being fetched) and no more than GROUP_MOST: 8
    in every serve cell's full layers, a ring of 5 pages in one group (the
    kernel alone on the chip, PERF.md section 6, PR 42: 8 beat 4 at every
    shape, by 3% at 524 KB and 3.8 pages a sequence and by 39% on a ring of
    5; 16 lost to 8 at 131 KB). A page of 1 MB would take 4. The one place G
    is decided: no option, no model's name; the kernel's body is the same
    size at every G."""
    page_bytes = 2 * kv * page_size * (head_dim + -head_dim % 128) * itemsize
    return max(1, min(GROUP_MOST, reach_pages(n_pages, page_size, window), GROUP_VMEM_BYTES // (2 * page_bytes)))


def kv_row_width(head_dim: int) -> int:
    """The columns a caller gives a head's K or V row in the pools: whole lane
    tiles of 128 on a TPU backend, where the kernel copies whole pages out of
    HBM and a row there is a whole number of lane tiles; the head's own
    elsewhere, where the references take any width. A head of 64 then lies in
    rows of 128, at twice the bytes, and the kernel takes the pool as it lies
    (padding a narrow pool at the call moved both pools on every call). The
    padding columns hold zeros: the three calls of this module pad q and the
    token's rows to the pool's rows with zeros, so no score moves, and cut the
    output back to the head."""
    return head_dim + -head_dim % 128 if jax.default_backend() == "tpu" else head_dim


def _to_pool_rows(q, k_new, v_new, k_pages, scale):
    """(q, k_new, v_new, scale) for pools whose rows are wider than a head
    (``kv_row_width``): the three zero-padded to the pool's rows, and the
    head's own scale told; as they came where the widths agree."""
    D, width = q.shape[-1], k_pages.shape[-1]
    if width == D:
        return q, k_new, v_new, scale

    def wide(a):
        return jnp.pad(a, ((0, 0),) * (a.ndim - 1) + ((0, width - D),))

    return wide(q), wide(k_new), wide(v_new), scale if scale is not None else 1.0 / math.sqrt(D)


def _head_columns(o, head_dim: int):
    """o cut back from the pool's rows to the head's columns."""
    return o if o.shape[-1] == head_dim else o[..., :head_dim]


def _chunk_pages(kv: int, group: int) -> int:
    """Pages of a group the kernel's softmax chain takes at once: as many as
    make 8 matrix products with the KV heads a device holds, for the chain's
    steps wait on each other and only its heads and pages run side by side
    (one page at 8 heads; 4 at the 2 a chip of four holds, where a chain a
    page took 0.52 us a page of 64 KB for 0.16 us of bytes: PERF.md section 6,
    PR 43), and a divisor of the group, whose buffer the last chunk must not
    pass."""
    c = max(1, min(8 // kv, group))
    while group % c:
        c -= 1
    return c


def page_groups(lengths, page_indices, page_size, window=0, group=1):
    """The paged kernel's walk, from lengths [B] (the current token counted;
    0: no sequence in the row) and the page table [B, n_pages]: seven int32
    arrays. With a window the table's width alone is read (how many pages a
    sequence may reach) and a page's place in the pool is its place in its
    sequence's ring. An entry is a page group: up to ``group`` consecutive
    pages of one sequence's ``_page_range``, cut from the range's first page
    on. Entry t < count is the t-th grid step:

    - ``seqs[t]``, ``first[t]``, ``live[t]``: pages ``first[t]`` ..
      ``first[t] + live[t] - 1`` of sequence ``seqs[t]``'s table, sequences
      in order and each one's groups ascending; every group of a sequence is
      full (``live`` = ``group``) but its last;
    - ``where[t * group + i]``: the group's i-th page in the pool;
    - ``win_page[t]``, ``win_row[t]``: where the sequence's current token
      goes, as the pool page and the block of WINDOW_ROWS rows in it;
    - ``count`` [1]: the number of steps. A row of length 0 has none.

    Everything a grid step needs is an entry here, so its address arithmetic
    is a handful of scalar loads (worth 5-14% of a call beside maps that
    derive it from lengths and table; PERF.md section 6, PR 33). Plain jnp,
    and the same for every layer of a decode step: a caller with several
    calls on the same lengths and table builds it once and hands it to each.
    The latent kernel's walk too (ops/latent_attention.py)."""
    B, n_pages = page_indices.shape
    n = group
    lengths, table = lengths.astype(jnp.int32), page_indices.astype(jnp.int32)
    first, last = _page_range(lengths, page_size, n_pages, window)
    n_groups = -(-reach_pages(n_pages, page_size, window) // n)  # the most a sequence can have
    start = first[:, None] + n * jnp.arange(n_groups, dtype=jnp.int32)[None, :]  # [B, n_groups]
    # pages of the group inside the range, which is empty for a row of length 0
    live = jnp.where(lengths[:, None] > 0, jnp.clip(last[:, None] - start + 1, 0, n), 0)
    j = jnp.arange(n_pages, dtype=jnp.int32)[None, :]
    if window:
        ring = ring_pages(window, page_size)
        own = jnp.arange(B, dtype=jnp.int32)[:, None] * ring
        table = own + j % ring
        where = [own + (start + i) % ring for i in range(n)]
    else:  # first is 0: a group's pages are a slice of the table's row
        padded = jnp.pad(table, ((0, 0), (0, n_groups * n - n_pages))).reshape(B, n_groups, n)
        where = [padded[:, :, i] for i in range(n)]
    win_page = jnp.sum(jnp.where(j == last[:, None], table, 0), axis=1)  # table[b, last[b]]
    win_row = jnp.maximum(lengths - 1, 0) % page_size // min(page_size, WINDOW_ROWS)

    def of_its_sequence(x):  # [B] -> an entry a group
        return jnp.broadcast_to(x[:, None], start.shape).reshape(-1)

    # The live groups first, in the table's order (a stable sort on one bit,
    # the lists riding along: no gather, which costs a TPU program megabytes
    # of temporaries for arrays this small). Past count come the dead
    # entries, which nothing visits and which are valid all the same.
    _, entry, start, live, win_page, win_row, *where = jax.lax.sort(
        (jnp.where(live > 0, 0, 1).reshape(-1), jnp.arange(B * n_groups, dtype=jnp.int32),
         start.reshape(-1), live.reshape(-1), of_its_sequence(win_page), of_its_sequence(win_row),
         *(w.reshape(-1) for w in where)),
        num_keys=1, is_stable=True,
    )
    count = jnp.sum(live > 0, dtype=jnp.int32).reshape(1)
    return entry // n_groups, start, live, jnp.stack(where, axis=1).reshape(-1), win_page, win_row, count


def _paged_kernel(lens_ref, layer_ref, seqs_ref, first_ref, live_ref, where_ref, win_page_ref,
                  win_row_ref, q_ref, kn_ref, vn_ref, _k_in, _v_in, o_ref, k_hbm, v_hbm,
                  k_buf, v_buf, sems, win_sems, m_scr, l_scr, acc_scr,
                  *, scale, ps, n, n_pages, window=0):
    """Grid (count,), the page groups of the batch (``page_groups``): step t
    is pages ``first_ref[t]`` .. of sequence ``seqs_ref[t]``, ``live_ref[t]``
    of them. The pools stay in HBM (``k_hbm`` / ``v_hbm``: the outputs, which
    the inputs alias) and the kernel copies a group's pages itself, since
    pages of one sequence are no neighbours in the pool: ONE copy a page
    carries ALL kv heads (page ids are shared across heads in the pool
    layout) into rows i * ps .. of buffer t % 2 of ``k_buf`` / ``v_buf``
    ([2, kv, n * ps, D]: a head's group is one block of rows), and step t
    starts step t + 1's copies before it waits for its own.

    Nothing here is unrolled by n or by the KV heads: the copies start in a
    loop over the group's live pages, and a second loop takes the group a
    chunk at a time (``_chunk_pages``: one page at 8 KV heads), waits for the
    chunk's pages and folds them into the sequence's online softmax, every
    head at once (the heads are the batch dimension of the two matrix
    products). A page the group lacks costs no copy, and arithmetic only
    where it fills up the group's last chunk. The loop carries the chain
    (m, l, acc) and the scratch keeps it between a sequence's groups.

    The sequence's last page has the current token's row (``kn_ref`` /
    ``vn_ref``, f32) spliced in where it lies in the buffer, before it is
    attended, and the WINDOW_ROWS rows around it copied back into the pool."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t = pl.program_id(0)
    b = seqs_ref[t]
    j0 = first_ref[t]
    live = live_ref[t]
    length = lens_ref[b]
    layer = layer_ref[0]
    buf = t % 2
    kv = k_buf.shape[1]
    c = _chunk_pages(kv, n)
    chunks = (live + c - 1) // c
    _, last = _page_range(length, ps, n_pages)
    newest = j0 + live - 1 == last  # the sequence's last group: the token's
    pools = ((k_hbm, k_buf, kn_ref), (v_hbm, v_buf, vn_ref))

    def rows(i, size=ps):  # place i of a buffer
        return pl.ds(pl.multiple_of(i * ps, ps), size)

    def copies(step, i):  # page i of step's group, K and V, into that step's buffer
        at = step % 2
        return [pltpu.make_async_copy(hbm.at[layer, :, where_ref[step * n + i]], vmem.at[at, :, rows(i)],
                                      sems.at[which, at, i])
                for which, (hbm, vmem, _) in enumerate(pools)]

    def each(lo, hi, do):  # do(i) for i in lo .. hi - 1, both runtime values: one body, whatever the count
        def body(i, carry):
            do(i)
            return carry
        jax.lax.fori_loop(lo, hi, body, 0)

    def fetch(step):
        def start(i):
            for copy in copies(step, i):
                copy.start()
        each(0, live_ref[step], start)

    def landed(i):  # page i of this step's group
        for copy in copies(t, i):
            copy.wait()

    win = min(ps, WINDOW_ROWS)
    row = (length - 1) % ps
    top = pl.multiple_of((live - 1) * ps + row // win * win, win)  # the token's rows in the buffer, if newest

    def window_copies():  # the token's rows, buffer -> pool
        return [pltpu.make_async_copy(vmem.at[buf, :, pl.ds(top, win)],
                                      hbm.at[layer, :, win_page_ref[t], pl.ds(win_row_ref[t] * win, win)],
                                      win_sems.at[which])
                for which, (hbm, vmem, _) in enumerate(pools)]

    @pl.when(t == 0)
    def _first_group():
        fetch(t)

    @pl.when(t + 1 < pl.num_programs(0))
    def _next_group():
        fetch(t + 1)

    if c > 1:
        # The places that fill up the group's last chunk were not fetched:
        # their columns are masked below, and their V rows, which p's zeros
        # would multiply, are zeroed first (a buffer may hold anything).
        def no_page(i):
            v_buf[buf, :, rows(i), :] = jnp.zeros((kv, ps, v_buf.shape[-1]), v_buf.dtype)
        each(live, chunks * c, no_page)

    q = q_ref[0]  # [kv, Gp, D]
    ends = jnp.minimum(length, (j0 + live) * ps)  # columns past it: another token's, or a page not fetched
    # What the sequence's earlier groups left, or nothing yet: the scratch is
    # read under a select and never initialised.
    opens = (t == 0) | (seqs_ref[jnp.maximum(t - 1, 0)] != b)  # the sequence's first group
    chain = (jnp.where(opens, NEG_INF, m_scr[:, :, :1]), jnp.where(opens, 0.0, l_scr[:, :, :1]),
             jnp.where(opens, 0.0, acc_scr[...]))

    def chunk(i, chain):
        each(i * c, jnp.minimum(i * c + c, live), landed)

        @pl.when(newest & (i == chunks - 1))
        def _write_the_token():
            # Spliced in f32 (bf16 -> f32 -> bf16 is exact): a 32-bit select
            # needs no packed-row mask, and the row comes as a plain f32
            # sublane a head.
            for _, vmem, new_ref in pools:
                old = vmem[buf, :, pl.ds(top, win), :].astype(jnp.float32)  # [kv, win, D]
                here = jax.lax.broadcasted_iota(jnp.int32, old.shape, 1) == row % win
                vmem[buf, :, pl.ds(top, win), :] = jnp.where(here, new_ref[0], old).astype(vmem.dtype)
            for copy in window_copies():
                copy.start()

        m_prev, l_prev, acc_prev = chain
        s = jax.lax.dot_general(
            q, k_buf[buf, :, rows(i * c, c * ps), :], (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        ) * scale  # [kv, Gp, c * ps]
        cols = (j0 + i * c) * ps + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        seen = cols < ends
        if window:  # the first live page's older columns, and a ring page's rows of an earlier turn
            seen = seen & (cols >= length - window)
        s = jnp.where(seen, s, NEG_INF)
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=2, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur)
        l_cur = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc = acc_prev * alpha + jax.lax.dot_general(
            p.astype(v_buf.dtype), v_buf[buf, :, rows(i * c, c * ps), :], (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32,
        )  # [kv, Gp, D]
        return m_cur, l_cur, acc

    m, l, acc = jax.lax.fori_loop(0, chunks, chunk, chain)
    m_scr[...] = jnp.broadcast_to(m, m_scr.shape)
    l_scr[...] = jnp.broadcast_to(l, l_scr.shape)
    acc_scr[...] = acc
    # Every group leaves the sequence's output as far as it got: the last
    # one's stands (the block stays in VMEM while the sequence does; l >= 1:
    # the row's largest score counts exp(0)).
    o_ref[0] = (acc / l).astype(o_ref.dtype)

    @pl.when(newest)
    def _token_stored():  # before the step after next fetches into this buffer
        for copy in window_copies():
            copy.wait()


# one trace a shape signature: ops/__init__.py
@functools.partial(jax.jit, inline=True, static_argnames=("n_pages", "scale", "interpret", "window"))
def _paged_pallas(q, k_new, v_new, k_pages, v_pages, lengths, n_pages, layer, walk,
                  *, scale, interpret, window=0):
    """q: [B, KV, Gp, D] (Gp >= 8, sublane-padded); k_new/v_new: f32
    [B, KV, 1, D]; k_pages/v_pages: [L, KV, P_total, ps, D]; n_pages: the
    table's width; layer: int32[1]; walk: ``page_groups`` of lengths and
    table -> (o [B, KV, Gp, D], k_pages, v_pages), the pools aliased to the
    inputs. A row of `o` whose sequence has no group is not written."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, Gp, D = q.shape
    ps = k_pages.shape[3]
    seqs, first, live, where, win_page, win_row, count = walk
    n = where.shape[0] // seqs.shape[0]  # the walk's page group

    def whole(t, lens, layer, seqs, *_):
        return (seqs[t], 0, 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(count[0],),  # a runtime value: one compiled call serves every batch
        in_specs=[
            pl.BlockSpec((1, KV, Gp, D), whole),
            pl.BlockSpec((1, KV, 1, D), whole),
            pl.BlockSpec((1, KV, 1, D), whole),
            in_hbm,
            in_hbm,
        ],
        out_specs=[pl.BlockSpec((1, KV, Gp, D), whole), in_hbm, in_hbm],
        scratch_shapes=[
            pltpu.VMEM((2, KV, n * ps, D), k_pages.dtype),
            pltpu.VMEM((2, KV, n * ps, D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2, n)),  # K or V, the buffer, the page
            pltpu.SemaphoreType.DMA((2,)),  # the token's rows, K and V
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(_paged_kernel, scale=scale, ps=ps, n=n, n_pages=n_pages, window=window)
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, Gp, D), q.dtype),
            jax.ShapeDtypeStruct(k_pages.shape, k_pages.dtype),
            jax.ShapeDtypeStruct(v_pages.shape, v_pages.dtype),
        ],
        # operands count the eight scalar-prefetch arrays: 11, 12 are the pools
        input_output_aliases={11: 1, 12: 2},
        compiler_params=pltpu.CompilerParams(
            # in order: a sequence's groups accumulate into one scratch, and
            # a step fetches the next one's pages
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        # the trace tells a window layer's call from a full one's by this name
        name="window_attn" if window else "paged_attn",
    )(lengths, layer, seqs, first, live, where, win_page, win_row, q, k_new, v_new, k_pages, v_pages)


def paged_attention(q, k_new, v_new, k_pages, v_pages, lengths, page_indices, layer,
                    scale=None, interpret=False, mesh=None, head_axis="tensor", walk=None, window=0):
    """Paged decode attention. q: [B, H, D] (one query token per sequence);
    k_new/v_new: [B, KV, D], that token's K and V; k_pages/v_pages:
    [L, KV, P_total, page_size, D], every layer's pool; lengths: [B] valid
    tokens per sequence including the current one, or 0 for a row that holds
    no sequence (nothing is walked or written for it, and its row of o is
    zeros); page_indices:
    [B, pages_per_seq] (entries past a sequence's length must still be valid
    page ids — use 0); layer: which of the L pools to attend (an int or a
    traced int32 scalar: the engine's layer loop passes its counter, so one
    compiled call serves every layer); walk: ``page_groups`` of these
    lengths and this table, for a caller that makes several calls on them (a
    decode step's layers) and builds it once, with the page group it chose
    (``group_pages``); built here without it; window:
    the layer's attention window (0: none), whose pools hold rings
    ([L, KV, B * ring_pages(window, page_size), page_size, D]: the module's
    docstring) and of whose page_indices the width alone is read. The pools'
    rows are ``kv_row_width`` of q's D columns wide (a narrower pool is
    refused: padding it here moved both pools on every call).

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
    Hd = q.shape[-1]
    q, k_new, v_new, scale = _to_pool_rows(q, k_new, v_new, k_pages, scale)
    shards = mesh.shape.get(head_axis, 1) if mesh is not None else 1
    if walk is None:
        _, kv, _, ps, d = k_pages.shape
        walk = page_groups(lengths, page_indices, ps, window, group_pages(
            kv // shards, ps, d, k_pages.dtype.itemsize, page_indices.shape[1], window))
    if shards > 1:
        from jax.sharding import PartitionSpec as P

        if window:
            raise NotImplementedError("paged_attention: a window layer's rings are not sharded over a mesh")

        def inner(*args):  # every device walks the same pages, of its own heads
            return paged_attention(*args[:8], scale=scale, interpret=interpret, walk=args[8:])

        heads, pool = P(None, head_axis, None), P(None, head_axis, None, None, None)
        o, k_pages, v_pages = jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(heads, heads, heads, pool, pool, P(None), P(None, None), P(),
                      *(P(None),) * len(walk)),
            out_specs=(heads, pool, pool),
            check_vma=False,
        )(q, k_new, v_new, k_pages, v_pages, lengths, page_indices,
          jnp.asarray(layer, jnp.int32), *walk)
        return _head_columns(o, Hd), k_pages, v_pages
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
    if D % 128 and not interpret:
        raise ValueError(
            f"paged_attention: the pools' rows are {D} columns, not whole lane tiles of 128, which the kernel "
            f"copies out of HBM: allocate them kv_row_width(head_dim) wide")
    # Sublane-pad the group axis up to a multiple of 8, the rows of the
    # kernel's f32 score and accumulator tiles (a group of 9 takes two). q
    # itself may be bf16 (tile 16 rows): Mosaic compiles the 8-row block as
    # is (chip_smoke.py checks it on the chip).
    Gp = -(-group // 8) * 8
    qg = q.reshape(B, KV, group, D)
    if Gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - group), (0, 0)))

    def as_rows(new, pages):  # rounded as the pool stores it, handed over in f32, a sublane a head
        return new.astype(pages.dtype).astype(jnp.float32)[:, :, None, :]

    o, k_pages, v_pages = _paged_pallas(
        qg, as_rows(k_new, k_pages), as_rows(v_new, v_pages), k_pages, v_pages,
        lengths.astype(jnp.int32), page_indices.shape[1],
        jnp.asarray(layer, jnp.int32).reshape(1), walk, scale=scale, interpret=interpret, window=window,
    )
    # a row without a sequence had no step and was never written
    o = jnp.where((lengths > 0)[:, None, None], o[:, :, :group].reshape(B, H, D), 0)
    return _head_columns(o, Hd), k_pages, v_pages
