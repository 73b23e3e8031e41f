"""Paged decode attention: reference vs contiguous oracle, Pallas kernel
(interpret mode) vs reference — GQA, ragged lengths, partial pages, the
layer index into a pool that holds every layer, and the current token's K/V
written into that pool by the call."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.paged_attention import (
    group_pages,
    page_groups,
    paged_attention,
    paged_attention_reference,
    ring_pages,
    window_attention_reference,
)


L = 3  # layers in the pool, each with contents of its own
LAYERS = (0, L - 1)
GROUPS = (1, 2, 3, 5, 8)  # pages of one sequence a grid step of the kernel takes (5: a ring of a window of 512)


def _make_case(B, H, KV, D, ps, ppseq, lengths, layer, seed=0, empty=()):
    """Random paged cache of L layers where sequence b owns pages
    [b*ppseq .. ) shuffled, plus a contiguous copy of `layer` for the
    oracle. The call under test gets the pools as they are BEFORE the current
    token (its row in `layer` NaN) with the row beside them, and must return
    the attention over, and the pools with, the row in place. A sequence in
    `empty` is what the engine makes of a free slot: length 0 and a table of
    zeros; the call writes nothing for it and returns it zeros."""
    rng = np.random.default_rng(seed)
    P_total = B * ppseq + 1  # page 0 reserved as the dead-entry target
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    k_pages = rng.normal(size=(L, KV, P_total, ps, D)).astype(np.float32)
    v_pages = rng.normal(size=(L, KV, P_total, ps, D)).astype(np.float32)
    page_indices = np.zeros((B, ppseq), np.int32)
    assert all(lengths[b] == 0 for b in empty) and all(lengths[b] for b in set(range(B)) - set(empty))
    for b in range(B):
        n_used = 0 if b in empty else math.ceil(lengths[b] / ps)
        # its own pages: the call writes into a sequence's newest page
        perm = rng.permutation(np.arange(1 + b * ppseq, 1 + (b + 1) * ppseq))[:n_used]
        page_indices[b, :n_used] = perm
    # Contiguous K/V per sequence for the oracle.
    k_full = np.zeros((B, KV, ppseq * ps, D), np.float32)
    v_full = np.zeros((B, KV, ppseq * ps, D), np.float32)
    for b in range(B):
        for j in range(ppseq):
            pg = page_indices[b, j]
            k_full[b, :, j * ps:(j + 1) * ps] = k_pages[layer, :, pg]
            v_full[b, :, j * ps:(j + 1) * ps] = v_pages[layer, :, pg]
    k_before, v_before = k_pages.copy(), v_pages.copy()
    k_new = np.zeros((B, KV, D), np.float32)
    v_new = np.zeros((B, KV, D), np.float32)
    for b in range(B):
        if b in empty:  # a token nobody may write, into a page 0 that must come back as it went
            k_new[b] = v_new[b] = np.nan
            continue
        pg, off = page_indices[b, (lengths[b] - 1) // ps], (lengths[b] - 1) % ps
        k_new[b], v_new[b] = k_pages[layer, :, pg, off], v_pages[layer, :, pg, off]
        k_before[layer, :, pg, off] = v_before[layer, :, pg, off] = np.nan
    return dict(
        call=(jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new), jnp.asarray(k_before),
              jnp.asarray(v_before), jnp.asarray(np.asarray(lengths, np.int32)),
              jnp.asarray(page_indices)),
        pools=(k_pages, v_pages), oracle=(jnp.asarray(k_full), jnp.asarray(v_full)),
    )


def _assert_same(got, want, tol):
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=tol, atol=tol)


def _kernel_against_reference(case, layer, group=None):
    """group: the walk's page group; None: the one the call reads off its shapes."""
    want = paged_attention_reference(*case["call"], layer)
    _assert_same(want[1:], case["pools"], 0)  # the reference wrote the rows, and only them
    lengths, table = case["call"][5:]
    walk = None if group is None else page_groups(lengths, table, case["call"][3].shape[3], group=group)
    got = paged_attention(*case["call"], layer, interpret=True, walk=walk)
    _assert_same(got[:1], want[:1], 2e-3)
    _assert_same(got[1:], case["pools"], 0)
    for o in (got[0], want[0]):  # a row that holds no sequence comes back as zeros
        assert not np.asarray(o)[np.asarray(lengths) == 0].any()


def _oracle(q, k_full, v_full, lengths):
    B, H, D = q.shape
    KV = k_full.shape[1]
    group = H // KV
    S = k_full.shape[2]
    qg = q.reshape(B, KV, group, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k_full) / math.sqrt(D)
    valid = (jnp.arange(S)[None, :] < lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, -1e30)
    p = jax.nn.softmax(s.astype(jnp.float32), axis=-1).astype(q.dtype)
    return jnp.einsum("bkgs,bksd->bkgd", p, v_full).reshape(B, H, D)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("H,KV", [(8, 8), (8, 2), (16, 4)])
def test_reference_matches_oracle(H, KV, layer):
    lengths = [1, 17, 64, 33]
    case = _make_case(B=4, H=H, KV=KV, D=64, ps=16, ppseq=4, lengths=lengths, layer=layer)
    got, kp, vp = paged_attention_reference(*case["call"], layer)
    want = _oracle(case["call"][0], *case["oracle"], case["call"][5])
    _assert_same([got], [want], 2e-5)
    _assert_same((kp, vp), case["pools"], 0)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("H,KV", [(8, 8), (8, 2), (16, 4)])
def test_kernel_matches_reference(H, KV, layer):
    lengths = [5, 16, 61, 128]
    _kernel_against_reference(_make_case(
        B=4, H=H, KV=KV, D=64, ps=32, ppseq=4, lengths=lengths, layer=layer, seed=1
    ), layer)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("lengths,empty", [
    # ragged: 1, 1, 2, 10, 5, 8, 9, 3, 4 and 6 pages, so at every G a sequence
    # of one page, of exactly G, of G + 1 and of no multiple of G
    ([5, 16, 61, 300, 129, 256, 260, 96, 128, 190], ()),
    # no sequence in the first row, two middle ones and the last, nor in any but one, nor in any: a grid of no step
    ([0, 70, 0, 0, 33, 320, 1, 64, 65, 0], (0, 2, 3, 9)),
    ([0, 0, 0, 0, 0, 0, 161, 0, 0, 0], (0, 1, 2, 3, 4, 5, 7, 8, 9)),
    ([0] * 10, tuple(range(10))),
])
def test_kernel_matches_reference_at_every_page_group(lengths, empty, group):
    """G = 1 is the same kernel a page a step; a row of length 0 is returned
    as zeros and leaves both pools bit for bit, dead page 0 and NaN token and
    all."""
    _kernel_against_reference(_make_case(
        B=10, H=8, KV=2, D=64, ps=32, ppseq=10, lengths=lengths, layer=1, seed=8, empty=empty
    ), 1, group)


@pytest.mark.parametrize("layer", range(L))
@pytest.mark.parametrize("H,KV", [(4, 2), (16, 4), (12, 2), (18, 2)], ids=lambda x: str(x))
def test_query_heads_in_groups_of_2_4_6_and_9_at_every_layer(H, KV, layer):
    """The serve cells' GQA groups (9 takes two sublane tiles of query rows,
    padded to 16) over page groups of 3: sequences of 1, 3, 4 and 7 pages
    beside an empty row, in every layer of the pool."""
    _kernel_against_reference(_make_case(
        B=5, H=H, KV=KV, D=64, ps=16, ppseq=8, lengths=[9, 48, 0, 49, 100], layer=layer, seed=10, empty=(2,)
    ), layer, 3)


def test_the_kernel_under_shard_map_on_a_mesh_of_four():
    """Tensor-parallel serving's call: 8 KV heads over a `tensor` axis of
    four (2 a device, query heads in groups of 4), the walk's seven lists
    replicated operands of the map; every device walks the same page groups
    over its own heads, rows without a sequence among them."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:4]), ("tensor",))
    case = _make_case(B=6, H=32, KV=8, D=64, ps=16, ppseq=12, lengths=[0, 150, 16, 0, 33, 192], layer=1, seed=11,
                      empty=(0, 3))
    want = paged_attention_reference(*case["call"], 1)
    for walk in (None, page_groups(*case["call"][5:], 16, group=5)):
        got = jax.jit(lambda *a: paged_attention(*a, 1, interpret=True, mesh=mesh, walk=walk))(*case["call"])
        _assert_same(got[:1], want[:1], 2e-3)
        _assert_same(got[1:], case["pools"], 0)
        assert not np.asarray(got[0])[[0, 3]].any()


def _equations(jaxpr):
    """Equations of a jaxpr, those of its loops' and branches' bodies counted once each."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns"):
                    n += _equations(inner)
    return n


# The kernel's body from PR 33 to PR 43, one page a grid step and a Python
# loop over 8 KV heads, counted by `_equations` at commit 429bafd.
ONE_PAGE_BODY_AT_8_KV_HEADS = 601


@pytest.mark.parametrize("H,KV,n_pages,window", [
    (16, 8, 16, 0), (48, 8, 73, 0), (72, 8, 73, 512), (8, 2, 32, 0),
], ids=["internlm2-1.8b", "laguna_full_groups_of_6", "laguna_window_groups_of_9", "mistral-7b_a_chip_of_four"])
def test_the_kernels_body_does_not_grow_with_the_page_group_nor_the_kv_heads(H, KV, n_pages, window):
    """What every start of a replica pays, compile cache or not: the decode
    program traces and lowers the kernel's body once a call, so its size is
    `setup_warmup_s` (PR 42's body, a chain a power of two of pages times a
    loop over heads, cost 5 s a call a program there and was refused for it).
    The body at the serve cells' shapes, counted in equations: the same at
    every G and at 2 KV heads as at 8, but for the loop that fills up a chunk
    where a chunk is several pages (``_chunk_pages``), and never over the
    one-page body's (a body that unrolls G x KV counts thousands)."""
    B, D, ps = 4, 128, 128

    def body(G, kv=KV):
        pool = jnp.zeros((2, kv, 8, ps, D), jnp.bfloat16)
        rows = jnp.zeros((B, kv, D), jnp.bfloat16)
        args = (jnp.zeros((B, H // KV * kv, D), jnp.bfloat16), rows, rows, pool, pool, jnp.ones(B, jnp.int32),
                jnp.zeros((B, n_pages), jnp.int32), jnp.int32(0))
        jaxpr = jax.make_jaxpr(lambda *a: paged_attention(
            *a, interpret=True, window=window, walk=page_groups(a[5], a[6], ps, window, G)))(*args)
        calls = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1
        return _equations(calls[0].params["jaxpr"])

    sizes = [body(G, kv) for kv in (2, 8) for G in (1, 2, 4, 5, 8)]
    assert max(sizes) - min(sizes) <= 16  # the loop that fills up a chunk, where a chunk is several pages
    assert max(sizes) <= ONE_PAGE_BODY_AT_8_KV_HEADS


def test_a_place_the_group_lacks_holds_nothing_of_the_buffer():
    """The buffer keeps what an earlier step fetched: sequence 0's fourth page
    (all NaN here) lies where sequence 2's group of three pages has none.
    Nothing of it may reach sequence 2: the kernel attends a group's live
    pages and no place beyond them but zeroed ones (a zero weight times NaN
    is NaN), at 2 KV heads, where a chain step takes the four places at once,
    as at 8, where it takes one."""
    ps, layer = 32, 1
    for KV in (2, 8):
        case = _make_case(B=3, H=16, KV=KV, D=64, ps=ps, ppseq=4, lengths=[4 * ps, ps, 3 * ps], layer=layer, seed=9)
        q, k_new, v_new, kp, vp, lengths, table = case["call"]
        poisoned = int(table[0, 3])
        assert poisoned not in np.asarray(table[1:]).tolist()
        kp, vp = kp.at[layer, :, poisoned].set(jnp.nan), vp.at[layer, :, poisoned].set(jnp.nan)
        walk = page_groups(lengths, table, ps, group=4)
        assert np.asarray(walk[2])[:3].tolist() == [4, 1, 3] and np.asarray(walk[0])[:3].tolist() == [0, 1, 2]
        got = paged_attention(q, k_new, v_new, kp, vp, lengths, table, layer, interpret=True, walk=walk)[0]
        want = paged_attention_reference(q, k_new, v_new, kp, vp, lengths, table, layer)[0]
        assert np.isnan(np.asarray(got[0])).all()  # it attends its own NaN page
        _assert_same([got[1:]], [want[1:]], 2e-3)


def test_the_page_group_is_read_off_the_shapes():
    """A page's K and V on the device and what a sequence can reach, nothing
    else: the serve cells' pages of 524 KB (one chip) and 131 KB (a chip of
    four) take 8 a step, a ring of 5 pages is one group of 5, a table of
    three or of one page takes its width, pages of 1 MB take 4 (8 would not
    fit the buffers twice), and a page that fills the buffers alone one."""
    assert group_pages(8, 128, 128, 2, 16) == 8
    assert group_pages(2, 128, 128, 2, 32) == 8
    assert group_pages(8, 128, 128, 2, 73, window=512) == 5
    assert group_pages(8, 128, 128, 2, 3) == 3
    assert group_pages(2, 128, 128, 2, 1) == 1
    assert group_pages(8, 256, 128, 2, 16) == 4
    assert group_pages(8, 128, 64, 2, 16) == 8  # a head under a lane tile is attended padded to one
    assert group_pages(8, 512, 128, 4, 16) == 1


@pytest.mark.parametrize("layer", LAYERS)
def test_kernel_ragged_and_single_page(layer):
    # Lengths straddling page boundaries, incl. a 1-token sequence; large
    # group (no sublane padding) and page_size 128 lane-width case.
    _kernel_against_reference(_make_case(
        B=3, H=16, KV=2, D=128, ps=128, ppseq=2, lengths=[1, 129, 256], layer=layer, seed=2
    ), layer)


@pytest.mark.parametrize("layer", LAYERS)
def test_dead_table_entries_are_ignored(layer):
    """Entries past a sequence's length point at page 0 (shared, full of
    data) — they must not contribute."""
    _kernel_against_reference(_make_case(
        B=2, H=4, KV=4, D=64, ps=16, ppseq=8, lengths=[16, 40], layer=layer, seed=3
    ), layer)


def test_layer_index_reads_and_writes_that_layer_and_no_other():
    """A traced layer index, as the engine's layer loop passes it: the call
    attends that layer's pages against the contiguous oracle and writes the
    token's row there; every other layer's pool can hold anything (here:
    NaN) without showing, and comes back as it went in."""
    layer = 1
    case = _make_case(B=2, H=8, KV=2, D=64, ps=32, ppseq=2, lengths=[7, 50], layer=layer, seed=4)
    q, k_new, v_new, kp, vp, lens, pidx = case["call"]
    others = (jnp.arange(L) != layer)[:, None, None, None, None]
    kp, vp = jnp.where(others, jnp.nan, kp), jnp.where(others, jnp.nan, vp)
    want = _oracle(q, *case["oracle"], lens)
    kernel = jax.jit(lambda l: paged_attention(q, k_new, v_new, kp, vp, lens, pidx, l, interpret=True))
    reference = jax.jit(lambda l: paged_attention_reference(q, k_new, v_new, kp, vp, lens, pidx, l))
    for fn in (kernel, reference):
        o, kp_out, vp_out = fn(jnp.int32(layer))
        _assert_same([o], [want], 2e-3)
        for out, full in zip((kp_out, vp_out), case["pools"]):
            np.testing.assert_array_equal(np.asarray(out[layer]), full[layer])
            assert np.isnan(np.asarray(out)[np.arange(L) != layer]).all()
        assert np.isnan(np.asarray(fn(jnp.int32(0))[0])).any()


# The walk over live pages (PR 33): what a grid over the whole table could not
# get wrong, because it visited every entry of every row anyway.
B_WALK = 4
WALKS = {
    # every slot empty but one
    "one_live": dict(lengths=[0, 0, 37, 0], empty=(0, 1, 3)),
    "one_live_last": dict(lengths=[0, 0, 0, 64], empty=(0, 1, 2)),
    # exactly one full page, and a full table, beside an empty slot and one of length 1
    "full_page_full_table": dict(lengths=[0, 16, 64, 1], empty=(0,)),
    # a length at a page's first row and at its last
    "page_edges": dict(lengths=[17, 32, 49, 48]),
    # the batch's live pages number none (every slot empty), B (one each) and B x n_pages (all of them)
    "no_page": dict(lengths=[0, 0, 0, 0], empty=(0, 1, 2, 3)),
    "one_page_each": dict(lengths=[1, 1, 1, 1]),
    "whole_table": dict(lengths=[64, 64, 64, 64]),
}


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("walk", sorted(WALKS))
def test_walk_of_live_pages(walk, layer):
    _kernel_against_reference(_make_case(
        B=B_WALK, H=8, KV=2, D=64, ps=16, ppseq=4, layer=layer, seed=5, **WALKS[walk]
    ), layer)


@pytest.mark.parametrize("layer", LAYERS)
def test_a_batch_of_one_page(layer):
    _kernel_against_reference(_make_case(
        B=1, H=4, KV=2, D=64, ps=16, ppseq=4, lengths=[9], layer=layer, seed=6
    ), layer)


@pytest.mark.parametrize("layer", LAYERS)
@pytest.mark.parametrize("ppseq,H,KV", [(16, 16, 8), (32, 8, 2)])
def test_the_serve_cells_tables(ppseq, H, KV, layer):
    """Table widths and heads a chip of the two serve configurations: most
    slots empty, one near the end of its table, one on its second page."""
    ps = 8
    lengths = [0, ppseq * ps - 3, 0, ps + 1, 0, 0]
    _kernel_against_reference(_make_case(
        B=6, H=H, KV=KV, D=64, ps=ps, ppseq=ppseq, lengths=lengths, layer=layer, seed=7,
        empty=(0, 2, 4, 5),
    ), layer)


def _walk_in_plain_python(lengths, table, ps, window, group):
    """The walk as a loop: a sequence's pages first..last in groups of up to
    `group` from the first on, none for a length of 0."""
    B, n_pages = table.shape
    ring = ring_pages(window, ps) if window else 0
    steps = []
    for b, n in enumerate(int(x) for x in lengths):
        if not n:
            continue
        last = min((n - 1) // ps, n_pages - 1)
        first = min(max(n - window, 0) // ps, last) if window else 0
        where = (lambda j: b * ring + j % ring) if window else (lambda j: int(table[b, j]))
        for start in range(first, last + 1, group):
            live = min(group, last - start + 1)
            steps.append(dict(slot=b, first=start, live=live, where=[where(start + i) for i in range(live)],
                              win_page=where(last), win_row=(n - 1) % ps // min(ps, 16)))
    return steps


def _walk_lengths(ps, n_pages):
    """Ragged, a page's edges, a full table, a sequence run past its table,
    and no sequence in the first, a middle and the last row."""
    return np.array([0, 1, 2, ps - 1, ps, ps + 1, 2 * ps, 0, 3 * ps - 1, n_pages * ps - 1,
                     n_pages * ps, n_pages * ps + 5, 0], np.int32)


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("ps,n_pages,window", [(16, 4, 0), (128, 16, 0), (128, 32, 0), (16, 12, 40), (128, 73, 512)])
def test_page_steps_are_the_tokens_in_the_cache(ps, n_pages, window, group):
    """The walk the kernel's grid follows: a sequence's ceil(length / page_size)
    pages (the window's, with one: from the page of position length - window,
    which here lies mid-group of the table and mid-ring) in ceil(pages / group)
    steps, in sequence order and each one's groups ascending, one that ran
    past its table its whole table, and none for a length of 0; with every
    step, where its pages lie and where the sequence's token goes."""
    lengths = _walk_lengths(ps, n_pages)
    B = len(lengths)
    table = np.random.default_rng(0).permutation(B * n_pages).reshape(B, n_pages).astype(np.int32)
    walk = page_groups(jnp.asarray(lengths), jnp.asarray(table), ps, window, group)
    slots, first, live, where, win_page, win_row, count = (np.asarray(x) for x in walk)
    want = _walk_in_plain_python(lengths, table, ps, window, group)
    n = count[0]
    assert n == len(want) and n == sum(math.ceil(s / group) for s in np.bincount(
        [w["slot"] for w in want for _ in range(w["live"])], minlength=B))
    if not window:  # inside its table a sequence costs its tokens
        inside = (lengths >= 1) & (lengths <= n_pages * ps)
        assert sum(w["live"] for w in want if inside[w["slot"]]) == sum(math.ceil(x / ps) for x in lengths[inside])
    for key, got in (("slot", slots), ("first", first), ("live", live), ("win_page", win_page), ("win_row", win_row)):
        assert got[:n].tolist() == [w[key] for w in want], key
    where = where.reshape(-1, group)
    for t, w in enumerate(want):
        assert where[t, :w["live"]].tolist() == w["where"]
    # past the count nothing is visited, and every entry is still inside the pool
    pool = B * ring_pages(window, ps) if window else B * n_pages
    assert len(slots) == B * math.ceil((min(n_pages, ring_pages(window, ps)) if window else n_pages) / group)
    for x, hi in ((slots, B), (where, pool), (win_page, pool)):
        assert (0 <= x).all() and (x < hi).all()
    assert (live[n:] == 0).all()


@pytest.mark.parametrize("group", GROUPS)
def test_the_walk_of_a_batch_with_no_sequence_is_empty(group):
    walk = page_groups(jnp.zeros(5, jnp.int32), jnp.zeros((5, 16), jnp.int32), 128, 0, group)
    assert walk[-1].tolist() == [0] and not np.asarray(walk[2]).any()


@pytest.mark.parametrize("group", GROUPS)
@pytest.mark.parametrize("lengths", [(5, 40, 150, 0), (0, 33, 97, 64), (0, 0, 0, 0)])
def test_the_window_kernel_matches_its_reference_at_every_page_group(lengths, group):
    """Rings of 4 pages of 16 under a window of 40: a sequence inside its
    first page, one whose window starts mid-page and mid-group, one several
    turns round its ring, and rows with no sequence (zeros back, ring as it
    was)."""
    rng = np.random.default_rng(sum(lengths) + group)
    B, KV, H, D, ps, W = len(lengths), 2, 4, 64, 16, 40
    ring = ring_pages(W, ps)
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, B, KV, D)), jnp.float32)
    pools = jnp.asarray(rng.normal(size=(2, L, KV, B * ring, ps, D)), jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    table = jnp.zeros((B, 12), jnp.int32)  # its width alone is read
    want = window_attention_reference(q, *new, *pools, lens, 1, W)
    got = paged_attention(q, *new, *pools, lens, table, 1, window=W, interpret=True,
                          walk=page_groups(lens, table, ps, W, group))
    _assert_same(got[:1], want[:1], 2e-3)
    _assert_same(got[1:], want[1:], 0)
    for b, n in enumerate(lengths):
        if not n:
            assert not np.asarray(got[0][b]).any() and not np.asarray(want[0][b]).any()
            for pool_out, pool_in in zip(got[1:], pools):
                np.testing.assert_array_equal(np.asarray(pool_out[:, :, b * ring:(b + 1) * ring]),
                                              np.asarray(pool_in[:, :, b * ring:(b + 1) * ring]))


@pytest.mark.parametrize("window", [0, 40], ids=["every_page", "a_ring"])
def test_pools_whose_rows_are_wider_than_a_head_give_the_narrow_pools_answer(window):
    """``kv_row_width``: a head of 64 in rows of 128, zeros behind it, as a
    TPU's pools lie from allocation. The kernel and both references pad q and
    the token's rows themselves, keep the head's own scale (64^-1/2, not
    128^-1/2), write the padded row and return the head's 64 columns: the
    narrow pools' output, and the narrow pools' rows with zeros behind them."""
    rng = np.random.default_rng(7 + window)
    lengths = (5, 40, 150, 0)
    B, KV, H, D, ps, wide = len(lengths), 2, 4, 64, 16, 128
    n_pages = B * ring_pages(window, ps) if window else 1 + B * 10
    q = jnp.asarray(rng.normal(size=(B, H, D)), jnp.float32)
    new = jnp.asarray(rng.normal(size=(2, B, KV, D)), jnp.float32)
    pools = jnp.asarray(rng.normal(size=(2, L, KV, n_pages, ps, D)), jnp.float32)
    padded = jnp.pad(pools, ((0, 0),) * 5 + ((0, wide - D),))
    lens = jnp.asarray(lengths, jnp.int32)
    table = jnp.asarray([[0] * 10 if not n else list(range(1 + 10 * b, 11 + 10 * b)) for b, n in enumerate(lengths)],
                        jnp.int32)
    if window:
        want = window_attention_reference(q, *new, *pools, lens, 1, window)
        reference = window_attention_reference(q, *new, *padded, lens, 1, window)
    else:
        want = paged_attention_reference(q, *new, *pools, lens, table, 1)
        reference = paged_attention_reference(q, *new, *padded, lens, table, 1)
    kernel = paged_attention(q, *new, *padded, lens, table, 1, window=window, interpret=True)
    for got, tol in ((reference, 1e-6), (kernel, 2e-3)):
        assert got[0].shape == (B, H, D)
        _assert_same(got[:1], want[:1], tol)
        for pool_got, pool_want in zip(got[1:], want[1:]):
            np.testing.assert_array_equal(np.asarray(pool_got[..., :D]), np.asarray(pool_want))
            assert not np.asarray(pool_got[..., D:]).any()
