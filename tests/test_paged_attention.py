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
    live_pages,
    paged_attention,
    paged_attention_reference,
)


L = 3  # layers in the pool, each with contents of its own
LAYERS = (0, L - 1)


def _make_case(B, H, KV, D, ps, ppseq, lengths, layer, seed=0, empty=()):
    """Random paged cache of L layers where sequence b owns pages
    [b*ppseq .. ) shuffled, plus a contiguous copy of `layer` for the
    oracle. The call under test gets the pools as they are BEFORE the current
    token (its row in `layer` NaN) with the row beside them, and must return
    the attention over, and the pools with, the row in place. A sequence in
    `empty` is what the engine makes of a free slot: length 1 and a table of
    zeros, so all of them write the same row of dead page 0."""
    rng = np.random.default_rng(seed)
    P_total = B * ppseq + 1  # page 0 reserved as the dead-entry target
    q = rng.normal(size=(B, H, D)).astype(np.float32)
    k_pages = rng.normal(size=(L, KV, P_total, ps, D)).astype(np.float32)
    v_pages = rng.normal(size=(L, KV, P_total, ps, D)).astype(np.float32)
    page_indices = np.zeros((B, ppseq), np.int32)
    assert all(lengths[b] == 1 for b in empty)
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


def _kernel_against_reference(case, layer):
    want = paged_attention_reference(*case["call"], layer)
    _assert_same(want[1:], case["pools"], 0)  # the reference wrote the rows, and only them
    got = paged_attention(*case["call"], layer, interpret=True)
    _assert_same(got[:1], want[:1], 2e-3)
    _assert_same(got[1:], case["pools"], 0)


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
    "one_live": dict(lengths=[1, 1, 37, 1], empty=(0, 1, 3)),
    "one_live_last": dict(lengths=[1, 1, 1, 64], empty=(0, 1, 2)),
    # exactly one full page, and a full table, beside slots of length 1
    "full_page_full_table": dict(lengths=[1, 16, 64, 1], empty=(0,)),
    # a length at a page's first row and at its last
    "page_edges": dict(lengths=[17, 32, 49, 48]),
    # the batch's live pages number B (one each) and B x n_pages (all of them)
    "one_page_each": dict(lengths=[1, 1, 1, 1], empty=(0, 1, 2, 3)),
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
    lengths = [1, ppseq * ps - 3, 1, ps + 1, 1, 1]
    _kernel_against_reference(_make_case(
        B=6, H=H, KV=KV, D=64, ps=ps, ppseq=ppseq, lengths=lengths, layer=layer, seed=7,
        empty=(0, 2, 4, 5),
    ), layer)


@pytest.mark.parametrize("ps,n_pages", [(16, 4), (128, 16), (128, 32)])
def test_page_steps_are_the_tokens_in_the_cache(ps, n_pages):
    """The walk the kernel's grid follows: ceil(length / page_size) steps a
    sequence, in sequence order and each one's pages ascending, one that ran
    past its table its whole table, none without a step (its row of the
    output would stay unwritten); with every step, where its page lies and
    where the sequence's token goes."""
    lengths = np.array([1, 2, ps - 1, ps, ps + 1, 2 * ps, 3 * ps - 1, n_pages * ps - 1,
                        n_pages * ps, n_pages * ps + 5, 0], np.int32)
    B = len(lengths)
    table = np.random.default_rng(0).permutation(B * n_pages).reshape(B, n_pages).astype(np.int32)
    walk = live_pages(jnp.asarray(lengths), jnp.asarray(table), ps)
    slots, pages, where, win_page, win_row, count = (np.asarray(x) for x in walk)
    steps = [min(max(math.ceil(n / ps), 1), n_pages) for n in lengths]
    inside = (lengths >= 1) & (lengths <= n_pages * ps)
    assert count.tolist() == [sum(steps)]
    assert sum(s for s, ok in zip(steps, inside) if ok) == sum(math.ceil(n / ps) for n in lengths[inside])
    want = [(b, j) for b in range(B) for j in range(steps[b])]
    n = count[0]
    assert list(zip(slots[:n], pages[:n])) == want
    assert where[:n].tolist() == [table[b, j] for b, j in want]
    assert win_page[:n].tolist() == [table[b, steps[b] - 1] for b, _ in want]
    assert win_row[:n].tolist() == [(lengths[b] - 1) % ps // min(ps, 16) for b, _ in want]
    # past the count nothing is visited, and every entry is still inside the table
    for x, hi in ((slots, B), (pages, n_pages), (where, B * n_pages), (win_page, B * n_pages)):
        assert x.shape == (B * n_pages,) and (0 <= x).all() and (x < hi).all()
