"""Flash-attention kernel exactness vs the jnp oracle, run in Pallas
interpret mode on CPU (the kernels themselves, not the fallback; real-TPU
execution is covered by chip_smoke.py and the benchmark's train cell). Covers MHA, native GQA (grouped KV heads,
no repeat), segment masking (packed sequences), backward gradients, and the walk of a block's live sub-tiles
(PR 53): what it skips, what it counts and how large its three bodies are."""
import json
import math
import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import attention
from ray_tpu.ops.attention import flash_attention, live_tiles, mha_reference

B, S, D = 2, 256, 64


def _qkv(key, H, KV):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), jnp.float32)
    k = jax.random.normal(kk, (B, S, KV, D), jnp.float32)
    v = jax.random.normal(kv, (B, S, KV, D), jnp.float32)
    return q, k, v


def _segs():
    # Two segments per row, boundary at different positions per batch row.
    bounds = jnp.array([100, 160])
    pos = jnp.arange(S)[None, :]
    return (pos >= bounds[:, None]).astype(jnp.int32)


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2), (8, 2)])
def test_flash_forward_matches_reference(H, KV):
    q, k, v = _qkv(jax.random.PRNGKey(0), H, KV)
    ref = mha_reference(q, k, v, causal=True)
    out = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_flash_segment_mask_matches_reference():
    q, k, v = _qkv(jax.random.PRNGKey(1), 4, 2)
    segs = _segs()
    ref = mha_reference(q, k, v, causal=True, segment_ids=segs)
    out = flash_attention(
        q, k, v, causal=True, segment_ids=segs, block_q=128, block_k=128, interpret=True
    )
    np.testing.assert_allclose(np.asarray(ref), np.asarray(out), atol=2e-5, rtol=2e-5)


def test_flash_segment_isolation():
    """Tokens after a segment boundary must be unaffected by tokens before it."""
    q, k, v = _qkv(jax.random.PRNGKey(2), 4, 4)
    segs = _segs()
    out1 = flash_attention(q, k, v, segment_ids=segs, block_q=128, block_k=128, interpret=True)
    # Perturb segment-0 keys/values of row 0; segment-1 outputs must not move.
    k2 = k.at[0, :100].add(1.0)
    v2 = v.at[0, :100].add(1.0)
    out2 = flash_attention(q, k2, v2, segment_ids=segs, block_q=128, block_k=128, interpret=True)
    np.testing.assert_allclose(
        np.asarray(out1[0, 100:]), np.asarray(out2[0, 100:]), atol=1e-6
    )
    assert not np.allclose(np.asarray(out1[0, :100]), np.asarray(out2[0, :100]))


@pytest.mark.parametrize("H,KV", [(4, 4), (4, 2)])
def test_flash_backward_matches_reference(H, KV):
    q, k, v = _qkv(jax.random.PRNGKey(3), H, KV)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, causal=True, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
        )


def test_flash_backward_with_segments():
    q, k, v = _qkv(jax.random.PRNGKey(4), 4, 2)
    segs = _segs()

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, segment_ids=segs, block_q=128, block_k=128, interpret=True)
        return jnp.sum(jnp.sin(o))

    def loss_ref(q, k, v):
        return jnp.sum(jnp.sin(mha_reference(q, k, v, causal=True, segment_ids=segs)))

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{name}"
        )


def test_packed_sequence_training_step():
    """End-to-end: packed batch (segment_ids + restarting positions) trains
    and matches the loss of the equivalent unpacked batch."""
    from ray_tpu.models import TransformerConfig, cross_entropy_loss
    from ray_tpu.models.transformer import init_params

    cfg = TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2, d_ff=64,
        max_seq_len=32, dtype=jnp.float32, attention_impl="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    # Two examples of length 8 packed into one row of 16.
    ex = jax.random.randint(jax.random.PRNGKey(1), (2, 8), 0, 64)
    packed_tokens = ex.reshape(1, 16)
    segs = jnp.array([[0] * 8 + [1] * 8])
    positions = jnp.array([list(range(8)) + list(range(8))])
    packed_loss = cross_entropy_loss(
        params,
        {"tokens": packed_tokens, "segment_ids": segs, "positions": positions},
        cfg,
    )
    # Unpacked: mean of the two examples' per-token NLL (equal lengths).
    unpacked_loss = cross_entropy_loss(params, {"tokens": ex}, cfg)
    np.testing.assert_allclose(float(packed_loss), float(unpacked_loss), rtol=1e-5)


# ---------------------------------------------------------------------------
# live sub-tiles (PR 53)
# ---------------------------------------------------------------------------

def _runs(*lengths_and_ids):
    """One row of ids: (length, id) pairs laid end to end."""
    return np.concatenate([np.full(n, i, np.int32) for n, i in lengths_and_ids])


_UNSORTED = np.random.default_rng(53).integers(0, 3, (2, S)).astype(np.int32)
_UNSORTED[:, :64] = 7  # a run of one id, then ids in no order
# name: (ids [B, S] or None, window, H, KV). Blocks of 128 walked in sub-tiles of 32: a boundary at 96 or 128 lies on
# a sub-tile's edge, one at 100 or 150 inside a sub-tile.
_WALKS = {
    "packed ascending": (np.stack([_runs((40, 1), (56, 2), (104, 3), (56, 0)), _runs((128, 1), (100, 2), (28, 3))]), 0, 4, 2),
    "unsorted": (_UNSORTED, 0, 4, 2),
    "pad tail": (np.stack([_runs((150, 0), (106, 1)), _runs((33, 0), (223, 1))]), 0, 4, 2),
    "boundary on an edge": (np.stack([_runs((96, 1), (160, 2)), _runs((128, 1), (128, 2))]), 0, 4, 2),
    "boundary off an edge": (np.stack([_runs((100, 1), (156, 2)), _runs((150, 1), (106, 2))]), 0, 4, 2),
    "group of 1": (np.stack([_runs((100, 1), (156, 2)), _runs((96, 1), (160, 2))]), 0, 2, 2),
    "group of 4": (np.stack([_runs((100, 1), (156, 2)), _runs((96, 1), (160, 2))]), 0, 4, 1),
    "window": (None, 48, 4, 2),
    "window with ids": (np.stack([_runs((40, 1), (56, 2), (104, 3), (56, 0)), _runs((150, 0), (106, 1))]), 48, 4, 2),
    "no ids": (None, 0, 4, 2),
}


@pytest.fixture
def small_sub_tiles(monkeypatch):
    monkeypatch.setattr(attention, "SUB_TILE", 32)


@pytest.mark.parametrize("name", list(_WALKS))
def test_live_sub_tiles_match_reference(name, small_sub_tiles):
    """Forward and gradients of the walk over live sub-tiles against the oracle, at today's tolerances: a skipped
    sub-tile is one whose every pair was masked."""
    ids, window, H, KV = _WALKS[name]
    q, k, v = _qkv(jax.random.PRNGKey(53), H, KV)
    seg = None if ids is None else jnp.asarray(ids)
    live, causal = live_tiles(ids, S, 128, 128, window)
    assert live < causal or name == "no ids"  # something is skipped in every case but one

    def flash(q, k, v):
        return flash_attention(q, k, v, segment_ids=seg, window=window, block_q=128, block_k=128, interpret=True)

    def ref(q, k, v):
        return mha_reference(q, k, v, segment_ids=seg, window=window)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)), np.asarray(ref(q, k, v)), atol=2e-5, rtol=2e-5)
    g_flash = jax.grad(lambda *a: jnp.sum(jnp.sin(flash(*a))), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(lambda *a: jnp.sum(jnp.sin(ref(*a))), argnums=(0, 1, 2))(q, k, v)
    for gf, gr, which in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=5e-4, rtol=5e-4, err_msg=f"d{which}")


def test_rows_whose_first_sub_tiles_are_dead_stay_finite(small_sub_tiles):
    """The last document's rows meet three dead k blocks and then dead sub-tiles before their first live one: the
    accumulators are still at their initial values there, and nothing of that may reach o or lse."""
    ids = jnp.asarray(np.stack([_runs((200, 1), (56, 2)), _runs((130, 1), (126, 2))]))
    q, k, v = _qkv(jax.random.PRNGKey(7), 2, 2)
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, S, D)
    o, lse = attention._fwd_pallas(
        fold(q), fold(k), fold(v), jnp.broadcast_to(ids[:, None, :], (B, 8, S)), causal=True, scale=D ** -0.5,
        block_q=64, block_k=64, group=1, H=2, interpret=True, sub_tile=attention.SUB_TILE)
    assert np.isfinite(np.asarray(o)).all() and np.isfinite(np.asarray(lse)).all()
    assert float(jnp.min(lse)) > -1e3  # no row's statistics were left at NEG_INF
    ref = mha_reference(q, k, v, segment_ids=ids)
    np.testing.assert_allclose(np.asarray(o.reshape(B, 2, S, D).transpose(0, 2, 1, 3)), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_a_dead_sub_tile_is_not_computed(small_sub_tiles):
    """Not masked: skipped. Blocks of 128 in sub-tiles of 32, a document of 64 rows and one of 192. NaN in rows 32-63
    of K and V would reach rows 0-31 through the diagonal block's sub-tile above the diagonal and rows 64-255
    through the sub-tiles between the documents if those were multiplied by p = 0 as the whole-block kernels did;
    NaN in the second document's q and do would reach the first one's dk and dv likewise."""
    ids = jnp.asarray(np.stack([_runs((64, 1), (192, 2))] * B))
    q, k, v = _qkv(jax.random.PRNGKey(11), 4, 2)
    clean = np.r_[0:32, 64:S]

    def loss(rows):
        def f(q, k, v):
            o = flash_attention(q, k, v, segment_ids=ids, block_q=128, block_k=128, interpret=True)
            return jnp.sum(jnp.sin(o[:, rows])), o
        return jax.grad(f, argnums=(0, 1, 2), has_aux=True)

    (dq, _, _), o = loss(clean)(q, k.at[:, 32:64].set(jnp.nan), v.at[:, 32:64].set(jnp.nan))
    assert np.isfinite(np.asarray(o[:, clean])).all() and np.isnan(np.asarray(o[:, 32:64])).all()
    assert np.isfinite(np.asarray(dq[:, clean])).all()
    (_, dk, dv), _ = loss(np.r_[0:64])(q.at[:, 64:].set(jnp.nan), k, v)
    assert np.isfinite(np.asarray(dk[:, :64])).all() and np.isfinite(np.asarray(dv[:, :64])).all()


@pytest.mark.parametrize("window", [0, 96])
def test_live_tiles_counts_what_a_brute_force_mask_holds(window, small_sub_tiles):
    """A sub-tile is counted live exactly where its rows' and columns' id ranges meet inside the band, and every
    sub-tile that holds a live pair is among them (the kernel skips the rest)."""
    rng = np.random.default_rng(window)
    ids = np.sort(rng.integers(0, 6, (3, S)), axis=1).astype(np.int32)
    ids[2] = rng.integers(0, 6, S)  # one row in no order
    rows, cols = np.arange(S)[:, None], np.arange(S)[None, :]
    seen = (rows >= cols) & ((cols > rows - window) if window else True)
    pairs = seen[None] & (ids[:, :, None] == ids[:, None, :])
    tiles = lambda m: m.reshape(m.shape[0], S // 32, 32, S // 32, 32)
    holds_a_pair = tiles(pairs).any((2, 4))
    lo, hi = tiles(np.broadcast_to(ids[:, :, None], pairs.shape)), tiles(np.broadcast_to(ids[:, None, :], pairs.shape))
    ranges_meet = (lo.min((2, 4)) <= hi.max((2, 4))) & (hi.min((2, 4)) <= lo.max((2, 4))) & tiles(np.broadcast_to(seen, pairs.shape)).any((2, 4))
    live, causal = live_tiles(ids, S, 128, 128, window)
    assert live == ranges_meet.sum() and causal == 3 * 8 * 9 // 2
    assert not (holds_a_pair & ~ranges_meet).any()
    assert holds_a_pair[:2].sum() == ranges_meet[:2].sum()  # sorted ids: ranges that meet hold a pair
    band = tiles(seen[None]).any((2, 4))[0]
    assert live_tiles(None, S, 128, 128, window)[0] == band.sum()
    # The kernels' walk reads the band by `_band_columns`, a q sub-tile's range of a block's k sub-tiles: the same.
    for q_tile in range(S // 32):
        for k_block in range(S // 128):
            lo, hi = attention._band_columns(q_tile * 32, 32, k_block * 128, 32, 4, window)
            assert [lo <= j < hi for j in range(4)] == list(band[q_tile, 4 * k_block:4 * k_block + 4]), (q_tile, k_block)


def test_live_tiles_of_the_train_cells_rows(monkeypatch):
    """ISSUE 53's table, from the rows the train cell runs (the same for every seed). A row's 4096 x 4096 scores
    were ten blocks of 1024 x 1024 to the whole-block kernels; at the sub-tile that was kept, 512, they are 36
    causal sub-tiles of which 27.95 are live, 0.699 of those pairs; at 256, which the chip refused (PERF.md
    section 6), 86.91 of 136, 0.543. The record of what the kernels visit cannot drift from the kernels: their
    tables are made from `_live_sub_tiles` too."""
    import sys
    bench = pathlib.Path(__file__).resolve().parents[1] / "benchmarks"
    sys.path.insert(0, str(bench))
    try:
        from harness import schedule
    finally:
        sys.path.remove(str(bench))
    traffic = json.loads((bench / "traffic" / "pretrain-packed-4k.json").read_text())
    ids = schedule.train_arrays(traffic, 1, 32768)["segment_ids"][:, :-1]
    assert ids.shape == (233, 4096) and attention.SUB_TILE == 512
    for sub, causal_a_row, live_a_row, of_todays_pairs in ((512, 36, 27.95, 0.699), (256, 136, 86.91, 0.543)):
        monkeypatch.setattr(attention, "SUB_TILE", sub)
        live, causal = live_tiles(ids, 4096, 1024, 1024)
        assert causal == 233 * causal_a_row
        assert round(live / 233, 2) == live_a_row
        assert round(live * sub * sub / (233 * 10 * 1024 * 1024), 3) == of_todays_pairs


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


def _kernels(jaxpr, found):
    """name -> equations of every pallas_call's body in a jaxpr, nested calls included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = _equations(eqn.params["jaxpr"])
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns") and eqn.primitive.name != "pallas_call":
                    _kernels(inner, found)
    return found


# What a warm start pays: a Pallas body is traced and lowered again in every program that holds it (four calls a
# layer in the train step, one in each prefill bucket's program of every serve cell), compile cache or not: the
# first form of this walk, 185-219 equations with a dozen floor divisions, cost `laguna` 20 s of a warm-up of 78
# (PERF.md section 6, PR 53). The walk is two rolled loops around one sub-tile, so a body does not grow with the
# sub-tiles a block holds (the unrolled walk of 16 sub-tiles of 256 counted 1,398 / 1,236 / 1,334); its scalar
# arithmetic divides by `lax.div`, one equation where `//` is a dozen. The whole-block bodies of the parent
# counted 86-106 / 59-67 / 82-90 at the same shapes. The limits are what was read here (129 / 99 / 104 at most)
# and a few more.
FLASH_BODIES = {"flash_attn_fwd": 135, "flash_attn_dq": 105, "flash_attn_dkv": 110}


def _flash_bodies(blocks, window, with_ids):
    Bq, Sq, H, KV, Dh = 1, 4096, 8, 2, 128
    q = jnp.zeros((Bq, Sq, H, Dh), jnp.bfloat16)
    kv = jnp.zeros((Bq, Sq, KV, Dh), jnp.bfloat16)
    ids = jnp.zeros((Bq, Sq), jnp.int32) if with_ids else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, segment_ids=ids, window=window, block_q=blocks, block_k=blocks,
                                       interpret=True).astype(jnp.float32))

    return _kernels(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, kv, kv).jaxpr, {})


@pytest.mark.parametrize("blocks,window,with_ids", [(1024, 0, True), (512, 512, True), (512, 0, False)])
def test_the_flash_bodies_stay_small(blocks, window, with_ids):
    """The train cell's call (blocks of 1024: four sub-tiles of 512 a block, ids) and the serve cells' (blocks of
    512, one sub-tile; a window or none)."""
    found = _flash_bodies(blocks, window, with_ids)
    suffix = "_win" if window else ""  # a windowed call's kernels say so in their names
    assert set(found) == {name + suffix for name in FLASH_BODIES}
    for name, n in found.items():
        assert n <= FLASH_BODIES[name.removesuffix(suffix) if suffix else name], (name, n)


def test_the_flash_bodies_do_not_grow_with_the_sub_tiles_of_a_block(monkeypatch):
    one = _flash_bodies(1024, 0, True)
    monkeypatch.setattr(attention, "SUB_TILE", 128)  # 64 sub-tiles a block
    many = _flash_bodies(1024, 0, True)
    assert all(many[name] <= one[name] for name in one)  # less by the repeat along the lanes a width of 128 spares
