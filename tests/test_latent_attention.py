"""ops/latent_attention.py's kernel in interpret mode against its jax.numpy
reference, on the paged kernel's walk of page groups: the context and the
returned pool over page groups of 1, 3 and 8 and the lengths a walk can get
wrong, the size of the kernel's body, and a latent model served through the
kernel with empty and retiring slots. tests/test_chip_compiles.py holds the
TPU compiler's word on the call, chip_smoke.py and the benchmark the chip's."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.llm import cache_rules
from ray_tpu.llm.engine import EngineConfig, LLMEngine
from ray_tpu.models.transformer import TransformerConfig
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops import latent_attention as la
from ray_tpu.ops.latent_attention import latent_attention_reference, latent_paged_attention
from ray_tpu.ops.paged_attention import group_pages, page_groups

PS = 16  # rows a page
GROUPS = (1, 3, 8)


def _table_width(G):
    return 2 * G + 2  # so that a sequence can hold two full groups and a page


# lengths [B] as a function of the page group: what the walk, the copies and the last turn can get wrong
LENGTHS = {
    # zeros returned, no row of the pool written, no step
    "a_row_of_length_0_beside_live_rows": lambda G: [0, 5, 0, G * PS + 3],
    "every_row_empty": lambda G: [0, 0, 0, 0],
    # exactly one page; exactly G pages; G pages and a row (a second group of one page); exactly G + 1 pages
    "one_page_G_pages_and_G_plus_1": lambda G: [PS, G * PS, G * PS + 1, (G + 1) * PS],
    # the table's last row, the row before it, a sequence run past its table, a first token
    "the_tables_last_row_and_past_it": lambda G: [_table_width(G) * PS, _table_width(G) * PS - 1,
                                                  _table_width(G) * PS + 5, 1],
    "a_pages_first_and_last_row": lambda G: [PS + 1, 2 * PS, 2 * G * PS + 1, 2 * G * PS],
    # two full groups and a page; a group one page short (its turn is not full at G > 1)
    "a_last_turn_that_is_not_full": lambda G: [(2 * G + 1) * PS - 2, max(G - 1, 1) * PS - 1, 0, (G + 2) * PS - 7],
}


def _case(lengths, n_pages, *, H=8, W=128, ps=PS, L=2, seed=0):
    """(q, row, pool, lengths, table): a pool of random rows, every live row's
    pages its own and in no order, dead entries naming page 0."""
    rng = np.random.default_rng(seed)
    B = len(lengths)
    q = jnp.asarray(rng.normal(size=(B, H, W)), jnp.float32)
    row = jnp.asarray(rng.normal(size=(B, W)), jnp.float32)
    pool = jnp.asarray(rng.normal(size=(L, 1 + B * n_pages, ps, W)), jnp.float32)
    own = 1 + rng.permutation(B * n_pages).reshape(B, n_pages)
    held = np.arange(n_pages)[None, :] * ps < np.asarray(lengths)[:, None]
    return q, row, pool, jnp.asarray(lengths, jnp.int32), jnp.asarray(np.where(held, own, 0), jnp.int32)


def _steps(lengths, ps, n_pages, G):
    return sum(-(-min(-(-n // ps), n_pages) // G) for n in lengths)


def _assert_kernel_matches(args, layer, G, *, v_width=64, scale=0.17, atol=2e-5, exact=None):
    """`exact`: the same values in float32, for the reference of a bfloat16 call."""
    q, row, pool, lengths, table = args
    ps, n_pages = pool.shape[2], table.shape[1]
    walk = page_groups(lengths, table, ps, 0, G)
    assert int(walk[-1][0]) == _steps(np.asarray(lengths).tolist(), ps, n_pages, G)  # none for an empty row
    want, pool_want = latent_attention_reference(*(exact or args), layer, v_width=v_width, scale=scale)
    got, pool_got = latent_paged_attention(*args, layer, v_width=v_width, scale=scale, walk=walk, interpret=True)
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want), atol=atol, rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(pool_got, np.float32), np.asarray(pool_want))
    # an empty row: zeros, and nothing of the pool written (no row at all where every row is empty)
    empty = np.asarray(lengths) == 0
    assert not np.asarray(got)[empty].any()
    changed = (np.asarray(pool_got) != np.asarray(pool)).any(axis=-1)  # [L, pages, ps]
    assert changed.sum() == (~empty).sum() and not changed[1 - layer].any()
    for b, n in enumerate(np.asarray(lengths).tolist()):
        if n:
            page, at = int(table[b, min((n - 1) // ps, n_pages - 1)]), (n - 1) % ps
            np.testing.assert_array_equal(np.asarray(pool_got[layer, page, at]), np.asarray(row[b]))


@pytest.mark.parametrize("G", GROUPS)
@pytest.mark.parametrize("lengths", sorted(LENGTHS))
def test_the_latent_kernel_on_page_groups_matches_its_reference(lengths, G):
    """The chain's turn is the whole group at these widths (``_turn_pages``:
    8 heads and pages of 16 rows make a small score tile), so a group that is
    not full is a turn that is not full."""
    assert la._turn_pages(8, PS, G) == G
    args = _case(LENGTHS[lengths](G), _table_width(G), seed=G + len(lengths))
    _assert_kernel_matches(args, 1, G)


def test_a_turn_of_four_pages_at_the_cells_heads_and_page_size():
    """128 heads and pages of 128 rows, the expert cell's: the chain takes 4
    pages a turn of a group of 8, so groups of 6 and 2 pages end in turns
    that are not full, one of 3 pages is less than a turn, and one of 8 is
    two turns; the pool's rows bfloat16 as the cell's."""
    H, ps, G = 128, 128, 8
    assert la._turn_pages(H, ps, G) == 4 and group_pages(1, ps, 640, 2, 32) == 8
    lengths = [5 * ps + 1, 9 * ps + 5, 0, 3 * ps, 8 * ps]
    q, row, pool, lens, table = _case(lengths, 10, H=H, ps=ps, seed=3)
    args = (q.astype(jnp.bfloat16), row.astype(jnp.bfloat16), pool.astype(jnp.bfloat16), lens, table)
    exact = tuple(a.astype(jnp.float32) for a in args[:3]) + (lens, table)
    _assert_kernel_matches(args, 0, G, atol=1e-2, exact=exact)  # probabilities and outputs rounded to bfloat16


@pytest.mark.parametrize("G", GROUPS)
def test_a_traced_layer_and_a_walk_built_inside_or_handed_in_give_equal_results(G):
    """The engine's layer scan passes its counter and one walk for all its
    layers; a caller without a walk gets the one the call builds from the
    pool's shapes (``group_pages``)."""
    n_pages = _table_width(G)
    args = _case([3, 0, G * PS + 2, n_pages * PS], n_pages, seed=7)
    lengths, table = args[3:]
    kw = dict(v_width=64, scale=0.17, interpret=True)
    handed = jax.jit(lambda layer, *a: latent_paged_attention(
        *a, layer, walk=page_groups(a[3], a[4], PS, 0, G), **kw))
    inside = jax.jit(lambda layer, *a: latent_paged_attention(*a, layer, **kw))
    for layer in (0, 1):
        want = latent_attention_reference(*args, layer, v_width=64, scale=0.17)
        for got in (handed(jnp.int32(layer), *args), inside(jnp.int32(layer), *args)):
            np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), atol=2e-5, rtol=1e-5)
            np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(want[1]))


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


# The paged kernel's body at its serve cells' shapes (tests/test_paged_attention.py
# holds it under 601, the one-page body's); this kernel's from PR 36 to PR 47,
# one page a grid step through a BlockSpec, counted 130 at commit 752a738.
PAGED_KERNELS_BODY = 340


def test_the_latent_kernels_body_does_not_grow_with_the_page_group_nor_the_heads():
    """What every start of a replica pays, compile cache or not: the decode
    program traces and lowers the kernel's body once a call (four times a
    start in the expert cell), so its size is `setup_warmup_s` (PR 42's
    unrolled body cost 5 s a call a program and was refused for it). At the
    cell's shapes, counted in equations: equal at every G > 1 and at 16 heads
    as at 128, and at G = 1 less by the loop that fills up a turn of several
    pages; never over the paged kernel's."""
    B, W, R, ps, n_pages = 4, 640, 512, 128, 32

    def body(G, H):
        args = (jnp.zeros((B, H, W), jnp.bfloat16), jnp.zeros((B, W), jnp.bfloat16),
                jnp.zeros((2, 8, ps, W), jnp.bfloat16), jnp.ones(B, jnp.int32), jnp.zeros((B, n_pages), jnp.int32),
                jnp.int32(0))
        jaxpr = jax.make_jaxpr(lambda *a: latent_paged_attention(
            *a, v_width=R, scale=0.07, interpret=True, walk=page_groups(a[3], a[4], ps, 0, G)))(*args)
        calls = [eqn for eqn in jaxpr.jaxpr.eqns if eqn.primitive.name == "pallas_call"]
        assert len(calls) == 1 and calls[0].params["name"] == "latent_attn"
        return _equations(calls[0].params["jaxpr"])

    sizes = {(G, H): body(G, H) for G in (1, 2, 8) for H in (16, 128)}
    assert sizes[2, 16] == sizes[2, 128] == sizes[8, 16] == sizes[8, 128] <= PAGED_KERNELS_BODY
    assert sizes[1, 16] == sizes[1, 128] and 0 <= sizes[8, 128] - sizes[1, 128] <= 16


# ---------------------------------------------------------------------------
# a latent model served through the kernel
# ---------------------------------------------------------------------------

CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=3, n_heads=4, d_ff=48, max_seq_len=128, rope_theta=1e4,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-5, attention_impl="reference",
    attention_kind="latent", q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, sandwich_norm=True, n_dense_layers=1, n_experts=8, expert_top_k=3,
    experts_held=4, first_expert=2, expert_d_ff=16, n_shared_experts=1, routed_scaling=2.5,
    router_score="sigmoid",
)
ENGINE_KW = dict(max_slots=3, max_seq=128, page_size=16, prefill_buckets=(16, 32), decode_block=8)
PROMPTS = [(np.arange(3 + 4 * i, dtype=np.int32) * (i + 3) + i) % 96 for i in range(7)]


def _staggered(eng, prompts, max_tokens, every=2):
    """Requests added one every ``every`` steps and run to their ends: id -> tokens."""
    done, pending, steps = {}, list(enumerate(prompts)), 0
    while pending or eng.has_work():
        if pending and steps % every == 0:
            i, p = pending.pop(0)
            eng.add_request(f"r{i}", p, max_tokens)
        for rid, ev in eng.step().items():
            if ev.get("finished"):
                done[rid] = ev["tokens"]
        steps += 1
    return done


def _handed(eng, decode):
    """eng's decode program wrapped: what every block was handed, as (lengths, tables, n_steps)."""
    handed = []

    def spy(*args):
        handed.append((np.array(args[3]), np.array(args[4]), args[6]))  # copies: the mirrors' buffers are reused
        return decode(*args)

    eng._decode_jit = spy
    return handed


def test_a_latent_model_through_the_kernel_with_empty_and_retiring_slots_emits_the_reference_paths_tokens(monkeypatch):
    """The decode program traced as on a TPU (the step's ONE walk of page
    groups, built by the branch every model takes, and the Pallas kernel,
    here interpreted) under staggered traffic: 7 requests through 3 slots,
    so blocks go out with slots empty (lengths of 0: no grid step, no row
    written, zeros attended) and with rows that an EOS retires inside a
    block. Request for request the tokens of the reference path; a block's
    record counts a latent model's pages and grid steps as every other
    model's: of its active slots alone, no more steps than pages."""
    solo = LLMEngine(CFG, engine_config=EngineConfig(**ENGINE_KW))
    solos = [solo.generate(p, max_tokens=20)["tokens"] for p in PROMPTS]
    # a token some request emits inside a block of 8 and not at its end
    eos = next(tok for toks in solos for at, tok in enumerate(toks[1:], start=1) if (at - 1) % 8 != 7 and at >= 3)
    want = [toks[: toks.index(eos) + 1] if eos in toks else toks for toks in solos]

    eng = LLMEngine(CFG, params=solo.params, engine_config=EngineConfig(**ENGINE_KW, eos_id=eos))
    group = eng.rules[0].group
    assert group == group_pages(1, 16, eng.rules[0].width, 4, 8) == 8  # the rule every pool's group comes from

    def as_on_a_tpu(*args):
        with monkeypatch.context() as m:  # while the program is traced, and no longer
            m.setattr(jax, "default_backend", lambda: "tpu")
            m.setattr(cache_rules, "latent_paged_attention", functools.partial(latent_paged_attention, interpret=True))
            m.setattr(grouped_matmul, "expert_matmul", lambda: grouped_matmul.expert_gmm_reference)  # not under test
            return eng._decode_impl(*args)

    handed = _handed(eng, jax.jit(as_on_a_tpu, donate_argnums=(1,), static_argnums=(6,)))
    got = _staggered(eng, PROMPTS, 20)
    assert [got[f"r{i}"] for i in range(7)] == want
    blocks = [s for s in eng.trace_snapshot()["steps"] if s["block"]]
    assert any(s["active"] < eng.ec.max_slots for s in blocks) and sum(s["dropped_rows"] for s in blocks) >= 1
    ps, table = eng.ec.page_size, eng.ec.max_seq // eng.ec.page_size
    assert len(blocks) == len(handed) > 3
    for s, (lens, tables, n) in zip(blocks, handed):
        assert s["block"] * s["active"] <= s["grid_steps"] <= s["live_pages"] <= s["block"] * s["active"] * table
        # to the digit what the program's walk took, from the lengths and tables it was handed
        live = tables[:, 0] > 0
        pages = np.minimum(-(-np.where(live, lens + np.arange(1, n + 1)[:, None], 0) // ps), table)
        assert s["active"] == live.sum() and s["live_pages"] == pages.sum()
        assert s["grid_steps"] == (-(-pages // group)).sum()
    assert any(s["grid_steps"] < s["live_pages"] for s in blocks)  # a step that held more than a page
