"""The decoder with window and full attention layers and softmax-routed held
experts as models/transformer.py TRAINS it (``layer_pattern``,
``experts_held``, ``router_aux_coef``; ops/grouped_matmul.py's custom VJP)
against its plain reference (models/reference_window_softmax_moe.py), at toy
widths on the CPU with seeded random weights: the packed loss and every leaf's
gradient with a window that binds and two documents a row, the grouped
matmul's backward kernels in interpret mode against ``jax.grad`` of the
``jax.numpy`` form, the balance term against a hand count, the four shares of
a routed layer in output and gradients, the benchmark's copy of the
reference, its key mapping and counts, and the new kernel bodies' sizes."""
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import reference_window_softmax_moe as ref
from ray_tpu.models import transformer
from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, _balance_term, _held_experts_ffn, cross_entropy_loss, forward, init_params,
    loss_and_metrics, make_train_step,
)
from ray_tpu.ops import grouped_matmul
from ray_tpu.ops.grouped_matmul import expert_gmm, expert_gmm_reference, group_rows

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FULL_ROPE = dict(rope_type="yarn", rope_theta=5e5, factor=16, original_max_position_embeddings=16,
                 beta_fast=32, beta_slow=1, attention_factor=1.2772588722239782)
SLIDING_ROPE = dict(rope_type="default", rope_theta=5e5)
WINDOW = 8
FULL = LayerKind("full_attention", 4, rope_theta=5e5, yarn_factor=16.0, yarn_original_len=16,
                 yarn_beta_fast=32.0, yarn_beta_slow=1.0, attention_factor=1.2772588722239782)
SLIDING = LayerKind("sliding_attention", 4, window=WINDOW, rope_theta=5e5)
CFG = TransformerConfig(
    vocab_size=96, d_model=32, n_layers=4, n_heads=4, n_kv_heads=2, head_dim=16, d_ff=48, max_seq_len=64,
    dtype=jnp.float32, param_dtype=jnp.float32, norm_eps=1e-6, attention_impl="reference",
    layer_pattern=(SLIDING, SLIDING, SLIDING, FULL), n_experts=8, expert_top_k=3, experts_held=2, first_expert=2,
    expert_d_ff=16, router_score="softmax", router_aux_coef=0.05,
)
MODEL = dict(rms_norm_eps=1e-6, sliding_window=WINDOW, layer_types=["sliding_attention"] * 3 + ["full_attention"],
             rope_parameters={"full_attention": FULL_ROPE, "sliding_attention": SLIDING_ROPE},
             num_experts_per_tok=3, router_aux_loss_coef=0.05)
HELD = (CFG.first_expert, CFG.experts_held)


def _params(cfg=CFG, seed=0):
    """Seeded random weights, the norms' too (init_params makes them ones)."""
    params = init_params(jax.random.PRNGKey(seed), cfg)
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 100), 64))

    def jitter(path, a):
        if "norm" in jax.tree_util.keystr(path):
            return (a + 0.2 * jax.random.normal(next(keys), a.shape, jnp.float32)).astype(a.dtype)
        return a

    return jax.tree_util.tree_map_with_path(jitter, params)


def _batch(rows=2, seq=40, seed=1):
    """Packed rows of seq + 1 tokens: two documents (23 and 14 tokens: both pass the window of 8) and 4 of padding."""
    rng = np.random.default_rng(seed)
    docs = [23, 14]
    seg = np.concatenate([np.full(d, i + 1) for i, d in enumerate(docs)] + [np.zeros(seq + 1 - sum(docs))])
    pos = np.concatenate([np.arange(d) for d in docs] + [np.zeros(seq + 1 - sum(docs))])
    seg, pos = (np.tile(a.astype(np.int32), (rows, 1)) for a in (seg, pos))
    mask = (seg > 0).astype(np.int32)
    tokens = rng.integers(0, CFG.vocab_size, (rows, seq + 1)).astype(np.int32) * mask
    return {k: jnp.asarray(v) for k, v in dict(tokens=tokens, segment_ids=seg, positions=pos, mask=mask).items()}


def _rel(a, b):
    return float(jnp.linalg.norm((a - b).ravel()) / jnp.maximum(jnp.linalg.norm(b.ravel()), 1e-30))


# ---------------------------------------------------------------------------
# the program's loss and gradients against the plain reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("remat_policy,pairs_a_pass", [("", 32_768), ("dots", 48), ("full", 96)])
def test_the_packed_loss_and_every_leafs_gradient_are_the_references(remat_policy, pairs_a_pass, monkeypatch):
    """A window that binds, two documents a row, a quarter of the experts held, the loss with its balance term;
    without remat in one pass, and under "dots" and "full" in passes of 16 and 32 tokens (a last pass of padding)."""
    monkeypatch.setattr(transformer, "PAIRS_A_PASS", pairs_a_pass)
    cfg = TransformerConfig(**{**CFG.__dict__, "remat": bool(remat_policy), "remat_policy": remat_policy or "full"})
    params, batch = _params(), _batch()
    loss, grads = jax.jit(jax.value_and_grad(lambda p: cross_entropy_loss(p, batch, cfg)))(params)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.packed_loss(p, batch, MODEL, HELD)))(params)
    assert abs(float(loss) - float(want)) < 2e-5
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    for (path, g), w in zip(flat, jax.tree.leaves(want_grads)):
        assert float(jnp.linalg.norm(w.ravel())) > 0, jax.tree_util.keystr(path)  # no leaf is out of the loss's reach
        assert _rel(g, w) < 2e-4, (jax.tree_util.keystr(path), _rel(g, w))


def test_the_train_steps_metrics_name_the_losss_parts_and_the_experts_counts():
    init, step, _ = make_train_step(CFG)
    state = jax.jit(init)(jax.random.PRNGKey(0))
    batch = _batch()
    _, m = jax.jit(step)(state, batch)
    assert set(m) == {"loss", "nll", "balance_loss", "expert_pairs", "expert_live_tiles", "grad_norm", "step"}
    assert float(m["loss"]) == pytest.approx(float(m["nll"]) + CFG.router_aux_coef * float(m["balance_loss"]), rel=1e-6)
    # 4 layers of 2 x 40 tokens x 3 choices over 8 experts, 2 of them held: a quarter of 960 pairs expected
    assert 0 < float(m["expert_pairs"]) < 960 and float(m["expert_pairs"]) == int(m["expert_pairs"])
    assert 4 <= float(m["expert_live_tiles"]) <= float(m["expert_pairs"])
    assert 4 * 0.9 < float(m["balance_loss"]) < 4 * 2.0  # 1 a layer under uniform routing, more the less even


def test_the_rate_is_the_optimizers_and_no_field_of_the_model():
    """AdamW's first step moves a weight by its rate: make_train_step's own optimizer is AdamW at 3e-4, another rate
    comes in as ``optimizer=`` (the layer that owns it), and the model's configuration has no field for one."""
    import optax

    assert "learning_rate" not in TransformerConfig.__dataclass_fields__
    batch, moved = _batch(), {}
    for rate, optimizer in ((3e-4, None), (3e-5, optax.adamw(3e-5, weight_decay=0.01))):
        init, step, _ = make_train_step(CFG, optimizer=optimizer)
        state = jax.jit(init)(jax.random.PRNGKey(0))
        before = state["params"]["lm_head"]
        after = jax.jit(step)(state, batch)[0]["params"]["lm_head"]
        moved[rate] = float(jnp.max(jnp.abs(after - before)))
    assert moved[3e-4] == pytest.approx(3e-4, rel=0.05) and moved[3e-5] == pytest.approx(3e-5, rel=0.05)


def test_the_coefficients_default_keeps_the_every_expert_forms_loss():
    """``router_aux_coef`` defaults to the 0.01 that stood in the loss; _moe_ffn's term still counts a first choice."""
    cfg = TransformerConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=4, d_ff=48, max_seq_len=64, n_experts=4,
                            dtype=jnp.float32, param_dtype=jnp.float32, attention_impl="reference")
    assert cfg.router_aux_coef == 0.01
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": _batch()["tokens"]}
    loss, parts = loss_and_metrics(params, batch, cfg)
    _, aux = forward(params, batch["tokens"][:, :-1], cfg)
    assert float(parts["balance_loss"]) == pytest.approx(float(aux)) and float(aux) > 0
    assert float(loss) == pytest.approx(float(parts["nll"]) + 0.01 * float(aux), rel=1e-6)
    assert float(parts["expert_pairs"]) == 0 == float(parts["expert_live_tiles"])


# ---------------------------------------------------------------------------
# the balance term
# ---------------------------------------------------------------------------

def test_the_balance_term_is_the_hand_count():
    """4 tokens, 2 choices of 4 experts: pairs on the experts 3, 2, 2, 1 of 8; mean scores 0.4, 0.3, 0.2, 0.1:
    4 x (3/8 x 0.4 + 2/8 x 0.3 + 2/8 x 0.2 + 1/8 x 0.1) = 1.15; uniform routing reads 1."""
    cfg = TransformerConfig(n_experts=4, expert_top_k=2)
    chosen = jnp.asarray([3.0, 2.0, 2.0, 1.0])
    score_sum = 4 * jnp.asarray([0.4, 0.3, 0.2, 0.1])
    assert float(_balance_term(chosen, score_sum, 4, cfg)) == pytest.approx(1.15)
    assert float(_balance_term(jnp.full(4, 2.0), jnp.full(4, 1.0), 4, cfg)) == pytest.approx(1.0)


def test_a_trained_layers_term_counts_every_choice_of_every_expert_and_no_padding(monkeypatch):
    """The layer's term over 25 tokens in passes of 16 (7 rows of padding in the second) is the hand count over the
    25 from the router's own scores: all 8 experts, all 3 choices, whichever 2 experts are held."""
    monkeypatch.setattr(transformer, "PAIRS_A_PASS", 48)
    lp = {k: v[0] for k, v in _params()["kind_layers"]["sliding_attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 25, CFG.d_model), jnp.float32)
    _, aux = _held_experts_ffn(x, lp, CFG, balance=True)
    p = jax.nn.softmax(jnp.einsum("td,de->te", x[0], lp["router"], precision="highest"), axis=-1)
    _, top = jax.lax.top_k(p, 3)
    pairs_on = np.bincount(np.asarray(top).ravel(), minlength=8)
    assert pairs_on.sum() == 75
    want = 8 * float(np.sum(pairs_on / 75 * np.asarray(jnp.mean(p, axis=0))))
    assert float(aux[0]) == pytest.approx(want, rel=1e-5)
    # the counts are the grouped matmul's work: the 7 rows of padding (zeros: an even score, experts 0, 1 and 2
    # chosen, 2 of them held here) are among its pairs, as the served layer counts them
    assert int(aux[1]) == pairs_on[2] + pairs_on[3] + 7
    served, counts = _held_experts_ffn(x, lp, CFG)  # the served layer: counts alone, as it was
    assert counts.dtype == jnp.int32 and counts.shape == (2,)


# ---------------------------------------------------------------------------
# the grouped matmul's custom VJP, the kernels in interpret mode
# ---------------------------------------------------------------------------

def _grouped_case(seed=0, tm=8):
    """40 tokens x 3 choices over held experts 1 .. 6: local expert 2 empty, expert 3 of several tiles, ragged last
    tiles; a stack of 3 layers, layer 1 used."""
    rng = np.random.default_rng(seed)
    top = rng.integers(0, 9, (40, 3)).astype(np.int32)
    top[top == 3] = 4  # nobody chooses global expert 3 = local 2
    plan = group_rows(jnp.asarray(top), 1, 6, tm)
    sizes = np.asarray(plan.sizes)
    assert sizes[2] == 0 and sizes[3] > 2 * tm and any(s % tm for s in sizes)
    M = plan.token_of_row.shape[0]
    x = jnp.asarray(rng.normal(size=(M, 128)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 6, 128, 256)), jnp.float32) / 10
    return plan, x, w, tm


def _grouped_grads(fn, plan, x, w, tm, ct, **kw):
    live = (jnp.arange(x.shape[0]) < plan.n_tiles[0] * tm)[:, None]

    def loss(x, w):
        y = fn(x, w, jnp.int32(1), plan.tile_expert, plan.n_tiles, tm=tm, **kw)
        return jnp.sum(jnp.where(live, y, 0.0) * ct)  # rows of dead tiles are not the kernel's to write

    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, w)


def test_the_grouped_matmuls_vjp_is_jax_grad_of_the_jax_numpy_form():
    plan, x, w, tm = _grouped_case()
    ct = jax.random.normal(jax.random.PRNGKey(1), (x.shape[0], 256), jnp.float32)
    want, (dx_want, dw_want) = _grouped_grads(expert_gmm_reference, plan, x, w, tm, ct)
    got, (dx, dw) = _grouped_grads(expert_gmm, plan, x, w, tm, ct, interpret=True, block_k=128, block_n=128)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    np.testing.assert_allclose(dx, dx_want, atol=2e-5)
    np.testing.assert_allclose(dw, dw_want, atol=5e-5)
    assert dw.shape == w.shape  # the stack's shape: the other layers' and the empty expert's slices are zeros
    dead_rows = np.arange(x.shape[0]) >= int(plan.n_tiles[0]) * tm
    assert not np.asarray(dx)[dead_rows].any() and not np.asarray(dw)[[0, 2]].any() and not np.asarray(dw)[1, 2].any()
    assert np.asarray(dw)[1, [0, 1, 3, 4, 5]].any(axis=(1, 2)).all()


def test_a_padding_row_inside_a_live_tile_adds_nothing_to_dw():
    """Padding rows hold token 0's row going in; nothing reads their result back, so their cotangent is zero, and
    whatever they hold the weight's gradient is the same."""
    plan, x, w, tm = _grouped_case(seed=2)
    rows = np.zeros(x.shape[0], bool)
    rows[np.asarray(plan.row_of_pair)[np.asarray(plan.held)]] = True  # the rows that hold a pair
    live = np.arange(x.shape[0]) < int(plan.n_tiles[0]) * tm
    assert (live & ~rows).any()
    ct = jnp.where(rows[:, None], jax.random.normal(jax.random.PRNGKey(1), (x.shape[0], 256), jnp.float32), 0.0)
    kw = dict(interpret=True, block_k=128, block_n=128)
    _, (_, dw) = _grouped_grads(expert_gmm, plan, x, w, tm, ct, **kw)
    _, (_, dw_other) = _grouped_grads(expert_gmm, plan, jnp.where(rows[:, None], x, 7.0), w, tm, ct, **kw)
    np.testing.assert_array_equal(dw, dw_other)


def test_a_held_layer_trains_through_the_kernels_as_through_the_jax_numpy_form(monkeypatch):
    """One routed layer's output, balance term and gradients (to its input, the router and the three stacks) with
    the grouped matmul's Pallas kernels (interpret mode) in place of the ``jax.numpy`` form, in two passes."""
    monkeypatch.setattr(transformer, "PAIRS_A_PASS", 96)
    cfg = TransformerConfig(**{**CFG.__dict__, "d_model": 128, "expert_d_ff": 128})
    stack = _params(cfg)["kind_layers"]["sliding_attention"]
    lp = {**{k: v[1] for k, v in stack.items() if k not in transformer.HELD_EXPERT_WEIGHTS},
          **{k: stack[k] for k in transformer.HELD_EXPERT_WEIGHTS}}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape, jnp.float32)

    def loss(x, lp):
        out, aux = _held_experts_ffn(x, {**lp, "expert_layer": jnp.int32(1)}, cfg, balance=True)
        return jnp.sum(out * ct) + 0.3 * aux[0]

    want = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, lp)
    monkeypatch.setattr(grouped_matmul, "expert_matmul", lambda: functools.partial(expert_gmm, interpret=True))
    got = jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, lp)
    assert float(got[0]) == pytest.approx(float(want[0]), rel=1e-5)
    for name in ("router", *transformer.HELD_EXPERT_WEIGHTS):
        assert _rel(got[1][1][name], want[1][1][name]) < 1e-5, name
        assert float(jnp.linalg.norm(want[1][1][name])) > 0
    assert _rel(got[1][0], want[1][0]) < 1e-5
    for name in transformer.HELD_EXPERT_WEIGHTS:  # the stack's other layers: zeros
        assert not np.asarray(got[1][1][name])[[0, 2]].any()


# ---------------------------------------------------------------------------
# the two copies between token space and the grouped matmul's row space (``_token_rows``, ``_row_tokens``)
# ---------------------------------------------------------------------------

def _even(rng):
    return np.stack([rng.permutation(8)[:3] for _ in range(24)])


ROUTINGS = {  # [T, K] choices among 8 experts, of which 2 .. 5 are held, in tiles of 8 rows
    "even": _even,
    "no pair held": lambda rng: np.stack([rng.permutation([0, 1, 6, 7])[:3] for _ in range(24)]),
    # 17, 17, 17 and 21 pairs: every held expert's last tile is partly filled, 12 live tiles of the plan's 13
    "every pair held": lambda rng: np.stack([np.delete([2, 3, 4, 5], min(t // 7, 3)) for t in range(24)]),
    "one expert takes every pair": lambda rng: np.full((24, 1), 3),
    "a last tile partly padded": lambda rng: np.full((21, 1), 2),
}


@pytest.mark.parametrize("chunk", [0, 40, 16], ids=["whole", "chunks_of_40", "chunks_of_16"])
@pytest.mark.parametrize("routing", sorted(ROUTINGS))
def test_the_layouts_two_copies_and_their_vjps_are_the_plain_gathers_on_live_rows(routing, chunk, monkeypatch):
    """Forward and VJP of both copies against ``jax.vjp`` of the plain gathers (every row of the plan gathered by
    token, every pair of T x K gathered by row): whole, and with the way back's rows in chunks that do and do not
    divide the plan (the last chunk then starts early and writes rows twice) and the sums by token in levels of 8
    tokens a step (a level's last chunk starts early too and adds no token twice). What dead tiles and padding
    rows hold reaches nothing: they are NaN here in everything the copies are handed."""
    monkeypatch.setattr(transformer, "LEVEL_CHUNK", 8)
    tm, D = 8, 16
    rng = np.random.default_rng(7)
    top = jnp.asarray(ROUTINGS[routing](rng).astype(np.int32))
    (T, K), plan = top.shape, group_rows(top, 2, 4, tm)
    M, live_rows = plan.pair_of_row.shape[0], int(plan.n_tiles[0]) * tm
    real = np.asarray(plan.pair_of_row) >= 0
    assert real.sum() == int(np.asarray(plan.held).sum()) and not real[live_rows:].any() and chunk <= M
    if routing == "every pair held":  # as many live tiles as 72 pairs on 4 experts can fill: the worst case
        assert real.sum() == T * K and live_rows == M - tm
    if routing == "a last tile partly padded":
        assert live_rows == 24 and real.sum() == 21
    assert (live_rows == 0) == (routing == "no pair held")
    xt = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    top_w = jnp.asarray(rng.uniform(0.1, 1.0, size=(T, K)), jnp.float32)
    y, ct_rows = (jnp.asarray(rng.normal(size=(M, D)), jnp.float32) for _ in range(2))
    ct_tokens = jnp.asarray(rng.normal(size=(T, D)), jnp.float32)
    poisoned = lambda a: jnp.where(real[:, None], a, jnp.nan)  # noqa: E731
    zeroed = lambda a: jnp.where(real[:, None], a, 0.0)  # noqa: E731

    rows, back = jax.vjp(lambda xt: transformer._token_rows(xt, plan, chunk), xt)
    want_rows, want_back = jax.vjp(lambda xt: xt[plan.token_of_row], xt)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_allclose(back(poisoned(ct_rows))[0], want_back(zeroed(ct_rows))[0], atol=1e-5)

    def plain(y, top_w):
        pair = y[plan.row_of_pair] * top_w[..., None]
        return jnp.sum(jnp.where(plan.held[..., None], pair, 0.0), axis=1)

    out, back = jax.vjp(lambda y, w: transformer._row_tokens(y, w, plan, tm, chunk), poisoned(y), top_w)
    want_out, want_back = jax.vjp(plain, zeroed(y), top_w)
    np.testing.assert_allclose(out, want_out, atol=1e-5)
    (dy, dw), (want_dy, want_dw) = back(ct_tokens), want_back(ct_tokens)
    np.testing.assert_allclose(dy[:live_rows], want_dy[:live_rows], atol=1e-5)  # zeros for a live tile's padding rows
    assert not chunk or not np.asarray(dy)[-(-live_rows // chunk) * chunk:].any()  # off a TPU: zeros past the chunks
    np.testing.assert_allclose(dw, want_dw, atol=1e-5)


def test_a_plan_of_four_chunks_on_follows_its_held_pairs_unless_every_expert_is_held():
    def cfg(held):
        return TransformerConfig(**{**CFG.__dict__, "n_experts": 64, "experts_held": held, "first_expert": 0})

    assert [transformer._layout_chunk(rows, cfg(16)) for rows in (1792, 8191, 8192, 36864)] == [0, 0, 2048, 2048]
    assert [transformer._layout_chunk(49152, cfg(held)) for held in (8, 32, 33, 64)] == [2048, 2048, 0, 0]
    assert transformer.LAYOUT_CHUNK % 256 == 0  # whole tiles, whatever _expert_tile picks


def test_a_trained_layer_gives_the_same_in_chunks_as_whole(monkeypatch):
    """One routed layer in two passes, output, balance term and gradients: its plans (136 rows of 8) with the way
    back in chunks of 32 rows and the sums by token in levels of 8 tokens a step, against the same plans moved whole."""
    monkeypatch.setattr(transformer, "PAIRS_A_PASS", 96)
    lp = {k: v[1] for k, v in _params()["kind_layers"]["sliding_attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, CFG.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(4), x.shape, jnp.float32)

    def loss(x, lp):
        out, aux = _held_experts_ffn(x, lp, CFG, balance=True)
        return jnp.sum(out * ct) + 0.3 * aux[0], out

    def run():
        jax.clear_caches()  # jax.checkpoint keeps a pass's trace, and the chunk is read inside it
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1), has_aux=True))(x, lp)

    (want, want_out), want_grads = run()
    monkeypatch.setattr(transformer, "_layout_chunk", lambda rows, cfg: 32 if rows >= 128 else 0)
    monkeypatch.setattr(transformer, "LEVEL_CHUNK", 8)
    summed, sums = [], transformer._summed_by_token
    monkeypatch.setattr(transformer, "_summed_by_token", lambda *a: (summed.append(a[3]), sums(*a))[1])
    (got, out), grads = run()
    assert len(summed) >= 2 and all(c == 32 for c in summed)
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    assert _rel(out, want_out) < 1e-6
    for g, w in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        assert _rel(g, w) < 1e-5


# ---------------------------------------------------------------------------
# the shares of an expert-parallel layer
# ---------------------------------------------------------------------------

def test_the_four_shares_of_a_routed_layer_add_up_to_the_uncut_layer():
    """Output, router's gradient and each expert's gradient (in the share that holds it): four chips of two experts
    each against the uncut reference layer. No shared expert to count once; the balance term, whole on every chip,
    is counted once."""
    whole_cfg = TransformerConfig(**{**CFG.__dict__, "experts_held": 8, "first_expert": 0})
    lp = {k: v[0] for k, v in _params(whole_cfg)["kind_layers"]["sliding_attention"].items()}
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 24, CFG.d_model), jnp.float32)
    ct = jax.random.normal(jax.random.PRNGKey(6), x.shape, jnp.float32)

    def reference_loss(lp, with_balance):
        out, term = ref.routed_ffn(x, lp, MODEL)
        return jnp.sum(out * ct) + (0.3 * term if with_balance else 0.0), out

    (_, want_out), want = jax.value_and_grad(functools.partial(reference_loss, with_balance=True), has_aux=True)(lp)
    router_of_output = jax.grad(lambda lp: reference_loss(lp, False)[0])(lp)["router"]
    out_sum, router_sum = jnp.zeros_like(x), jnp.zeros_like(lp["router"])
    for first in range(0, 8, 2):
        cfg = TransformerConfig(**{**CFG.__dict__, "first_expert": first})
        mine = {**lp, **{k: lp[k][first:first + 2] for k in transformer.HELD_EXPERT_WEIGHTS}}

        def share_loss(mine):
            out, aux = _held_experts_ffn(x, mine, cfg, balance=True)
            return jnp.sum(out * ct), (out, aux[0])

        (_, (out, term)), grads = jax.value_and_grad(share_loss, has_aux=True)(mine)
        out_sum, router_sum = out_sum + out, router_sum + grads["router"]
        for name in transformer.HELD_EXPERT_WEIGHTS:
            assert _rel(grads[name], want[name][first:first + 2]) < 2e-5, (first, name)
        assert float(term) == pytest.approx(float(ref.routed_ffn(x, lp, MODEL)[1]), rel=1e-5)  # whole on every chip
    assert _rel(out_sum, want_out) < 2e-5
    assert _rel(router_sum, router_of_output) < 2e-5


# ---------------------------------------------------------------------------
# the benchmark's copy of the reference, its key mapping and its counts
# ---------------------------------------------------------------------------

def _bench():
    spec = importlib.util.spec_from_file_location(
        "bench_mellum2_under_test", os.path.join(ROOT, "benchmarks", "architectures", "mellum2.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    with open(os.path.join(ROOT, "benchmarks", "configs", "mellum2-12b-a2.5b-ep4-l4.json")) as f:
        return mod, json.load(f)


def test_the_benchmarks_copy_and_the_repos_reference_give_equal_losses():
    bench, _ = _bench()
    model = {**MODEL, "num_hidden_layers": 4, "num_experts": CFG.experts_held, "first_expert": CFG.first_expert}
    params, batch = _params(), _batch()
    want = float(ref.packed_loss(params, batch, MODEL, HELD))
    assert float(bench.packed_loss(params, batch, model)) == pytest.approx(want, rel=1e-6)
    tokens = batch["tokens"][:, :-1]
    np.testing.assert_allclose(bench.logits(params, tokens, model, batch["segment_ids"][:, :-1],
                                            batch["positions"][:, :-1]),
                               ref.logits(params, tokens, MODEL, HELD, batch["segment_ids"][:, :-1],
                                          batch["positions"][:, :-1])[0], atol=2e-5)


def test_the_key_mapping_builds_this_configuration():
    bench, config = _bench()
    cfg = TransformerConfig(**bench.transformer_kwargs(config))
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.kv_heads, cfg.head_dim) == (4, 2304, 32, 4, 128)
    assert (cfg.n_experts, cfg.expert_top_k, cfg.experts_held, cfg.first_expert, cfg.expert_d_ff) == (64, 8, 16, 0, 896)
    assert cfg.router_score == "softmax" and cfg.router_aux_coef == 0.001 and cfg.vocab_size == 24576
    sliding, full = cfg.period[0], cfg.period[3]
    assert [k.name for k in cfg.period] == ["sliding_attention"] * 3 + ["full_attention"]
    assert (sliding.window, sliding.plain_rope, sliding.rope_theta) == (1024, True, 5e5)
    assert (full.window, full.yarn_factor, full.yarn_original_len, full.attention_factor) == (
        0, 16.0, 8192, 1.2772588722239782)
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)) == 595_153_152 == bench.param_counts(config)["total"]
    assert shapes["kind_layers"]["sliding_attention"]["w_gate"].shape == (3, 16, 2304, 896)


def test_the_key_mapping_refuses_a_program_whose_grouped_matmul_has_no_backward(monkeypatch):
    bench, config = _bench()
    monkeypatch.delattr(grouped_matmul, "expert_tgmm")
    with pytest.raises(SystemExit, match="no backward pass"):
        bench.transformer_kwargs(config)


def test_the_toy_widths_keep_a_window_that_a_rehearsals_documents_pass():
    bench, config = _bench()
    bench.shrink(config)
    cfg = TransformerConfig(**bench.transformer_kwargs(config))
    assert cfg.period[0].window == 32 and cfg.experts_held == 4 and cfg.n_experts == 16 and cfg.expert_top_k == 4


# ---------------------------------------------------------------------------
# the new kernel bodies stay small (ROADMAP S12)
# ---------------------------------------------------------------------------

def _bodies(jaxpr, found):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found[eqn.params["name"]] = len(eqn.params["jaxpr"].eqns)
        for value in eqn.params.values():
            for inner in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(inner, "jaxpr", inner)
                if hasattr(inner, "eqns") and eqn.primitive.name != "pallas_call":
                    _bodies(inner, found)
    return found


def test_the_grouped_matmuls_three_bodies_stay_small():
    """What a warm start traces and lowers again in every program that holds them: 12 equations forward and
    through the transposed block, 27 for the sum an expert (its two ends are scalar reads and compares; no
    division anywhere). The limits are what was read here and a few more."""
    plan, x, w, tm = _grouped_case()
    ct = jnp.ones((x.shape[0], 256), jnp.float32)

    def loss(x, w):
        return jnp.sum(expert_gmm(x, w, jnp.int32(1), plan.tile_expert, plan.n_tiles, tm=tm, interpret=True) * ct)

    found = _bodies(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, w).jaxpr, {})
    assert set(found) == {"expert_gmm", "expert_gmm_dx", "expert_tgmm"}
    limits = {"expert_gmm": 16, "expert_gmm_dx": 16, "expert_tgmm": 34}
    for name, n in found.items():
        assert n <= limits[name], (name, n)
