"""Tests: mesh/sharding strategies + flagship transformer on an 8-device CPU
mesh (fake-topology technique, SURVEY.md §4)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import TransformerConfig, cross_entropy_loss, make_train_step
from ray_tpu.models.transformer import forward, init_params, param_logical_axes
from ray_tpu.ops.attention import mha_reference
from ray_tpu.parallel import (
    MeshSpec,
    ShardingStrategy,
    logical_sharding,
    shard_pytree,
)
from ray_tpu.parallel.sharding import use_strategy

CFG = TransformerConfig(
    vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
    max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
)


def test_mesh_spec_infers_axis():
    spec = MeshSpec(data=-1, tensor=2)
    sizes = spec.resolved_sizes(8)
    assert sizes["data"] == 4 and sizes["tensor"] == 2


def test_mesh_spec_rejects_bad_product():
    with pytest.raises(ValueError):
        MeshSpec(data=3, tensor=2).resolved_sizes(8)


def test_mesh_build_8_devices():
    mesh = MeshSpec(data=-1, tensor=2).build()
    assert mesh.shape["tensor"] == 2
    assert np.prod(list(mesh.shape.values())) == 8


def test_strategy_specs():
    from jax.sharding import PartitionSpec as P

    tp = ShardingStrategy.tp()
    assert tp.spec(("embed", "mlp")) == P(None, "tensor")
    fsdp_tp = ShardingStrategy.fsdp() | ShardingStrategy.tp()
    assert fsdp_tp.spec(("embed", "heads", "head_dim")) == P("fsdp", "tensor", None)
    # duplicate mesh axis within one spec is dropped (used once)
    assert fsdp_tp.spec(("mlp", "heads")) == P("tensor", None)
    # batch over combined axes
    assert fsdp_tp.spec(("batch", "seq")) == P(("replica", "data", "fsdp"), None)


def test_strategy_named_composition():
    s = ShardingStrategy.named("fsdp+tp+sp")
    assert s.rules["seq"] == "seq"
    assert s.rules["mlp"] == "tensor"


def test_forward_shapes_and_loss():
    params = init_params(jax.random.PRNGKey(0), CFG)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, CFG.vocab_size)
    logits, aux = forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    loss = cross_entropy_loss(params, {"tokens": tokens}, CFG)
    assert jnp.isfinite(loss)
    # random init ≈ uniform over vocab
    assert abs(float(loss) - np.log(CFG.vocab_size)) < 1.5


def test_train_step_reduces_loss():
    init_state, train_step, _ = make_train_step(CFG)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, CFG.vocab_size)
    step = jax.jit(train_step)
    losses = []
    for _ in range(30):
        state, m = step(state, {"tokens": tokens})
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses[:3] + losses[-3:]


def test_sharded_train_step_matches_single_device():
    """DP+TP sharded step must match unsharded numerics."""
    mesh = MeshSpec(data=2, tensor=4).build()
    strategy = ShardingStrategy.dp() | ShardingStrategy.tp()
    init_state, train_step, state_axes = make_train_step(CFG)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, CFG.vocab_size)

    _, m_ref = jax.jit(train_step)(state, {"tokens": tokens})

    axes = state_axes(state)
    with use_strategy(strategy), mesh:
        st = shard_pytree(state, axes, mesh, strategy)
        state_sh = logical_sharding(mesh, strategy, axes)
        batch_sh = strategy.sharding(mesh, ("batch", "seq"))
        data = {"tokens": jax.device_put(tokens, batch_sh)}
        step = jax.jit(
            train_step,
            in_shardings=(state_sh, {"tokens": batch_sh}),
            out_shardings=(state_sh, None),
        )
        _, m_sharded = step(st, data)
    np.testing.assert_allclose(
        float(m_ref["loss"]), float(m_sharded["loss"]), rtol=2e-4
    )


def test_fsdp_actually_shards_params():
    mesh = MeshSpec(fsdp=8).build()
    strategy = ShardingStrategy.fsdp()
    params = init_params(jax.random.PRNGKey(0), CFG)
    axes = param_logical_axes(CFG)
    sharded = shard_pytree(params, axes, mesh, strategy)
    # wq [L, D(embed), H, hd] sharded on dim 1 across 8 devices
    shards = sharded["layers"]["wq"].addressable_shards
    assert len(shards) == 8
    assert shards[0].data.shape[1] == CFG.d_model // 8


def test_moe_forward():
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        n_experts=4, expert_top_k=2, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference",
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    logits, aux = forward(params, tokens, cfg)
    assert logits.shape == (2, 16, cfg.vocab_size)
    assert jnp.isfinite(aux) and float(aux) > 0


def test_ep_shards_experts_and_matches_unsharded():
    """Expert parallelism END-TO-END on the 8-device mesh: ep()|fsdp()
    partitions the expert dim of every expert weight, top-k routed dispatch
    runs sharded, and a sharded train step's loss equals the unsharded twin
    (same init key, same batch) — GSPMD must not change the math."""
    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        n_experts=4, expert_top_k=2, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference",
    )
    init_state, train_step, state_axes = make_train_step(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (8, 33), 0, cfg.vocab_size)

    mesh = MeshSpec(data=-1, fsdp=2, expert=2).build()
    strategy = ShardingStrategy.ep() | ShardingStrategy.fsdp()
    with use_strategy(strategy), mesh:
        st = init_state(jax.random.PRNGKey(0))
        axes = state_axes(st)
        st = shard_pytree(st, axes, mesh, strategy)
        # Expert weights [L, E, D, F] really partitioned: E over expert (2),
        # D over fsdp (2).
        for name in ("w_gate", "w_up", "w_down"):
            shard = st["params"]["layers"][name].addressable_shards[0].data
            full = st["params"]["layers"][name].shape
            assert shard.shape[1] == cfg.n_experts // 2, (name, shard.shape, full)
        assert st["params"]["layers"]["w_gate"].addressable_shards[0].data.shape[2] \
            == cfg.d_model // 2  # fsdp composes on embed
        st_sh = logical_sharding(mesh, strategy, axes)
        b_sh = strategy.sharding(mesh, ("batch", "seq"))
        batch = {"tokens": jax.device_put(tokens, b_sh)}
        step = jax.jit(train_step, in_shardings=(st_sh, {"tokens": b_sh}),
                       out_shardings=(st_sh, None))
        _, m1 = step(st, batch)
        sharded_loss = float(m1["loss"])

    ref_mesh = MeshSpec(data=-1).build(jax.devices()[:1])
    ref = ShardingStrategy.dp()
    with use_strategy(ref), ref_mesh:
        st = init_state(jax.random.PRNGKey(0))
        axes = state_axes(st)
        st = shard_pytree(st, axes, ref_mesh, ref)
        st_sh = logical_sharding(ref_mesh, ref, axes)
        b_sh = ref.sharding(ref_mesh, ("batch", "seq"))
        batch = {"tokens": jax.device_put(tokens, b_sh)}
        step = jax.jit(train_step, in_shardings=(st_sh, {"tokens": b_sh}),
                       out_shardings=(st_sh, None))
        _, mr = step(st, batch)
        ref_loss = float(mr["loss"])
    np.testing.assert_allclose(sharded_loss, ref_loss, rtol=2e-3)


def test_moe_topk_routing_actually_routes():
    """_moe_ffn's dispatch really routes token s to expert s (hand-crafted
    router): zeroing ONE expert's down-projection changes exactly the tokens
    routed to it and no others."""
    from ray_tpu.models.transformer import _moe_ffn

    cfg = TransformerConfig(
        vocab_size=128, d_model=8, n_layers=1, n_heads=2, d_ff=16,
        n_experts=4, expert_top_k=1, max_seq_len=64, dtype=jnp.float32,
        attention_impl="reference",
    )
    E, D, F = cfg.n_experts, cfg.d_model, cfg.d_ff
    key = jax.random.PRNGKey(0)
    lp = {
        # Router: dim d votes for expert d (d < E) with a huge margin, so
        # one-hot input e_s routes deterministically to expert s.
        "router": jnp.eye(D, E) * 50.0,
        "w_gate": jax.random.normal(key, (E, D, F)) * 0.5,
        "w_up": jax.random.normal(jax.random.PRNGKey(1), (E, D, F)) * 0.5,
        "w_down": jax.random.normal(jax.random.PRNGKey(2), (E, F, D)) * 0.5,
    }
    x = jnp.eye(4, D)[None]  # [1, 4, D]: token s = e_s -> expert s
    out, aux = _moe_ffn(x, lp, cfg)
    assert jnp.isfinite(aux)
    lp_cut = dict(lp, w_down=lp["w_down"].at[2].set(0.0))
    out_cut, _ = _moe_ffn(x, lp_cut, cfg)
    changed = np.asarray(jnp.abs(out - out_cut).sum(-1)[0]) > 1e-6  # per token
    assert list(changed) == [False, False, True, False], changed
    # And expert 2's tokens now produce exactly zero (top_k=1: sole expert).
    np.testing.assert_allclose(np.asarray(out_cut[0, 2]), 0.0, atol=1e-6)


def test_attention_reference_causal():
    q = jax.random.normal(jax.random.PRNGKey(0), (1, 8, 2, 16))
    k = jax.random.normal(jax.random.PRNGKey(1), (1, 8, 2, 16))
    v = jax.random.normal(jax.random.PRNGKey(2), (1, 8, 2, 16))
    o = mha_reference(q, k, v, causal=True)
    # first position attends only to itself
    o0 = mha_reference(q[:, :1], k[:, :1], v[:, :1], causal=True)
    np.testing.assert_allclose(o[:, 0], o0[:, 0], rtol=1e-5)


@pytest.mark.slow  # heavy battery; tier-1 budget (see CHANGES PR-13)
def test_graft_entry_contract():
    import os
    import sys
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import __graft_entry__ as ge

    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape[0] == 2
    ge.dryrun_multichip(8)


def test_ring_train_step_composes_with_sp():
    """FULL train step (fwd + bwd through the ppermute ring, WITH remat)
    using attention_impl='ring' on a seq-sharded mesh: loss and updated
    params must match the unsharded reference-attention step. This is the
    end-to-end CP composition — sp() shards activations' seq dim, ring
    attention provides full-sequence attention over the ring (VERDICT r4
    weak #3: the kernel existed but had never run inside a train step)."""
    import dataclasses

    cfg_ring = dataclasses.replace(
        CFG, attention_impl="ring", remat=True, n_kv_heads=2  # GQA: KV expand path
    )
    cfg_ref = dataclasses.replace(CFG, attention_impl="reference", n_kv_heads=2)

    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, CFG.vocab_size)
    init_ref, step_ref, _ = make_train_step(cfg_ref)
    state0 = init_ref(jax.random.PRNGKey(0))
    ref_state, m_ref = jax.jit(step_ref)(state0, {"tokens": tokens})

    init_ring, step_ring, state_axes = make_train_step(cfg_ring)
    mesh = MeshSpec(data=2, seq=4).build()
    strategy = ShardingStrategy.dp() | ShardingStrategy.sp()
    axes = state_axes(state0)
    with use_strategy(strategy), mesh:
        st = shard_pytree(init_ring(jax.random.PRNGKey(0)), axes, mesh, strategy)
        state_sh = logical_sharding(mesh, strategy, axes)
        # Tokens shard on batch only (S+1 isn't seq-divisible); the model's
        # logical constraints reshard activations onto the seq axis inside.
        batch_sh = strategy.sharding(mesh, ("batch", None))
        data = {"tokens": jax.device_put(tokens, batch_sh)}
        step = jax.jit(
            step_ring,
            in_shardings=(state_sh, {"tokens": batch_sh}),
            out_shardings=(state_sh, None),
        )
        new_state, m_ring = step(st, data)
        # Two consecutive steps: the bwd-through-ppermute gradients feed a
        # real optimizer update that the next fwd consumes.
        _, m_ring2 = step(new_state, data)
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_ring["loss"]), rtol=2e-4)
    np.testing.assert_allclose(
        float(m_ref["grad_norm"]), float(m_ring["grad_norm"]), rtol=2e-3
    )
    # Updated params match leaf-for-leaf (gradient parity, not just loss).
    np.testing.assert_allclose(
        np.asarray(jax.device_get(new_state["params"]["layers"]["wq"])),
        np.asarray(jax.device_get(ref_state["params"]["layers"]["wq"])),
        atol=2e-5, rtol=2e-4,
    )
    assert float(m_ring2["loss"]) < float(m_ring["loss"])  # learning continues


def test_ring_attention_matches_reference():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.ring_attention import ring_attention

    mesh = MeshSpec(seq=4, data=2).build()
    B, S, H, D = 2, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = [jax.random.normal(kk, (B, S, H, D)) for kk in ks]
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        qs, ks_, vs = jax.device_put(q, sh), jax.device_put(k, sh), jax.device_put(v, sh)
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, axis_name="seq"))(qs, ks_, vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ring_attention_noncausal():
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.ring_attention import ring_attention

    mesh = MeshSpec(seq=8).build()
    B, S, H, D = 1, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q, k, v = [jax.random.normal(kk, (B, S, H, D)) for kk in ks]
    ref = mha_reference(q, k, v, causal=False)
    with mesh:
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        args = [jax.device_put(x, sh) for x in (q, k, v)]
        out = jax.jit(lambda a, b, c: ring_attention(a, b, c, causal=False))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_pipeline_train_step_matches_sequential():
    """PP (4 stages) x DP (2): pipelined loss AND updated params must match
    the sequential step exactly (GPipe schedule is math-identical; VERDICT
    round-1 item 7)."""
    from ray_tpu.models import make_pipeline_train_step

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=4, n_heads=4, d_ff=128,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
    )
    mesh = MeshSpec(data=2, stage=4).build()
    init_state, seq_step, state_axes = make_train_step(cfg)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)

    ref_state, m_ref = jax.jit(seq_step)(state, {"tokens": tokens})

    _, pp_step, _ = make_pipeline_train_step(cfg, mesh, n_micro=4)
    strategy = ShardingStrategy.dp() | ShardingStrategy.pp()
    axes = state_axes(state)
    with mesh:
        st = shard_pytree(state, axes, mesh, strategy)
        state_sh = logical_sharding(mesh, strategy, axes)
        batch_sh = strategy.sharding(mesh, ("batch", "seq"))
        data = {"tokens": jax.device_put(tokens, batch_sh)}
        step = jax.jit(
            pp_step,
            in_shardings=(state_sh, {"tokens": batch_sh}),
            out_shardings=(state_sh, None),
        )
        new_state, m_pp = step(st, data)
        jax.block_until_ready(m_pp["loss"])
    np.testing.assert_allclose(float(m_ref["loss"]), float(m_pp["loss"]), rtol=2e-4)
    np.testing.assert_allclose(
        float(m_ref["grad_norm"]), float(m_pp["grad_norm"]), rtol=2e-3
    )
    # Parameter updates identical too (whole-state check, not just metrics).
    ref_leaf = ref_state["params"]["layers"]["wq"]
    pp_leaf = jax.device_get(new_state["params"]["layers"]["wq"])
    np.testing.assert_allclose(np.asarray(ref_leaf), pp_leaf, rtol=5e-3, atol=1e-5)


def test_pipeline_single_stage_fallback():
    """stage=1 mesh: pipeline path must degrade to the plain scan."""
    from ray_tpu.models import make_pipeline_train_step

    cfg = TransformerConfig(
        vocab_size=128, d_model=64, n_layers=2, n_heads=4, d_ff=128,
        max_seq_len=64, dtype=jnp.float32, attention_impl="reference",
    )
    mesh = MeshSpec(data=-1).build()
    init_state, pp_step, _ = make_pipeline_train_step(cfg, mesh, n_micro=2)
    state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 33), 0, cfg.vocab_size)
    with mesh:
        _, m = jax.jit(pp_step)(state, {"tokens": tokens})
    assert jnp.isfinite(m["loss"])


def test_ulysses_attention_matches_reference():
    """Ulysses all-to-all resharding: exact vs the dense oracle, causal."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = MeshSpec(seq=4, data=2).build()
    B, S, H, D = 2, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = [jax.random.normal(kk, (B, S, H, D)) for kk in ks]
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        args = [jax.device_put(x, sh) for x in (q, k, v)]
        out = jax.jit(lambda a, b, c: ulysses_attention(a, b, c))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_attention_gqa_and_segments():
    """Grouped KV heads stay grouped through the all_to_all; packed-sequence
    segment mask composes (segment ids all_gathered to full length)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = MeshSpec(seq=4).build(jax.devices()[:4])
    B, S, H, KV, D = 2, 32, 8, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, D))
    k = jax.random.normal(ks[1], (B, S, KV, D))
    v = jax.random.normal(ks[2], (B, S, KV, D))
    seg = jnp.concatenate(
        [jnp.zeros((B, S // 2), jnp.int32), jnp.ones((B, S - S // 2), jnp.int32)], axis=1
    )
    kr = jnp.repeat(k, H // KV, axis=2)
    vr = jnp.repeat(v, H // KV, axis=2)
    ref = mha_reference(q, kr, vr, causal=True, segment_ids=seg)
    with mesh:
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        seg_sh = NamedSharding(mesh, P(None, "seq"))
        qs, ks_, vs = (jax.device_put(x, s) for x, s in ((q, sh), (k, sh), (v, sh)))
        segs = jax.device_put(seg, seg_sh)
        out = jax.jit(
            lambda a, b, c, s: ulysses_attention(a, b, c, segment_ids=s)
        )(qs, ks_, vs, segs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_ulysses_attention_head_indivisible_falls_back_to_ring():
    """H < axis size: Ulysses can't shard heads; must still be exact (ring)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.ops.ulysses import ulysses_attention

    mesh = MeshSpec(seq=8).build()
    B, S, H, D = 1, 64, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    q, k, v = [jax.random.normal(kk, (B, S, H, D)) for kk in ks]
    ref = mha_reference(q, k, v, causal=True)
    with mesh:
        sh = NamedSharding(mesh, P(None, "seq", None, None))
        args = [jax.device_put(x, sh) for x in (q, k, v)]
        out = jax.jit(lambda a, b, c: ulysses_attention(a, b, c))(*args)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _autodiff_loss(params, batch, cfg):
    """What cross_entropy_loss was before the head made its own gradients:
    forward()'s whole [B, S, V] logits under _ce_from_logits, left to autodiff."""
    from ray_tpu.models.transformer import _ce_from_logits

    tokens, segs = batch["tokens"], batch.get("segment_ids")
    mask = None if batch.get("mask") is None else batch["mask"][:, 1:].astype(jnp.float32)
    if segs is not None:
        boundary = (segs[:, 1:] == segs[:, :-1]).astype(jnp.float32)
        mask = boundary if mask is None else mask * boundary
    logits, aux = forward(params, tokens[:, :-1], cfg, segment_ids=None if segs is None else segs[:, :-1])
    return _ce_from_logits(logits, tokens[:, 1:], mask) + 0.01 * aux


_HEAD_MODELS = {
    "dense": {},
    "tied": {"tie_embeddings": True},
    "divisor": {"logits_divisor": 3.0},
    "tied_divisor": {"tie_embeddings": True, "logits_divisor": 8.0},
    # At bfloat16 activations the logits' cotangent is rounded once here and
    # in two parts by autodiff: the gradients agree to bfloat16's precision.
    "bfloat16": {"dtype": jnp.bfloat16},
    "bfloat16_tied_divisor": {"dtype": jnp.bfloat16, "tie_embeddings": True, "logits_divisor": 8.0},
}
# A walk: (positions, ce_chunk, the chooser's budget as a share of the whole
# logits' bytes (None: the module's own), the chunks it then takes).
_HEAD_WALKS = {
    "one_chunk_chosen": (32, 0, None, 1),
    "two_chunks_chosen": (32, 0, 1 / 2, 2),
    "eight_chunks_chosen": (32, 0, 1 / 8, 8),
    "four_chunks_given": (32, 8, None, 4),
    "a_given_chunk_that_does_not_divide": (32, 5, None, 7),
    "a_prime_length_whose_last_chunk_is_short": (31, 0, 1 / 4, 4),
}
_HEAD_CASES = [("dense", kept, walk) for kept in ("all", "mask", "segment_ids", "mask_and_segment_ids") for walk in _HEAD_WALKS] + [
    ("tied", "mask", "one_chunk_chosen"), ("tied", "segment_ids", "two_chunks_chosen"), ("tied", "all", "a_given_chunk_that_does_not_divide"),
    ("divisor", "all", "eight_chunks_chosen"), ("divisor", "mask", "four_chunks_given"),
    ("tied_divisor", "segment_ids", "a_prime_length_whose_last_chunk_is_short"), ("tied_divisor", "mask", "eight_chunks_chosen"),
    ("bfloat16", "all", "two_chunks_chosen"), ("bfloat16", "mask_and_segment_ids", "four_chunks_given"),
    ("bfloat16_tied_divisor", "mask", "a_given_chunk_that_does_not_divide"), ("bfloat16_tied_divisor", "all", "eight_chunks_chosen"),
]


@pytest.mark.parametrize("model,kept,walk", _HEAD_CASES, ids=["-".join(c) for c in _HEAD_CASES])
def test_loss_head_matches_autodiff_of_the_whole_logits(model, kept, walk, monkeypatch):
    """The head that makes its gradients where it makes its logits
    (cross_entropy_loss -> head_loss) gives the loss AND every leaf's gradient
    that autodiff gives of forward() + _ce_from_logits, whatever the walk, and
    its primal alone the same loss."""
    import dataclasses

    from ray_tpu.models import transformer as T

    positions, ce_chunk, share, chunks = _HEAD_WALKS[walk]
    cfg = dataclasses.replace(CFG, n_layers=1, ce_chunk=ce_chunk, **_HEAD_MODELS[model])
    itemsize = jnp.dtype(cfg.dtype).itemsize
    if share is not None:
        monkeypatch.setattr(T, "_LOSS_HEAD_CHUNK_BYTES", int(3 * positions * cfg.vocab_size * itemsize * share))
    assert -(-positions // T._loss_head_chunk(3, positions, cfg.vocab_size, itemsize, ce_chunk)) == chunks
    params = init_params(jax.random.PRNGKey(0), cfg)
    shape = (3, positions + 1)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), shape, 0, 128)}
    if "mask" in kept:
        batch["mask"] = (jax.random.uniform(jax.random.PRNGKey(2), shape) > 0.3).astype(jnp.float32)
    if "segment_ids" in kept:
        batch["segment_ids"] = jnp.cumsum(jax.random.uniform(jax.random.PRNGKey(3), shape) > 0.8, axis=1).astype(jnp.int32)
    want, want_grads = jax.value_and_grad(_autodiff_loss)(params, batch, cfg)
    got, got_grads = jax.jit(jax.value_and_grad(lambda p, b: cross_entropy_loss(p, b, cfg)))(params, batch)
    exact = cfg.dtype == jnp.float32
    np.testing.assert_allclose(float(got), float(want), atol=2e-6 if exact else 1e-5)
    np.testing.assert_allclose(float(cross_entropy_loss(params, batch, cfg)), float(want), atol=2e-6 if exact else 1e-5)
    assert jax.tree.structure(got_grads) == jax.tree.structure(want_grads)
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got_grads), jax.tree.leaves(want_grads)):
        assert g.dtype == w.dtype and g.shape == w.shape, path
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), atol=1e-6 if exact else 1e-3, err_msg=str(path))


@pytest.mark.parametrize("B,S,V,itemsize,ce_chunk,chunk", [
    (3, 4096, 32768, 2, 0, 1024),  # the train cell's 805 MB of logits: 4 chunks of 201 MB (PERF.md section 6, PR 52)
    (1, 1024, 32768, 2, 0, 1024),  # its check's one row, 67 MB: whole
    (3, 4099, 32768, 2, 0, 820),  # a prime length, a little over 4 chunks' budget: 4 chunks of 820 and one of 819
    (3, 3000, 32768, 2, 0, 1000),  # 590 MB: 3 chunks
    (64, 4096, 131072, 4, 0, 128),  # 137 GB of float32 logits: the unrolled walk's most chunks, 32, of 4.3 GB each
    (3, 4096, 32768, 2, 1000, 1000),  # a given chunk as given, the last one of 96
    (3, 100, 32768, 2, 1000, 100),  # and no longer than the sequence
], ids=["the_train_cell", "its_check", "a_prime_length", "three_chunks", "the_most_chunks", "given", "given_over_the_length"])
def test_loss_head_reads_its_chunk_off_the_shapes(B, S, V, itemsize, ce_chunk, chunk):
    """ce_chunk 0: the fewest chunks whose logits fit the budget (no more than
    the walk may unroll), equal but for a last one that may be shorter; a
    given chunk as given."""
    from ray_tpu.models import transformer as T

    assert T._LOSS_HEAD_CHUNK_BYTES == 192 << 20
    assert T._loss_head_chunk(B, S, V, itemsize, ce_chunk) == chunk
    chunks = -(-S // chunk)
    if not ce_chunk and 1 < chunks < T._LOSS_HEAD_MAX_CHUNKS:  # they fit, and one chunk fewer would not
        assert B * chunk * V * itemsize <= T._LOSS_HEAD_CHUNK_BYTES < B * -(-S // (chunks - 1)) * V * itemsize
    assert chunks <= T._LOSS_HEAD_MAX_CHUNKS or ce_chunk


def _largest_with(jaxpr, width: int) -> int:
    """The most elements of any array with a dimension of `width` that an
    equation of a jaxpr makes, its inner jaxprs' equations too."""
    most = 0
    for eqn in jaxpr.eqns:
        most = max([most] + [int(np.prod(v.aval.shape)) for v in eqn.outvars if width in getattr(v.aval, "shape", ())])
        for inner in jax.core.jaxprs_in_params(eqn.params):
            most = max(most, _largest_with(inner, width))
    return most


@pytest.mark.parametrize("differentiated", [False, True], ids=["primal", "value_and_grad"])
def test_loss_head_never_builds_the_whole_logits(differentiated):
    """Neither the primal alone (a cell's check, evaluation) nor the
    differentiated loss holds anything of [B, S, V]: the largest array with
    the vocabulary's width that either makes is a chunk's logits, or the
    head's own gradient."""
    import dataclasses

    B, S, chunk = 4, 64, 8
    cfg = dataclasses.replace(CFG, n_layers=1, vocab_size=512, max_seq_len=S, ce_chunk=chunk)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch = {"tokens": jnp.zeros((B, S + 1), jnp.int32), "mask": jnp.ones((B, S + 1), jnp.float32)}

    def loss(p, b):
        return cross_entropy_loss(p, b, cfg)

    most = _largest_with(jax.make_jaxpr(jax.value_and_grad(loss) if differentiated else loss)(params, batch).jaxpr, cfg.vocab_size)
    assert most == max(B * chunk, cfg.d_model if differentiated else 0) * cfg.vocab_size < B * S * cfg.vocab_size
