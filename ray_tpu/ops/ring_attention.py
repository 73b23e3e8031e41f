"""Ring attention: exact causal attention over a sequence-sharded mesh axis.

Long-context is first-class here (the reference has NO context/sequence
parallelism anywhere — verified by repo-wide grep, SURVEY.md §5): the sequence
dim of Q/K/V lives sharded on the ``seq`` mesh axis, and K/V chunks rotate
around the ring with ``lax.ppermute`` while each device folds every chunk into
a flash-style online-softmax accumulator. Peak memory per device is
O(S_local·D); the S×S score matrix never exists, globally or locally.

The ring rides ICI neighbours (the ``seq`` axis is inner in
ray_tpu.parallel.mesh.AXIS_ORDER) and XLA overlaps each ppermute with the
current chunk's compute — the standard TPU ring-collective schedule
(pallas_guide.md "Patterns: Ring Collectives").

Causality across chunks: device i's queries attend fully to chunks from
devices < i, causally to its own chunk, not at all to chunks > i. All three
cases fall out of one global-position mask, so the loop body stays a single
compiled block (no data-dependent control flow).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30


def _ring_body(q, k, v, *, axis_name: str, causal: bool, scale: float, n_ring: int):
    """Per-shard body. q,k,v: [B, S_loc, H, D] local chunks."""
    B, S_loc, H, D = q.shape
    my = lax.axis_index(axis_name)

    qf = q.astype(jnp.float32)
    m = jnp.full((B, H, S_loc), NEG_INF, jnp.float32)
    l = jnp.zeros((B, H, S_loc), jnp.float32)
    acc = jnp.zeros((B, S_loc, H, D), jnp.float32)

    perm = [(j, (j + 1) % n_ring) for j in range(n_ring)]

    def step(t, carry):
        k_cur, v_cur, m, l, acc = carry
        src = (my - t) % n_ring  # which device's chunk we hold at step t
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, k_cur.astype(jnp.float32)) * scale
        if causal:
            q_pos = my * S_loc + lax.broadcasted_iota(jnp.int32, (S_loc, S_loc), 0)
            k_pos = src * S_loc + lax.broadcasted_iota(jnp.int32, (S_loc, S_loc), 1)
            s = jnp.where((q_pos >= k_pos)[None, None], s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[..., None])  # [B,H,q,k]
        l = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p, v_cur.astype(jnp.float32))
        acc = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return k_nxt, v_nxt, m_new, l, acc

    carry = (k, v, m, l, acc)
    for t in range(n_ring):  # static trip count: unrolled, ppermute overlaps
        carry = step(t, carry)
    _, _, m, l, acc = carry
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(
    q,
    k,
    v,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
    mesh=None,
):
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    q,k,v: *global* [B, S, H, D] arrays (S divisible by the axis size);
    call under jit within a mesh context. Falls back to the dense reference
    when the axis is absent or trivial.
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.parallel.sharding import _ambient_mesh

    *_, D = q.shape
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    mesh = mesh or _ambient_mesh()
    if mesh is None or axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        return mha_reference(q, k, v, causal=causal, scale=scale)
    n_ring = mesh.shape[axis_name]
    spec = P(None, axis_name, None, None)

    import functools

    body = functools.partial(
        _ring_body, axis_name=axis_name, causal=causal, scale=scale, n_ring=n_ring
    )
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
