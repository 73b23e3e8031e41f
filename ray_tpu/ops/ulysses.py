"""Ulysses (DeepSpeed-style) sequence parallelism: all-to-all head resharding.

The second first-class long-context strategy next to ring attention
(SURVEY.md §5 names both; the reference has neither — sequence scaling is
delegated to user frameworks). Where ring attention rotates K/V chunks around
the ``seq`` mesh axis, Ulysses re-shards: activations arrive sequence-sharded
[B, S/n, H, D], one ``all_to_all`` per tensor swaps the sharded dimension from
sequence to heads [B, S, H/n, D], each device runs *dense* (flash) attention
over the full sequence for its head group, and a final ``all_to_all`` restores
sequence sharding.

Trade-off vs the ring schedule: Ulysses moves Q, K, V and O once each
(4 tensors x (n-1)/n of their bytes) in two bursts, while the ring moves K and
V n-1 times in n overlappable steps. Ulysses wins when H >= n and the
per-device flash kernel is long enough to hide the bursts; the ring wins at
extreme S where even one full-sequence gather of scores' inputs is too big.
Both are exact (same oracle as ``mha_reference``).

The all_to_alls ride ICI: ``seq`` is an inner axis in
ray_tpu.parallel.mesh.AXIS_ORDER, so neighbours are ICI-adjacent.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _ulysses_body(q, k, v, seg, *, axis_name: str, causal: bool, scale: float):
    """Per-shard body. q: [B, S_loc, H, D]; k/v: [B, S_loc, KV, D];
    seg: [B, S_loc] or None."""
    from ray_tpu.ops.attention import flash_attention, flash_supported, mha_reference

    # Scatter heads, gather sequence: [B, S/n, H, D] -> [B, S, H/n, D].
    a2a = functools.partial(
        lax.all_to_all, axis_name=axis_name, split_axis=2, concat_axis=1, tiled=True
    )
    qg, kg, vg = a2a(q), a2a(k), a2a(v)
    seg_g = (
        lax.all_gather(seg, axis_name, axis=1, tiled=True) if seg is not None else None
    )
    local = flash_attention if flash_supported(qg.shape[1]) else mha_reference
    o = local(qg, kg, vg, causal=causal, scale=scale, segment_ids=seg_g)
    # Back: scatter sequence, gather heads: [B, S, H/n, D] -> [B, S/n, H, D].
    return lax.all_to_all(o, axis_name, split_axis=1, concat_axis=2, tiled=True)


def ulysses_attention(
    q,
    k,
    v,
    axis_name: str = "seq",
    causal: bool = True,
    scale: Optional[float] = None,
    mesh=None,
    segment_ids=None,
):
    """Exact attention with Q/K/V sequence-sharded over ``axis_name``.

    q: *global* [B, S, H, D]; k/v: [B, S, KV, D] (native GQA — KV heads are
    never repeated); segment_ids: optional [B, S] for packed sequences.
    Both H and KV must be divisible by the axis size (each device owns a
    whole head group); otherwise this falls back to ring attention, which has
    no head-count constraint. Call under jit within a mesh context.
    """
    from jax.sharding import PartitionSpec as P

    from ray_tpu.ops.attention import mha_reference
    from ray_tpu.parallel.sharding import _ambient_mesh

    *_, H, D = q.shape
    KV = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    mesh = mesh or _ambient_mesh()
    if mesh is None or axis_name not in mesh.shape or mesh.shape[axis_name] == 1:
        if KV != H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        return mha_reference(q, k, v, causal=causal, scale=scale, segment_ids=segment_ids)
    n = mesh.shape[axis_name]
    if H % n or KV % n:
        if KV != H:
            k = jnp.repeat(k, H // KV, axis=2)
            v = jnp.repeat(v, H // KV, axis=2)
        if segment_ids is not None:
            # Ring attention has no segment masking; dense reference is the
            # only exact packed-sequence fallback here (XLA inserts the
            # gathers). Head counts this small make dense affordable.
            return mha_reference(
                q, k, v, causal=causal, scale=scale, segment_ids=segment_ids
            )
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention(q, k, v, axis_name=axis_name, causal=causal, scale=scale, mesh=mesh)

    spec = P(None, axis_name, None, None)
    seg_spec = P(None, axis_name)
    body = functools.partial(
        _ulysses_body, axis_name=axis_name, causal=causal, scale=scale
    )
    if segment_ids is None:
        return jax.shard_map(
            lambda q, k, v: body(q, k, v, None),
            mesh=mesh,
            in_specs=(spec, spec, spec),
            out_specs=spec,
            check_vma=False,
        )(q, k, v)
    return jax.shard_map(
        body, mesh=mesh, in_specs=(spec, spec, spec, seg_spec), out_specs=spec,
        check_vma=False,
    )(q, k, v, segment_ids)
