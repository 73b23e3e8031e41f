"""Pipeline parallelism: differentiable GPipe microbatch schedule over the
stage axis.

The reference has no native pipeline engine (PP degree is passed through to
vLLM — SURVEY.md §2.4); here PP is compiled: stage-stacked parameters are
sharded over the ``stage`` mesh axis, and a single shard_map program runs the
microbatch rotation with ``lax.ppermute`` moving activations to the next
stage over ICI. Total steps = n_micro + n_stages - 1 (fill + drain bubble);
everything is static-shape, so XLA overlaps each ppermute with the next
microbatch's compute (scaling-book pipelining recipe).

The schedule is written with ``lax.scan`` (not fori_loop) so it is
**reverse-mode differentiable**: ``jax.grad`` through ``pipeline_apply``
yields the backward pipeline automatically (AD transposes each ppermute into
the reverse ring hop), which fuses microbatch gradient accumulation into one
XLA program — the TPU-native equivalent of a hand-scheduled GPipe backward.
Set remat on the stage body (cfg.remat) to trade the per-step activation
stash for recompute.

Layout contract:
- ``stage_params``: pytree whose leaves have leading dim n_stages, sharded
  ``PartitionSpec("stage", ...)`` (the ShardingStrategy.pp() rule).
- ``x``: [n_micro, mb, ...] microbatched input; ``x_spec`` gives its
  PartitionSpec over the non-stage mesh axes (e.g. P(None, "data") to compose
  PP with data parallelism), default fully replicated.
- ``stage_fn(params_slice, h) -> h``: one stage's compute (params_slice has
  the leading stage dim dropped).
"""
from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax


def pipeline_apply(
    stage_fn: Callable,
    stage_params: Any,
    x: "jax.Array",
    *,
    mesh,
    axis_name: str = "stage",
    x_spec=None,
):
    """Run the staged computation; returns [n_micro, mb, ...] outputs."""
    from jax.sharding import PartitionSpec as P

    n_stages = mesh.shape[axis_name]
    if n_stages == 1:
        def apply_all(h):
            leaves = jax.tree.leaves(stage_params)
            L = leaves[0].shape[0]
            for i in range(L):
                h = stage_fn(jax.tree.map(lambda p: p[i], stage_params), h)
            return h

        return jax.vmap(apply_all)(x)

    n_micro = x.shape[0]
    if x_spec is None:
        x_spec = P()

    param_specs = jax.tree.map(lambda _: P(axis_name), stage_params)
    body = functools.partial(
        _pipeline_body,
        stage_fn=stage_fn,
        axis_name=axis_name,
        n_stages=n_stages,
        n_micro=n_micro,
    )
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(param_specs, x_spec),
        out_specs=x_spec,
        check_vma=False,
    )(stage_params, x)


def _pipeline_body(params, x, *, stage_fn, axis_name, n_stages, n_micro):
    """Per-stage body. params leaves: [stages_local, ...]; x: [n_micro, mb, ...]."""
    idx = lax.axis_index(axis_name)
    mb_shape = x.shape[1:]
    T = n_micro + n_stages - 1

    # If the mesh puts multiple layer-groups per stage device, apply each in
    # sequence inside the stage.
    def apply_stage(h):
        L_local = jax.tree.leaves(params)[0].shape[0]
        for i in range(L_local):
            h = stage_fn(jax.tree.map(lambda p: p[i], params), h)
        return h

    fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

    def step(carry, t):
        recv, outputs = carry
        # Stage 0 ingests microbatch t (repeats the last one once drained —
        # those outputs land outside [0, T) and are never selected, so they
        # contribute zero gradient); other stages take the activation
        # ppermuted from the previous stage.
        mb_idx = jnp.clip(t, 0, n_micro - 1)
        x_t = lax.dynamic_index_in_dim(x, mb_idx, axis=0, keepdims=False)
        h_in = jnp.where(idx == 0, x_t, recv)
        h_out = apply_stage(h_in)
        # Last stage writes its completed microbatch (valid when
        # 0 <= t - (n_stages-1) < n_micro).
        out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
        valid = (t >= n_stages - 1) & (idx == n_stages - 1)
        cur = lax.dynamic_index_in_dim(outputs, out_idx, axis=0, keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, jnp.where(valid, h_out, cur), out_idx, axis=0
        )
        recv = lax.ppermute(h_out, axis_name, fwd_perm)
        return (recv, outputs), None

    recv0 = jnp.zeros(mb_shape, x.dtype)
    out0 = jnp.zeros((n_micro,) + mb_shape, x.dtype)
    (_, outputs), _ = lax.scan(step, (recv0, out0), jnp.arange(T))
    # Only the last stage holds real outputs; broadcast them to all stages
    # (out_specs replicated over stage). psum with a one-hot mask avoids a
    # gather; its transpose under AD is the identity broadcast back.
    mask = (lax.axis_index(axis_name) == n_stages - 1).astype(outputs.dtype)
    return lax.psum(outputs * mask, axis_name)
