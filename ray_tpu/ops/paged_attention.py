"""Paged decode attention for TPU: single-token GQA queries against a
block-paged KV cache.

The serving engine's KV cache is a pool of fixed-size pages ([KV, P_total,
page_size, D]); each sequence owns a page list (its page table row). Decode
attention must therefore gather a sequence's keys from non-contiguous pages.
An XLA gather would materialize the whole per-sequence KV every step (HBM
copy of the entire working set per token); the Pallas kernel instead walks
the page table through scalar prefetch — the BlockSpec index map reads the
NEXT page index while the current page is in flight, so pages stream through
VMEM exactly once with no materialized gather.

Kernel shape: grid (B, KV, pages_per_seq), online-softmax accumulator in VMEM
scratch across the page axis (innermost, "arbitrary"), pages past a
sequence's length predicated off entirely (their DMAs still target a valid
page — dead table entries point at page 0 — but compute is skipped).

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

def paged_attention_reference(q, k_pages, v_pages, lengths, page_indices, scale=None):
    """q: [B, H, D]; k_pages/v_pages: [KV, P_total, ps, D]; lengths: [B]
    (valid token count per sequence, INCLUDING the current position);
    page_indices: [B, pages_per_seq] -> [B, H, D]."""
    B, H, D = q.shape
    KV, _, ps, _ = k_pages.shape
    group = H // KV
    ppseq = page_indices.shape[1]
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # [KV, B, ppseq, ps, D] -> [B, KV, S_virt, D]
    k = k_pages[:, page_indices].transpose(1, 0, 2, 3, 4).reshape(B, KV, ppseq * ps, D)
    v = v_pages[:, page_indices].transpose(1, 0, 2, 3, 4).reshape(B, KV, ppseq * ps, D)
    qg = q.reshape(B, KV, group, D)
    s = jnp.einsum("bkgd,bksd->bkgs", qg, k).astype(jnp.float32) * scale
    valid = (jnp.arange(ppseq * ps)[None, :] < lengths[:, None])[:, None, None, :]
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    o = jnp.einsum("bkgs,bksd->bkgd", p, v)
    return o.reshape(B, H, D)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _paged_kernel(lens_ref, pidx_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale, ps, n_pages, kv):
    """Grid (B, n_pages): ONE page DMA carries ALL kv heads (page ids are
    shared across heads in the pool layout), and the head loop unrolls
    statically inside the step — 4-8x fewer, larger DMAs than a per-head
    grid, which is what the decode path's throughput is bound by."""
    from jax.experimental import pallas as pl

    b = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = lens_ref[b]
    start = j * ps

    @pl.when(start < length)
    def _compute():
        for h in range(kv):  # static unroll: kv is small (2-8)
            q = q_ref[0, h]  # [Gp, D]
            k = k_ref[h, 0]  # [ps, D]
            s = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
            ) * scale  # [Gp, ps]
            cols = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(cols < length, s, NEG_INF)
            m_prev = m_scr[h, :, 0]
            m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
            alpha = jnp.exp(m_prev - m_cur)
            p = jnp.exp(s - m_cur[:, None])
            l_cur = l_scr[h, :, 0] * alpha + jnp.sum(p, axis=1)
            acc_scr[h] = acc_scr[h] * alpha[:, None] + jax.lax.dot_general(
                p.astype(v_ref.dtype), v_ref[h, 0], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            m_scr[h] = jnp.broadcast_to(m_cur[:, None], m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_cur[:, None], l_scr.shape[1:])

    @pl.when(j == n_pages - 1)
    def _finalize():
        for h in range(kv):
            l = l_scr[h, :, 0]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, h] = (acc_scr[h] / l_safe[:, None]).astype(o_ref.dtype)


def _paged_pallas(q, k_pages, v_pages, lengths, page_indices, *, scale, interpret):
    """q: [B, KV, Gp, D] (Gp >= 8, sublane-padded); -> o [B, KV, Gp, D]."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    B, KV, Gp, D = q.shape
    ps = k_pages.shape[2]
    n_pages = page_indices.shape[1]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, n_pages),
        in_specs=[
            pl.BlockSpec((1, KV, Gp, D), lambda b, j, lens, pidx: (b, 0, 0, 0)),
            pl.BlockSpec((KV, 1, ps, D), lambda b, j, lens, pidx: (0, pidx[b, j], 0, 0)),
            pl.BlockSpec((KV, 1, ps, D), lambda b, j, lens, pidx: (0, pidx[b, j], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KV, Gp, D), lambda b, j, lens, pidx: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, 128), jnp.float32),
            pltpu.VMEM((KV, Gp, D), jnp.float32),
        ],
    )
    kernel = functools.partial(
        _paged_kernel, scale=scale, ps=ps, n_pages=n_pages, kv=KV
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KV, Gp, D), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(lengths, page_indices, q, k_pages, v_pages)


def paged_attention(q, k_pages, v_pages, lengths, page_indices, scale=None,
                    interpret=False, mesh=None, head_axis="tensor"):
    """Paged decode attention. q: [B, H, D] (one query token per sequence);
    k_pages/v_pages: [KV, P_total, page_size, D]; lengths: [B] valid tokens
    per sequence including the current one; page_indices: [B, pages_per_seq]
    (entries past a sequence's length must still be valid page ids — use 0).

    This is the Pallas kernel: it runs on a TPU backend, or anywhere with
    interpret=True, and raises elsewhere — a caller that may land on another
    backend chooses ``paged_attention_reference`` from what it observes.

    mesh: tensor-parallel serving (llm/engine.py) — the head axes (H of q, KV
    of the page pools) are sharded over ``mesh[head_axis]`` and the kernel is
    shard_map'd: each device attends its own head shard against its own KV
    pool shard (embarrassingly parallel — GQA groups never straddle shards
    because callers validate KV % degree == 0). Without the explicit map jax
    refuses to lower the call: GSPMD cannot partition a Mosaic kernel.
    """
    if mesh is not None and mesh.shape.get(head_axis, 1) > 1:
        from jax.sharding import PartitionSpec as P

        inner = functools.partial(paged_attention, scale=scale, interpret=interpret)
        return jax.shard_map(
            inner,
            mesh=mesh,
            in_specs=(
                P(None, head_axis, None),
                P(head_axis, None, None, None),
                P(head_axis, None, None, None),
                P(None),
                P(None, None),
            ),
            out_specs=P(None, head_axis, None),
            check_vma=False,
        )(q, k_pages, v_pages, lengths, page_indices)
    B, H, D = q.shape
    KV = k_pages.shape[0]
    if H % KV:
        raise ValueError(f"n_heads {H} not divisible by kv_heads {KV}")
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"paged_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    # Sublane-pad the group axis up to 8, the rows of the kernel's f32 score
    # and accumulator tiles. q itself may be bf16 (tile 16 rows): Mosaic
    # compiles the 8-row block as is (chip_smoke.py checks it on the chip).
    Gp = max(8, group)
    qg = q.reshape(B, KV, group, D)
    if Gp != group:
        qg = jnp.pad(qg, ((0, 0), (0, 0), (0, Gp - group), (0, 0)))
    o = _paged_pallas(
        qg, k_pages, v_pages, lengths.astype(jnp.int32),
        page_indices.astype(jnp.int32), scale=scale, interpret=interpret,
    )
    return o[:, :, :group].reshape(B, H, D)
