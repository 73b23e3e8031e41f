"""Flash attention for TPU: Pallas forward + backward kernels.

Memory-bound op #1 in the transformer. The kernel streams K/V blocks through
VMEM with an online-softmax accumulator so the S×S score matrix never touches
HBM (HBM traffic O(S·D) instead of O(S²)). Forward saves the per-row
log-sum-exp so the backward pass recomputes probabilities blockwise.

Native GQA: K/V carry their own (smaller) head count — the q-head grid maps
onto kv heads through the BlockSpec index maps (q head h reads kv head
h // group), so grouped K/V are NEVER materialized at full head count (the
whole point of GQA is the smaller KV HBM footprint; a jnp.repeat would throw
it away). The dk/dv backward iterates the q-heads of each group in its inner
grid axis, accumulating into one kv-head scratch.

Packed sequences: optional ``segment_ids`` [B, S] adds a block-wise
same-segment mask (rows attend only within their segment), composed with the
causal mask — the standard packed-example training contract.

Layout: kernels operate on [B*H, S, D] for Q (and [B*KV, S, D] for K/V);
blocks are (block_q × D)/(block_k × D) with D a lane multiple. Grid iteration
puts the reduction axis innermost ("arbitrary") so f32 accumulators live in
VMEM scratch across steps (pallas_guide.md: Grid and Block Specifications).

The reference framework has no attention kernels (compute is delegated to
torch/vLLM, SURVEY.md §2.4); functional parity target is the standard flash
attention contract (causal MHA/GQA with LSE residuals + segment masking).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 512
NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Reference implementation (numerical oracle + non-TPU backends)
# ---------------------------------------------------------------------------

def mha_reference(q, k, v, causal=True, scale=None, segment_ids=None, window=0):
    """q: [B, S, H, D]; k,v: [B, S, KV, D] (KV divides H) -> [B, S, H, D].
    Softmax in f32. segment_ids: optional [B, S] int; attention is masked to
    same-segment pairs (packed sequences). window (causal only): row i sees
    columns i - window < j <= i; 0 is no window."""
    *_, H, D = q.shape
    KV = k.shape[2]
    if KV != H:
        rep = H // KV
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    S_q, S_k = s.shape[-2], s.shape[-1]
    if causal:
        mask = jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q)
        if window:
            mask = mask & ~jnp.tril(jnp.ones((S_q, S_k), bool), k=S_k - S_q - window)
        s = jnp.where(mask, s, NEG_INF)
    if segment_ids is not None:
        seg = (segment_ids[:, None, :, None] == segment_ids[:, None, None, :])
        s = jnp.where(seg, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def flash_supported(seq_len: int) -> bool:
    """Whether ``flash_attention`` can run compiled on this process's backend
    at this sequence length — what an "auto" caller observes to choose
    between the kernel and ``mha_reference``."""
    return jax.default_backend() == "tpu" and seq_len % 128 == 0


def _mask_scores(s, q_start, k_start, causal, seg_q, seg_k, window=0):
    """Apply causal (inside `window` columns where there is one) + segment
    masks to a [bq, bk] score block."""
    if causal:
        rows = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        seen = rows >= cols
        if window:
            seen = seen & (cols > rows - window)
        s = jnp.where(seen, s, NEG_INF)
    if seg_q is not None:
        s = jnp.where(seg_q[:, None] == seg_k[None, :], s, NEG_INF)
    return s


def _in_band(q_start, k_start, block_q, block_k, window):
    """Whether a [block_q, block_k] block of a causal score matrix holds a
    pair that is seen: not wholly above the diagonal nor, with a window,
    wholly below the band."""
    seen = k_start <= q_start + block_q - 1
    if window:
        seen = seen & (k_start + block_k - 1 > q_start - window)
    return seen


def band_blocks(S: int, block_q: int, block_k: int, window: int) -> int:
    """K blocks a q block of a windowed causal forward visits: the grid's
    innermost length. A q block's rows see columns q_start - window + 1 ..
    q_start + block_q - 1; the most k blocks those lie in, over the q blocks
    (2 at blocks of 512 and a window of 512, whatever S). Without a window,
    every k block."""
    if not window:
        return -(-S // block_k)
    return max((q_start + block_q - 1) // block_k - max(q_start - window + 1, 0) // block_k + 1
               for q_start in range(0, S, block_q))


def _first_band_block(qi, block_q, block_k, window):
    """The first k block a q block's band touches."""
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k


# ---------------------------------------------------------------------------
# Pallas forward
# ---------------------------------------------------------------------------

def _fwd_kernel(*refs, scale, block_q, block_k, n_k, causal, has_seg, window=0):
    """Grid (BH, q blocks, k steps). Without a window the k steps are the k
    blocks, those above the diagonal skipped by predicate. With one they are
    the `band_blocks` blocks from the band's first on: a block wholly outside
    the band is no grid step (a step past the diagonal, which the last q
    blocks of a short band have none of, is held at the diagonal's block by
    the index maps and skipped here)."""
    from jax.experimental import pallas as pl

    if has_seg:
        q_ref, k_ref, v_ref, sq_ref, sk_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = refs
        sq_ref = sk_ref = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:, :] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:, :] = jnp.zeros_like(l_scr)
        acc_scr[:, :] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = (ki + _first_band_block(qi, block_q, block_k, window) if window else ki) * block_k

    def _compute():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        seg_q = sq_ref[0, 0, :] if has_seg else None
        seg_k = sk_ref[0, 0, :] if has_seg else None
        s = _mask_scores(s, q_start, k_start, causal, seg_q, seg_k, window)
        m_prev = m_scr[:, 0]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_cur = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        acc_scr[:, :] = acc_scr[:, :] * alpha[:, None] + jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0, :, :], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_scr[:, :] = jnp.broadcast_to(m_cur[:, None], m_scr.shape)
        l_scr[:, :] = jnp.broadcast_to(l_cur[:, None], l_scr.shape)

    if causal:
        # Skip blocks strictly above the diagonal (a window's grid has none below its band).
        @pl.when(_in_band(q_start, k_start, block_q, block_k, 0))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        l = l_scr[:, 0]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, :, :] = (acc_scr[:, :] / l_safe[:, None]).astype(o_ref.dtype)
        lse = m_scr[:, 0] + jnp.log(l_safe)  # [bq]
        # lse is materialized as [BH, 8, S] (8 sublanes to satisfy the
        # (8, 128) min-tile rule); broadcast the row across sublanes.
        lse_ref[0, :, :] = jnp.broadcast_to(lse[None, :], lse_ref.shape[1:])


def _fwd_pallas(q, k, v, seg, *, causal, scale, block_q, block_k, group, H, interpret, window=0):
    """q: [BH, S, D]; k,v: [BKV, S, D]; seg: [B, 8, S] i32 or None
    -> (o [BH, S, D], lse [BH, S] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    BH, S, D = q.shape
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    n_q = pl.cdiv(S, block_q)
    n_k = band_blocks(S, block_q, block_k, window)  # the grid's k steps
    has_seg = seg is not None

    kernel = functools.partial(
        _fwd_kernel, scale=scale, block_q=block_q, block_k=block_k, n_k=n_k,
        causal=causal, has_seg=has_seg, window=window,
    )

    def k_block(qi, ki):
        """The k block of a grid step: the step itself, or with a window the
        band's first block plus the step, held at the diagonal's block."""
        if not window:
            return ki
        return jnp.minimum(_first_band_block(qi, block_q, block_k, window) + ki,
                           (qi * block_q + block_q - 1) // block_k)

    in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // group, k_block(qi, ki), 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // group, k_block(qi, ki), 0)),
    ]
    inputs = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b // H, 0, qi)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki: (b // H, 0, k_block(qi, ki))),
        ]
        inputs += [seg, seg]
    return pl.pallas_call(
        kernel,
        grid=(BH, n_q, n_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, 0, qi)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BH, S, D), q.dtype),
            jax.ShapeDtypeStruct((BH, 8, S), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_fwd",
    )(*inputs)


# ---------------------------------------------------------------------------
# Pallas backward (dk/dv kernel + dq kernel)
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(*refs, scale, block_q, block_k, n_q, group, causal, has_seg, window=0):
    """Grid: (B*KV, n_k, group*n_q) — the inner axis walks every (q-head of
    the group) × (q-block), accumulating this kv head's dk/dv in scratch."""
    from jax.experimental import pallas as pl

    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
    else:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
         dk_ref, dv_ref, dk_scr, dv_scr) = refs
        sq_ref = sk_ref = None

    ki = pl.program_id(1)
    t = pl.program_id(2)
    qi = t % n_q

    @pl.when(t == 0)
    def _init():
        dk_scr[:, :] = jnp.zeros_like(dk_scr)
        dv_scr[:, :] = jnp.zeros_like(dv_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        v = v_ref[0, :, :]
        do = do_ref[0, :, :]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        seg_q = sq_ref[0, 0, :] if has_seg else None
        seg_k = sk_ref[0, 0, :] if has_seg else None
        s = _mask_scores(s, q_start, k_start, causal, seg_q, seg_k, window)
        p = jnp.exp(s - lse[:, None])  # [bq, bk] f32
        # dv += p^T @ do
        dv_scr[:, :] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        # dp = do @ v^T ; ds = p * (dp - delta)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dk_scr[:, :] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(_in_band(q_start, k_start, block_q, block_k, window))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(t == group * n_q - 1)
    def _finalize():
        dk_ref[0, :, :] = dk_scr[:, :].astype(dk_ref.dtype)
        dv_ref[0, :, :] = dv_scr[:, :].astype(dv_ref.dtype)


def _bwd_dq_kernel(*refs, scale, block_q, block_k, n_k, causal, has_seg, window=0):
    from jax.experimental import pallas as pl

    if has_seg:
        (q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, sq_ref, sk_ref,
         dq_ref, dq_scr) = refs
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr = refs
        sq_ref = sk_ref = None

    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:, :] = jnp.zeros_like(dq_scr)

    q_start = qi * block_q
    k_start = ki * block_k

    def _compute():
        q = q_ref[0, :, :]
        k = k_ref[0, :, :]
        v = v_ref[0, :, :]
        do = do_ref[0, :, :]
        lse = lse_ref[0, 0, :]
        delta = delta_ref[0, 0, :]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        seg_q = sq_ref[0, 0, :] if has_seg else None
        seg_k = sk_ref[0, 0, :] if has_seg else None
        s = _mask_scores(s, q_start, k_start, causal, seg_q, seg_k, window)
        p = jnp.exp(s - lse[:, None])
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        ds = p * (dp - delta[:, None]) * scale
        dq_scr[:, :] += jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    if causal:
        @pl.when(_in_band(q_start, k_start, block_q, block_k, window))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(ki == n_k - 1)
    def _finalize():
        dq_ref[0, :, :] = dq_scr[:, :].astype(dq_ref.dtype)


def _bwd_pallas(res, g, *, causal, scale, block_q, block_k, group, H, KV, interpret, window=0):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    q, k, v, o, lse, seg = res
    do = g
    BH, S, D = q.shape
    BKV = k.shape[0]
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    n_q = pl.cdiv(S, block_q)
    n_k = pl.cdiv(S, block_k)
    has_seg = seg is not None

    delta_row = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    delta = jnp.broadcast_to(delta_row[:, None, :], (BH, 8, S))  # sublane-tiled like lse

    # dk/dv: grid over kv heads; inner axis covers (group member g, q block).
    # q-head for (kv-fold index b, inner step t): batch*H + kv*group + g.
    def qhead(b, t):
        return (b // KV) * H + (b % KV) * group + t // n_q

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, ki, t: (qhead(b, t), t % n_q, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, ki, t: (b, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, ki, t: (b, ki, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, ki, t: (qhead(b, t), t % n_q, 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, ki, t: (qhead(b, t), 0, t % n_q)),
        pl.BlockSpec((1, 8, block_q), lambda b, ki, t: (qhead(b, t), 0, t % n_q)),
    ]
    dkv_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        dkv_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, ki, t: (b // KV, 0, t % n_q)),
            pl.BlockSpec((1, 8, block_k), lambda b, ki, t: (b // KV, 0, ki)),
        ]
        dkv_inputs += [seg, seg]
    dkv = pl.pallas_call(
        functools.partial(
            _bwd_dkv_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_q=n_q, group=group, causal=causal, has_seg=has_seg, window=window,
        ),
        grid=(BKV, n_k, group * n_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda b, ki, t: (b, ki, 0)),
            pl.BlockSpec((1, block_k, D), lambda b, ki, t: (b, ki, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((BKV, S, D), k.dtype),
            jax.ShapeDtypeStruct((BKV, S, D), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_dkv",
    )(*dkv_inputs)
    dk, dv = dkv

    dq_in_specs = [
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // group, ki, 0)),
        pl.BlockSpec((1, block_k, D), lambda b, qi, ki: (b // group, ki, 0)),
        pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, 0, qi)),
        pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b, 0, qi)),
    ]
    dq_inputs = [q, k, v, do, lse, delta]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, 8, block_q), lambda b, qi, ki: (b // H, 0, qi)),
            pl.BlockSpec((1, 8, block_k), lambda b, qi, ki: (b // H, 0, ki)),
        ]
        dq_inputs += [seg, seg]
    dq = pl.pallas_call(
        functools.partial(
            _bwd_dq_kernel, scale=scale, block_q=block_q, block_k=block_k,
            n_k=n_k, causal=causal, has_seg=has_seg, window=window,
        ),
        grid=(BH, n_q, n_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, D), lambda b, qi, ki: (b, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((BH, S, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attn_dq",
    )(*dq_inputs)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with custom VJP
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10, 11, 12))
def _flash_folded(q, k, v, seg, causal, scale, block_q, block_k, group, H, KV, interpret, window):
    o, _ = _fwd_pallas(
        q, k, v, seg, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, group=group, H=H, interpret=interpret, window=window,
    )
    return o


def _flash_fwd(q, k, v, seg, causal, scale, block_q, block_k, group, H, KV, interpret, window):
    o, lse = _fwd_pallas(
        q, k, v, seg, causal=causal, scale=scale, block_q=block_q,
        block_k=block_k, group=group, H=H, interpret=interpret, window=window,
    )
    return o, (q, k, v, o, lse, seg)


def _flash_bwd(causal, scale, block_q, block_k, group, H, KV, interpret, window, res, g):
    dq, dk, dv = _bwd_pallas(
        res, g, causal=causal, scale=scale, block_q=block_q, block_k=block_k,
        group=group, H=H, KV=KV, interpret=interpret, window=window,
    )
    seg = res[5]
    dseg = None if seg is None else np.zeros(seg.shape, jax.dtypes.float0)
    return dq, dk, dv, dseg


_flash_folded.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=True, scale=None, segment_ids=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=False, window=0):
    """Flash attention. q: [B, S, H, D]; k,v: [B, S, KV, D] -> [B, S, H, D].

    ``window`` (causal only; 0 is none): row i sees columns i - window < j <=
    i. The forward visits the k blocks of a q block's band and no other
    (``band_blocks`` grid steps a q block, not S / block_k); the backward
    kernels walk every block and skip those outside the band by predicate.

    KV may be smaller than H (GQA): kv heads are shared across groups of
    H // KV query heads inside the kernel — no repeat/materialization.
    ``segment_ids`` [B, S] masks attention to same-segment pairs (packed
    sequences). These are the Pallas kernels: they run on a TPU backend, or
    anywhere with interpret=True (the CPU test path), and raise elsewhere —
    a caller that may land on another backend chooses ``mha_reference``
    itself from what it observes. S must be a multiple of 128 (callers pad);
    D should be a lane multiple (64/128/256).
    """
    B, S, H, D = q.shape
    KV = k.shape[2]
    if H % KV:
        raise ValueError(f"n_heads {H} not divisible by kv_heads {KV}")
    if S % 128:
        raise ValueError(f"flash_attention needs S % 128 == 0, got S={S}")
    if window and not causal:
        raise ValueError("flash_attention: a window is written for causal attention")
    if not interpret and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"flash_attention needs a TPU backend (or interpret=True); this "
            f"process runs on {jax.default_backend()!r}"
        )
    group = H // KV
    scale = scale if scale is not None else 1.0 / math.sqrt(D)
    # Blocks must divide S exactly: Pallas pads out-of-bounds block reads with
    # undefined data, and the non-causal path applies no mask that would
    # neutralize padded key columns. S is a multiple of 128 here, so halving
    # always converges to a divisor.
    while S % block_q:
        block_q //= 2
    while S % block_k:
        block_k //= 2
    fold = lambda x: x.transpose(0, 2, 1, 3).reshape(-1, S, D)  # [B,S,h,D] -> [B*h,S,D]
    seg = None
    if segment_ids is not None:
        seg = jnp.broadcast_to(
            segment_ids.astype(jnp.int32)[:, None, :], (B, 8, S)
        )  # sublane-tiled like lse
    o = _flash_folded(
        fold(q), fold(k), fold(v), seg, causal, scale, block_q, block_k,
        group, H, KV, interpret, int(window),
    )
    return o.reshape(B, H, S, D).transpose(0, 2, 1, 3)
