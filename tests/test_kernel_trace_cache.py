"""A kernel's call is traced once a shape signature (ray_tpu/ops/__init__.py):
each of the eleven ``pl.pallas_call`` sites sits in a module-level
``jax.jit(..., inline=True)``, so JAX's trace cache serves every repeat inside
a program and across the programs of a process, and the caller's jaxpr is
what it was without the jit. Toy shapes, interpret mode, the CPU: what is
held is how often a kernel's BODY is traced (``pallas_call`` traces it at
every call it is asked to make) and that the lowered program did not change."""
import collections
import contextlib

import jax
import jax.numpy as jnp
import pytest
from jax._src.pallas import pallas_call as pallas_call_lib

from ray_tpu.ops import attention, grouped_matmul, latent_attention, linear_attention, paged_attention, ssd

F32, BF16 = jnp.float32, jnp.bfloat16
CALLS = 3  # calls a program: equal layers


@contextlib.contextmanager
def _bodies_traced():
    """Kernel name -> how often ``pallas_call`` traced a body under that name."""
    counts = collections.Counter()
    traced = pallas_call_lib._trace_kernel_to_jaxpr

    def counting(fun, debug_info, *args, **kw):
        counts[debug_info.func_name] += 1
        return traced(fun, debug_info, *args, **kw)

    pallas_call_lib._trace_kernel_to_jaxpr = counting
    try:
        yield counts
    finally:
        pallas_call_lib._trace_kernel_to_jaxpr = traced


# A site: the kernel's name, the module and the attributes that hold its jitted functions, operands(size) for two
# sizes, and call(operands, index): one of a program's calls, with whatever differs between equal layers made to
# differ by the index (the layer, the live slots, the live tiles, the lengths): a traced scalar as the engine's
# layer scan hands it over, or a Python int.
Site = collections.namedtuple("Site", "kernel module holders operands call")


def _flash_operands(size):
    S = 128 * size
    return jnp.zeros((1, S, 2, 64), F32), jnp.zeros((1, S, 1, 64), F32), jnp.zeros((1, S), jnp.int32)


def _flash(ops, index):
    q, kv, seg = ops
    return attention.flash_attention(q, kv, kv, segment_ids=seg, interpret=True)


def _flash_grads(ops, index):
    q, kv, seg = ops
    return jax.grad(lambda q, kv: jnp.sum(attention.flash_attention(q, kv, kv, segment_ids=seg, interpret=True)),
                    argnums=(0, 1))(q, kv)


def _live(slots, index):
    return jnp.arange(slots) <= index  # one more live slot a call


def _ssd_chunk_operands(size):
    B, S, H, P, N = 2, 150 * size, 4, 16, 32
    return jnp.zeros((B, S, H, P), F32), jnp.zeros((B, S, 1, N), F32), jnp.zeros((B, S, H), F32)


def _ssd_chunk(ops, index):
    x, bc, h = ops
    return ssd.ssd_chunk(x, bc, bc, h, h, interpret=True)


def _ssd_step_operands(size):
    slots, H, P, N = 2 * size, 4, 16, 32
    return (jnp.zeros((slots, H, P), F32), jnp.zeros((slots, 1, N), F32), jnp.zeros((slots, H), F32),
            jnp.zeros((CALLS, slots, N, H * P), F32))


def _ssd_step(ops, index):
    x, bc, h, pool = ops
    return ssd.ssd_step(x, bc, bc, h, h, pool, index, _live(x.shape[0], index), interpret=True)


def _kda_chunk_operands(size):
    seq = jnp.zeros((1, 70 * size, 4, 128), F32)
    return seq, seq.astype(BF16), jnp.zeros(seq.shape[:3], F32)


def _kda_chunk(ops, index):
    seq, v, beta = ops
    return linear_attention.kda_chunk(seq, seq, v, seq, beta, out_dtype=BF16, interpret=True)


def _kda_step_operands(size):
    slots, H, K = 2 * size, 4, 128
    return jnp.zeros((slots, H, K), F32), jnp.zeros((slots, H), F32), jnp.zeros((CALLS, slots, H, K, K), F32)


def _kda_step(ops, index):
    row, beta, pool = ops
    return linear_attention.kda_step(row, row, row, row, beta, pool, index, _live(row.shape[0], index), interpret=True)


def _prep_operands(size):
    return jnp.zeros((1, 3 + 64 * size, 3, 2, 128), BF16), jnp.zeros((4, 3, 2, 128), BF16)


def _delta_prep(ops, index):
    return linear_attention.delta_prep(*ops, 2, interpret=True)


def _gmm_operands(size):
    E, tm, D, N = 4, 8, 32, 48
    return jnp.zeros((6 * tm * size, D), F32), jnp.zeros((CALLS, E, D, N), F32), jnp.zeros(6 * size, jnp.int32)


def _gmm(ops, index):
    x, w, tile_expert = ops
    n_tiles = jnp.reshape(index + 1, 1).astype(jnp.int32)
    return grouped_matmul.expert_gmm(x, w, index, tile_expert, n_tiles, tm=8, interpret=True)


def _gmm_grads(ops, index):
    x, w, tile_expert = ops
    n_tiles = jnp.reshape(index + 1, 1).astype(jnp.int32)
    return jax.grad(lambda x, w: jnp.sum(grouped_matmul.expert_gmm(x, w, index, tile_expert, n_tiles, tm=8,
                                                                    interpret=True)), argnums=(0, 1))(x, w)


def _paged_operands(size, window=0):
    B, H, KV, D, ps, n_pages = 2 * size, 4, 2, 128, 16, 4
    pages = B * paged_attention.ring_pages(window, ps) if window else 1 + B * n_pages
    return (jnp.zeros((B, H, D), F32), jnp.zeros((B, KV, D), F32), jnp.zeros((CALLS, KV, pages, ps, D), F32),
            jnp.full(B, ps + 3, jnp.int32), jnp.zeros((B, n_pages), jnp.int32))


def _paged(window):
    def call(ops, index):
        q, new, pool, lengths, table = ops
        return paged_attention.paged_attention(q, new, new, pool, pool, lengths + index, table, index,
                                               interpret=True, window=window)
    return call


def _latent_operands(size):
    B, H, W, ps, n_pages = 2 * size, 8, 128, 16, 4
    return (jnp.zeros((B, H, W), F32), jnp.zeros((B, W), F32), jnp.zeros((CALLS, 1 + B * n_pages, ps, W), F32),
            jnp.full(B, ps + 3, jnp.int32), jnp.zeros((B, n_pages), jnp.int32))


def _latent(ops, index):
    q, row, pool, lengths, table = ops
    return latent_attention.latent_paged_attention(q, row, pool, lengths + index, table, index, v_width=64,
                                                   scale=0.17, interpret=True)


_FLASH = (attention, ("_fwd_pallas", "_bwd_pallas"), _flash_operands)
_GMM = (grouped_matmul, ("expert_gmm", "_gmm_call", "expert_tgmm"), _gmm_operands)
WINDOW = 24
SITES = [
    Site("flash_attn_fwd", *_FLASH, _flash),
    Site("flash_attn_dkv", *_FLASH, _flash_grads),
    Site("flash_attn_dq", *_FLASH, _flash_grads),
    Site("ssd_chunk", ssd, ("ssd_chunk",), _ssd_chunk_operands, _ssd_chunk),
    Site("ssd_step", ssd, ("ssd_step",), _ssd_step_operands, _ssd_step),
    Site("kda_chunk", linear_attention, ("kda_chunk",), _kda_chunk_operands, _kda_chunk),
    Site("delta_prep", linear_attention, ("delta_prep",), _prep_operands, _delta_prep),
    Site("kda_step", linear_attention, ("kda_step",), _kda_step_operands, _kda_step),
    Site("expert_gmm", *_GMM, _gmm),
    Site("expert_gmm_dx", *_GMM, _gmm_grads),  # the custom VJP's two kernels
    Site("expert_tgmm", *_GMM, _gmm_grads),
    Site("paged_attn", paged_attention, ("_paged_pallas",), _paged_operands, _paged(0)),
    # the paged site again under its other name: a window is a static, and a layer kind of its own
    Site("window_attn", paged_attention, ("_paged_pallas",), lambda size: _paged_operands(size, WINDOW),
         _paged(WINDOW)),
    Site("latent_attn", latent_attention, ("latent_paged_attention",), _latent_operands, _latent),
]


def _program(site, calls=CALLS, traced_index=True):
    """A new outer program at each asking (a new function: JAX's cache does not
    serve IT), which makes the site's call `calls` times on equal shapes, the
    index of each a traced scalar (an entry of the program's first operand) or
    a Python int."""
    return jax.jit(lambda indices, *ops: [site.call(ops, indices[i] if traced_index else i) for i in range(calls)])


@pytest.mark.parametrize("site", SITES, ids=[s.kernel for s in SITES])
def test_a_kernels_body_is_traced_once_a_shape_signature_and_the_program_is_what_it_was(site, monkeypatch):
    jax.clear_caches()  # whatever this process traced before is not this test's
    indices = jnp.arange(CALLS, dtype=jnp.int32)
    small, large = site.operands(1), site.operands(2)
    with _bodies_traced() as traced:
        _program(site).lower(indices, *small)
        assert traced[site.kernel] == 1, dict(traced)  # three calls, three layers or counts: one body
        _program(site).lower(indices, *small)
        assert traced[site.kernel] == 1, dict(traced)  # a second program of equal shapes: none
        # indices as Python ints 0, 1, 2: operands still, so at most ONE more signature (a weak-typed scalar's,
        # where the site has an index at all) and never one an int
        _program(site, traced_index=False).lower(indices, *small)
        seen = traced[site.kernel]
        assert seen <= 2, dict(traced)
        _program(site).lower(indices, *large)
        assert traced[site.kernel] == seen + 1, dict(traced)  # another shape: again, once
        # The program is what it is without the jit. Held on a program of ONE call, served by the cache: the
        # equations are the same in any, but equal calls share the cached jaxpr's constants (a flash call's
        # tables of live blocks), which a program then holds once and not once a call.
        lowered = _program(site, calls=1).lower(indices, *small)
        assert traced[site.kernel] == seen + 1, dict(traced)
        for holder in site.holders:
            jitted = getattr(site.module, holder)
            monkeypatch.setattr(site.module, holder, jitted.__wrapped__)
        plain = _program(site, calls=1).lower(indices, *small)
        assert lowered.as_text() == plain.as_text()
        _program(site).lower(indices, *small)
        assert traced[site.kernel] == seen + 2 + CALLS, dict(traced)  # and without it, a trace a call
