"""What a layer keeps of a sequence: one class a cache rule.

``LLMEngine`` holds one rule a LayerKind of its model, in the order of the pools (``rule_for`` picks the class, and
is the one place under ``llm/`` that reads what a kind is), and asks it what ``CacheRule`` lists. A rule is handed
its own pools and hands them back; the engine splices. An option a rule cannot serve is a sentence from
``refuses``; what only a rule with pages can do (copy pages, attend a tail over cached context) is a method the
others do not have. The arrows point one way: engine -> cache_rules -> ops, models.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as _P

from ray_tpu.models.transformer import (
    LayerKind, TransformerConfig, lane_padded, latent_absorb, latent_expand, latent_scale, latent_values, pad_last,
    recurrence, slot_state_shapes,
)
from ray_tpu.ops.latent_attention import latent_attention_reference, latent_paged_attention, latent_row_width
from ray_tpu.ops.paged_attention import (
    group_pages, kv_row_width, page_groups, paged_attention, paged_attention_reference, ring_pages,
    window_attention_reference,
)

# The one sentence for both halves: the latent rule's here, the held experts' (the FFN's) in LLMEngine.__init__.
ONE_CHIP = ("tensor_parallel > 1 is not written for a latent cache or held experts: their "
            "kernels run on one chip (ROADMAP M1, M3)")


def _kv_rows(kv, dtype, width=None):
    """One request's K or V of a layer, [1, P, KV, Hd], as the paged pool stores it: [KV, P, Hd] in the pool's
    dtype, zero-padded to the pool's row where that is wider than a head (ops/paged_attention.py
    ``kv_row_width``)."""
    rows = kv[0].transpose(1, 0, 2).astype(dtype)
    # padded rows are told their layout: without it the TPU compiler kept them tokens-minor and turned both pools
    # round to match, in and out of every prefill program (four copies of a pool a call; my chip run, PR 46)
    return rows if width in (None, rows.shape[-1]) else _row_major(pad_last(rows, width))


def _latent_rows(c, k_rope, width, dtype):
    """What a latent layer caches of tokens: c [..., R] and k_rope [..., rope] side by side, zero-padded to the
    pool's row width, in its dtype."""
    return pad_last(jnp.concatenate([c, k_rope], axis=-1), width).astype(dtype)


def _row_major(rows):
    """``rows`` held to the layout the pool has, last axis minor. A prompt's latent rows are put together from a 64
    wide roped key, which the TPU compiler lays token-minor, and a loop that carries the pool then takes the rows'
    layout for the pool: a transposed copy of the whole pool into the request scan and one out of it (2.9 GB each,
    as compiled for a v5e)."""
    if jax.default_backend() != "tpu":
        return rows
    from jax.experimental.layout import Layout, with_layout_constraint

    return with_layout_constraint(rows, Layout(major_to_minor=tuple(range(rows.ndim))))


def _prompt_attention(q, k, v, seg, mesh, scale=None, window=0):
    """Causal attention of a (padded) prompt over its own fresh K/V. seg masks pad columns (pad tokens are their
    own segment). scale: a latent layer's (its keys are wider than its values, so the flash kernel gets both
    zero-padded to one lane multiple); None is 1 / sqrt(head width). window: a sliding layer's (the flash kernel
    visits the band's blocks only). mesh: heads are sharded over mesh["tensor"], so the Pallas flash kernel runs
    per-shard under shard_map (GSPMD cannot partition a Mosaic kernel); the einsum reference path is
    GSPMD-partitionable as-is."""
    from ray_tpu.ops.attention import flash_attention, flash_supported, mha_reference

    def flash(q_, k_, v_, seg_):
        if scale is None:
            return flash_attention(q_, k_, v_, causal=True, segment_ids=seg_, window=window)
        return flash_attention(*lane_padded(q_, k_, v_), causal=True, segment_ids=seg_,
                               scale=scale)[..., :v_.shape[-1]]

    with jax.named_scope("flash_attn"):
        if not flash_supported(q.shape[1]):
            return mha_reference(q, k, v, causal=True, segment_ids=seg, scale=scale, window=window)
        if mesh is not None and mesh.shape.get("tensor", 1) > 1:
            hs = _P(None, None, "tensor", None)
            flash = jax.shard_map(
                flash, mesh=mesh, in_specs=(hs, hs, hs, _P(None, None)), out_specs=hs,
                check_vma=False,
            )
        return flash(q, k, v, seg)


def _kv_pools(cfg, kind, tokens: int, width: int) -> list:
    """K and V pools of a kind's layers, ``tokens`` rows of ``width`` columns a KV head, sharded by KV heads."""
    return [((cfg.layers_of(kind), cfg.kv_heads, tokens, width), _P(None, "tensor", None, None))] * 2


def _heads_prompt_attend(seg, mesh, dtype, width: int, window: int = 0):
    """A prompt's ``attend`` over its own K and V rows (inside ``window``); keeps them as the pools store them."""
    def attend(q, k, v):
        o = _prompt_attention(q, k, v, seg, mesh, window=window)
        return o, (_kv_rows(k, dtype, width), _kv_rows(v, dtype, width))
    return attend


def _heads_decode_attend(scope: str, call, pools, seen, page_tables, layer):
    """A decode step's ``attend`` over K and V pools: ``call`` writes k_new / v_new at position seen - 1 of
    each slot's pages (of its ring, in a layer with a window) and attends; it pads q and the token's rows
    where the pools' rows are wider."""
    def attend(q, k_new, v_new):
        with jax.named_scope(scope):
            o, kp, vp = call(q[:, 0], k_new[:, 0], v_new[:, 0], *pools, seen, page_tables, layer)  # o: [B, H, Hd]
        return o[:, None], (kp, vp)
    return attend


def _seen(lengths, n: int):
    """[n, rows]: each row's length at each of a block's n steps, the step's own token counted."""
    return lengths[None, :] + np.arange(1, n + 1)[:, None]


class CacheRule:
    """What ``LLMEngine`` asks of a rule, with the answers of one that has nothing to say. ``sl`` is the slice of
    the engine's ``cache`` its pools take; a method that is handed pools is handed those, and returns them. Beside
    these a rule has ``pools()`` (shape, partition spec, dtype of each), ``prompt_attend(lp, seg, length)`` (a
    prefill layer's ``attend`` over the prompt's own rows; its ``kept`` is what the layer hands out, one array a
    pool), ``write_prompt(pools, rows, page_idxs, place, length)`` (those rows of the kind's layers into the
    carried pools) and ``decode_attend(lp, pools, seen, page_tables, layer, walks)`` (a decode step's ``attend`` in
    the ``layer``-th layer of the kind; its ``kept`` the pools after the step's row; ``walks`` is None off the TPU,
    where the einsum references run). Only a rule with pages (``_PageTable``) restores."""
    n_pools = 2
    tok_axis: int | None = 2  # the pools' token axis (None: addressed by the slot, no tokens)
    walk_key = None  # (window, pages a grid step) of the walk a decode step builds for this rule; None: no walk
    zeroes: tuple = ()  # the keys of a step's record that are this rule's, beside live_pages and grid_steps
    device_counts: tuple = ()  # the names of what ``step_counts`` counts

    def __init__(self, cfg: TransformerConfig, kind: LayerKind, ec, first: int):
        self.cfg, self.kind, self.ec, self.name = cfg, kind, ec, kind.name
        self.sl = slice(first, first + self.n_pools)
        self.mesh = None

    def refuses(self, option: str) -> str | None:
        """Why ``option`` ("tensor_parallel > 1", "prefix_cache", "chunked_prefill") is not served; None: it is."""
        return None

    def beside(self, other: "CacheRule") -> str | None:
        """Why this rule and ``other`` are not served in one model; None: they are."""
        return None

    def allocate(self, mesh) -> tuple:
        """The rule's pools, zeroed, made directly sharded over ``mesh`` (replicated first, a multi-GB pool
        would be whole on one chip); the rule keeps the mesh for the kernels that run a shard at a time."""
        self.mesh = mesh

        def zeros(shape, spec, dtype=self.cfg.dtype):
            if mesh is None:
                return jnp.zeros(shape, dtype)
            return jax.jit(lambda: jnp.zeros(shape, dtype), out_shardings=NamedSharding(mesh, spec))()

        return tuple(zeros(*pool) for pool in self.pools())

    def in_pages(self, shape: tuple) -> tuple:
        """A pool's shape as a decode program carries it: the token axis split into pages and rows (a free
        reshape), or as it is for a pool addressed by the slot."""
        ax = self.tok_axis
        return shape if ax is None else shape[:ax] + (-1, self.ec.page_size) + shape[ax + 1:]

    def walk(self, seen, page_tables):
        """The walk of ``walk_key``: built once a decode step, for all the layers that share the key."""
        return page_groups(seen, page_tables, self.ec.page_size, *self.walk_key)

    def place(self, slots):
        """What the host tells a prefill of its requests' ``slots`` [k] beside their pages; None: nothing."""
        return None

    def step_counts(self, seen) -> tuple:
        """What a decode step counts on the device, int32 [1] each, named by ``device_counts``."""
        return ()

    def block_counts(self, lengths, n: int) -> dict:
        """What a decode block of ``n`` steps over rows of ``lengths`` (the host's mirror as the block was
        dispatched) sets in the step's record."""
        return {}


class _PageTable(CacheRule):
    """Rows of every token in pages of ``total_pages`` that the page tables share out, tokens along
    ``tok_axis``: what a prefix hit copies and a tail gathers its context from."""

    def read_pages(self, pool, page_idxs):
        """Pages ``page_idxs`` [n] of every layer, side by side along the token axis (unrolled: n is small)."""
        ps, ax = self.ec.page_size, self.tok_axis
        page = pool.shape[:ax] + (ps,) + pool.shape[ax + 1:]
        zeros = (0,) * pool.ndim
        return jnp.concatenate(
            [jax.lax.dynamic_slice(pool, zeros[:ax] + (page_idxs[i] * ps,) + zeros[ax + 1:], page)
             for i in range(page_idxs.shape[0])], axis=ax)

    def write_pages(self, pools, rows, page_idxs):
        """Fresh rows, one array a pool (``rows[i]`` as the pool but n * ps tokens long, in its dtype), into
        pages ``page_idxs`` [n] of the carried pools: one in-place ``dynamic_update_slice`` a page and pool
        (the rule for a carried pool: where LLMEngine makes them). Trailing page ids 0 are the dead sink."""
        ps, ax = self.ec.page_size, self.tok_axis
        pools = list(pools)
        with jax.named_scope("kv_write"):
            for p in range(page_idxs.shape[0]):
                for i, new in enumerate(rows):
                    at = (0,) * ax + (page_idxs[p] * ps,) + (0,) * (new.ndim - ax - 1)
                    pools[i] = jax.lax.dynamic_update_slice(
                        pools[i], jax.lax.slice_in_dim(new, p * ps, (p + 1) * ps, axis=ax), at)
        return pools

    def write_prompt(self, pools, rows, page_idxs, place, length):
        return self.write_pages(pools, rows, page_idxs)

    def block_counts(self, lengths, n):
        """(pages, grid steps) the paged kernel walks in a decode block, a layer that keeps every token: a
        slot's ceil(length / page_size) pages at each step, in ceil(pages / group) steps of up to ``group``
        pages each. Pages over n x max_slots x (max_seq / page_size) is the share of the page table the
        kernel walks, pages over steps how full a grid step is."""
        ps = self.ec.page_size
        pages = np.minimum(-(-_seen(lengths, n) // ps), self.ec.max_seq // ps)
        return {"live_pages": int(pages.sum()), "grid_steps": int((-(-pages // self.group)).sum())}


class PagedRows(_PageTable):
    """K and V rows of every token, at the KV-head count (the HBM saving is what makes long contexts fit; the paged
    kernel reads grouped heads directly): two pools [the kind's layers, KV, total_pages * page_size,
    ``kv_row_width``], shared out by the page tables and sharded by KV heads over a ``tensor`` mesh. Prefill
    scatters a prompt's rows into its pages; decode's ``paged_attn`` call writes the step's row in place and
    attends through the page table, a grid step ``group`` pages of one sequence. A prefix hit copies pages, and a
    tail attends cached pages gathered from the pool (einsum attention where the cold prefill runs the flash
    kernel)."""

    def __init__(self, cfg, kind, ec, first, itemsize):
        super().__init__(cfg, kind, ec, first)
        self.width, self.itemsize = kv_row_width(cfg.head_dim), itemsize

    def allocate(self, mesh):
        ec, shards = self.ec, 1 if mesh is None else mesh.shape["tensor"]
        # a page as ONE device holds it: its share of the KV heads
        self.group = group_pages(self.cfg.kv_heads // shards, ec.page_size, self.width, self.itemsize,
                                 ec.max_seq // ec.page_size)
        self.walk_key = (0, self.group)
        return super().allocate(mesh)

    def pools(self):
        return _kv_pools(self.cfg, self.kind, self.ec.total_pages * self.ec.page_size, self.width)

    def prompt_attend(self, lp, seg, length):
        return _heads_prompt_attend(seg, self.mesh, self.cfg.dtype, self.width)

    def decode_attend(self, lp, pools, seen, page_tables, layer, walks):
        call = paged_attention_reference if walks is None else functools.partial(
            paged_attention, mesh=self.mesh, walk=walks[self.walk_key])
        return _heads_decode_attend("paged_attn", call, pools, seen, page_tables, layer)

    def tail_attend(self, lp, mask, ctx_k, ctx_v):
        KV, Hd, dtype = self.cfg.kv_heads, self.cfg.head_dim, self.cfg.dtype
        heads = self.kind.n_heads

        def attend(q, k_new, v_new):
            Tb = q.shape[1]
            kt = _kv_rows(k_new, dtype, self.width)  # [KV,Tb,Hd], or as wide as the pool's rows
            vt = _kv_rows(v_new, dtype, self.width)
            kall = jnp.concatenate([ctx_k, kt], axis=1)[..., :Hd]  # [KV, C*ps+Tb, Hd]
            vall = jnp.concatenate([ctx_v, vt], axis=1)[..., :Hd]
            qg = q[0].reshape(Tb, KV, heads // KV, Hd)
            scores = jnp.einsum("tkgh,ksh->tkgs", qg, kall).astype(jnp.float32)
            scores = scores / math.sqrt(Hd)
            scores = jnp.where(mask[:, None, None, :], scores, -1e30)
            pr = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
            o = jnp.einsum("tkgs,ksh->tkgh", pr, vall).reshape(1, Tb, heads, Hd)
            return o, (kt, vt)
        return attend


class SlotRing(CacheRule):
    """The last ``window`` of K and V rows, a slot, in a ring of ``ring_pages(window, page_size)`` pages and
    nothing behind it (ops/paged_attention.py says how a page finds its place in the ring): two pools of max_slots
    x ring pages whatever the contexts, addressed by the slot and not through the page table. Prefill attends
    inside the window and writes a prompt's last window of rows into the slot's ring; decode's ``window_attn`` call
    walks the window's pages. Admission budgets pages of the layers that keep every token. Cached pages cannot
    restore a ring, and a chunk would attend pages the ring no longer holds: no method restores (ROADMAP M2)."""
    zeroes = ("window_pages", "window_tokens")

    def __init__(self, cfg, kind, ec, first, itemsize, window):
        super().__init__(cfg, kind, ec, first)
        self.window, self.width, ps = window, kv_row_width(cfg.head_dim), ec.page_size
        self.n_ring = ring_pages(window, ps)
        self.group = group_pages(cfg.kv_heads, ps, self.width, itemsize, ec.max_seq // ps, window)
        self.walk_key = (window, self.group)

    def refuses(self, option):
        return {
            "tensor_parallel > 1":
                "tensor_parallel > 1 is not written for window layers: a slot's ring of pages is "
                "addressed by the slot, not through the page table the sharded kernel walks (ROADMAP M2)",
            "prefix_cache":
                "prefix_cache is not written for window layers: a hit copies pages, and a window layer "
                "keeps a slot's last window of rows in a ring, which cached pages cannot restore; a "
                "partial hit's tail prefill would attend a context those layers no longer hold (ROADMAP M2)",
            "chunked_prefill":
                "chunked_prefill is not written for window layers: a chunk attends the earlier chunks' "
                "pages, and a window layer keeps no pages behind its ring (ROADMAP M2)",
        }.get(option)

    def beside(self, other):
        # a prefill is told ONE place: a ring's first page (slot x ring pages) or the slot (ROADMAP M4 (e))
        if isinstance(other, SlotRing) and other.window != self.window:
            return f"window layers of one window are written; the pattern has {self.cfg.kinds}"
        if isinstance(other, _BySlot):
            return (f"window layers beside {other.such} are not written: a prefill is told its slot's "
                    "ring or its slot (ROADMAP M4)")
        return None

    def pools(self):
        return _kv_pools(self.cfg, self.kind, self.ec.max_slots * self.n_ring * self.ec.page_size, self.width)

    def prompt_attend(self, lp, seg, length):
        return _heads_prompt_attend(seg, self.mesh, self.cfg.dtype, self.width, self.window)

    def write_prompt(self, pools, rows, page_idxs, place, length):
        """A prompt's last window of rows into the ring whose first page is ``place``. The pages that hold
        positions length - window .. length - 1 are at most the ring's, and page j goes to ring page
        j % ring; a prompt of fewer pages writes its first page again where it has no further one. What
        the bucket padded behind ``length`` lands in the last page's later rows, which a query's length
        masks until decode has overwritten them, as in a full layer's page."""
        ps, n_ring = self.ec.page_size, self.n_ring
        pools = list(pools)
        last = (length - 1) // ps
        with jax.named_scope("kv_write"):
            for r in range(n_ring):
                j = jnp.maximum(last - r, 0)
                for i, new in enumerate(rows):
                    pools[i] = jax.lax.dynamic_update_slice(
                        pools[i], jax.lax.dynamic_slice_in_dim(new, j * ps, ps, axis=2),
                        (0, 0, (place + j % n_ring) * ps, 0))
        return pools

    def decode_attend(self, lp, pools, seen, page_tables, layer, walks):
        if walks is None:
            def call(q, k, v, kp, vp, seen, _table, layer):
                return window_attention_reference(q, k, v, kp, vp, seen, layer, self.window)
        else:
            call = functools.partial(paged_attention, walk=walks[self.walk_key], window=self.window)
        return _heads_decode_attend("window_attn", call, pools, seen, page_tables, layer)

    def place(self, slots):
        return np.asarray(slots, np.int32) * self.n_ring  # each slot's first ring page

    def block_counts(self, lengths, n):
        """(pages, positions attended) of ONE window layer in a decode block: a slot walks the pages from the
        one that holds position seen - window to the current one and attends min(seen, window) positions."""
        ps, w = self.ec.page_size, self.window
        seen = _seen(lengths, n)
        pages = (seen - 1) // ps - np.maximum(seen - w, 0) // ps + 1
        return {"window_pages": int(pages.sum()), "window_tokens": int(np.minimum(seen, w).sum())}


class _BySlot(CacheRule):
    """Pools addressed by the slot, [the kind's layers, max_slots, ...] each, that do not grow with the context: a
    prefill is told its slot and leaves there what its prompt left, one in-place update a pool. A subclass says in
    ``why`` what each option it refuses would need of it."""
    tok_axis = None

    def __init__(self, cfg, kind, ec, first, such):
        super().__init__(cfg, kind, ec, first)
        self.such = such  # the model's recurrent layers, as a refusal names them

    def write_prompt(self, pools, rows, page_idxs, place, length):
        """What the prompt left, every layer's, into slot ``place``: one in-place update a pool."""
        with jax.named_scope("state_write"):
            return [jax.lax.dynamic_update_slice(pool, new[:, None].astype(pool.dtype),
                                                 (0, place) + (0,) * (pool.ndim - 2))
                    for pool, new in zip(pools, rows)]

    def place(self, slots):
        return np.asarray(slots, np.int32)  # the slot itself

    def refuses(self, option):
        return f"{option} is not written for {self.such}: {self.why[option]} (ROADMAP M4)"

    def step_counts(self, seen):
        # the slots this step rewrote in every layer of the kind: the slots with pages (a step call's grid)
        return (jnp.sum(seen > 0, dtype=jnp.int32).reshape(1),)


class SlotState(_BySlot):
    """A state kept by slot, for both recurrences (``mixer="delta"``, ops/linear_attention.py; ``mixer="ssd"``,
    ops/ssd.py: the kind gives the shapes, ``slot_state_shapes``, and the two calls, ``recurrence``): such a layer
    keeps no rows of tokens. Its two pools are addressed by the slot and do not grow with the context: a state in
    float32 and the last conv_size - 1 inputs of its short convolution, each [the kind's layers, max_slots, ...].
    Prefill leaves both as they stand at the prompt's own length, not at its bucket's end (positions behind the
    length leave the state alone, and the tail is cut at the length); decode carries both through its loops like
    the other pools, the state updated in place by the step call its output aliases (``kda_step``, ``ssd_step``),
    one grid step a live slot and none for an empty one, whose state stays bit for bit. Admission budgets pages for
    the layers that keep every token. A page copy cannot restore a state and a chunk of a prompt would have to
    start from one: no method restores (ROADMAP M4)."""
    zeroes = ("state_rows",)
    device_counts = ("state_rows",)

    why = {
        "prefix_cache": "a hit copies pages, and a page copy cannot restore the state such a layer keeps of a prefix",
        "chunked_prefill":
            "a chunk would have to start from the state and the convolution tail the chunk before left, "
            "and the prefill programs start from an empty one",
        "tensor_parallel > 1": "the state pool is addressed by the slot and its kernels run on one chip",
    }

    def pools(self):
        state, tail = slot_state_shapes(self.cfg, self.kind)
        behind = (self.cfg.layers_of(self.kind), self.ec.max_slots)
        return [((*behind, *state), _P(), jnp.float32), ((*behind, *tail), _P())]

    def prompt_attend(self, lp, seg, length):
        """The state and the convolution's last inputs as they stand at the prompt's ``length``: the bucket's
        padding behind it leaves the state alone (no step, no decay)."""
        cfg, kind = self.cfg, self.kind
        name, over_a_prompt, _ = recurrence(kind)

        def rule(ops, window):
            def real(a):  # the log decay or the step size [1, P, ...], zero behind the prompt's length
                return jnp.where((seg == 0).reshape(seg.shape + (1,) * (a.ndim - 2)), a, 0.0)
            with jax.named_scope(f"{name}_chunk"):
                o, state = over_a_prompt(*ops[:-2], real(ops[-2]), real(ops[-1]), out_dtype=cfg.dtype)
            # positions length - (T - 1) .. length - 1: the window leads with the T - 1 before position 0
            tail = jax.lax.dynamic_slice_in_dim(window[0], length, kind.conv_size - 1, axis=0)
            return o, (state[0], tail.reshape(slot_state_shapes(cfg, kind)[1]))
        return None, rule

    def decode_attend(self, lp, pools, seen, page_tables, layer, walks):
        state, tails = pools
        live = seen > 0
        tail = jax.lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False)  # [B, T - 1, ...]
        name, _, one_token = recurrence(self.kind)

        def rule(ops, window):
            # a slot without a request keeps its tail and its state as they were
            kept = jnp.where(live.reshape((-1,) + (1,) * (tail.ndim - 1)),
                             window[:, 1:].astype(tails.dtype).reshape(tail.shape), tail)
            with jax.named_scope(f"{name}_step"):
                o, new_state = one_token(*(a[:, 0] for a in ops), state, layer, live)
            new_tails = jax.lax.dynamic_update_slice(tails, kept[None], (layer,) + (0,) * (tails.ndim - 1))
            return o[:, None], (new_state, new_tails)
        return tail, rule


class SlotTail(_BySlot):
    """A convolution's tail kept by slot and nothing beside it (``mixer="conv"``): the last conv_size - 1 rows of
    what the layer's short convolution runs over, end to end in ONE pool [the kind's layers, max_slots, (T - 1) x
    d_model] in the activations' dtype. Prefill cuts the tail at the prompt's own length, not at its bucket's end;
    decode shifts a live slot's tail by the step's row and leaves an empty slot's bit for bit. No kernel, no float32.
    No page holds a tail and a chunk of a prompt would have to start from one: no method restores (ROADMAP M4)."""
    n_pools = 1
    zeroes = ("tail_rows",)
    device_counts = ("tail_rows",)

    why = {
        "prefix_cache": "a hit copies pages, and no page holds the convolution tail such a layer keeps of a prefix",
        "chunked_prefill":
            "a chunk would have to start from the convolution tail the chunk before left, and the prefill "
            "programs start from zeros",
        "tensor_parallel > 1": "the tail pool is addressed by the slot, not through the page table a shard walks",
    }

    def pools(self):
        return [((self.cfg.layers_of(self.kind), self.ec.max_slots, *slot_state_shapes(self.cfg, self.kind)[1]), _P())]

    def prompt_attend(self, lp, seg, length):
        def keep(window):  # rows length - (T - 1) .. length - 1: the window leads with the T - 1 before position 0
            return (jax.lax.dynamic_slice_in_dim(window[0], length, self.kind.conv_size - 1, axis=0).reshape(-1),)
        return None, keep

    def decode_attend(self, lp, pools, seen, page_tables, layer, walks):
        (tails,) = pools
        tail = jax.lax.dynamic_index_in_dim(tails, layer, 0, keepdims=False)  # [B, (T - 1) x D]

        def keep(window):  # [B, T, D]: a slot without a request keeps its tail as it was
            kept = jnp.where((seen > 0)[:, None], window[:, 1:].astype(tails.dtype).reshape(tail.shape), tail)
            return (jax.lax.dynamic_update_slice(tails, kept[None], (layer, 0, 0)),)
        return tail, keep


class LatentRows(_PageTable):
    """One row a token a layer of the kind, ``[c | k_rope]`` (the normed low-rank latent and the roped key all heads
    share), in ONE pool [the kind's layers, total_pages * page_size, ``latent_row_width``] in place of two pools of
    head rows; page
    tables, lengths, admission and the prefix cache's digests are the paged rule's. A prompt expands its own rows
    to keys and values for the flash kernel; decode absorbs the two up-projections into the query and the output
    and attends the rows as they lie (``latent_attn``, ops/latent_attention.py); a tail expands context and tail
    rows alike (the plain path; no absorbed form there). Its kernels run on one chip."""
    n_pools, tok_axis = 1, 1

    def __init__(self, cfg, kind, ec, first, itemsize):
        super().__init__(cfg, kind, ec, first)
        self.width = latent_row_width(cfg.kv_lora_rank, cfg.qk_rope_head_dim)
        self.group = group_pages(1, ec.page_size, self.width, itemsize, ec.max_seq // ec.page_size)
        self.walk_key = (0, self.group)

    def refuses(self, option):
        return ONE_CHIP if option == "tensor_parallel > 1" else None

    def pools(self):
        return [((self.cfg.layers_of(self.kind), self.ec.total_pages * self.ec.page_size, self.width),
                 _P(None, None, None))]

    def prompt_attend(self, lp, seg, length):
        cfg = self.cfg

        def attend(q, c, k_rope):
            k, v = latent_expand(lp, c, k_rope, c.dtype)
            o = _prompt_attention(jnp.concatenate(q, axis=-1), k, v, seg, self.mesh, latent_scale(cfg, self.kind))
            return o, (_row_major(_latent_rows(c[0], k_rope[0], self.width, cfg.dtype)),)
        return attend

    def decode_attend(self, lp, pools, seen, page_tables, layer, walks):
        cfg = self.cfg
        call = latent_attention_reference if walks is None else functools.partial(
            latent_paged_attention, walk=walks[self.walk_key])

        def attend(q, c, k_rope):
            q_nope, q_rope = q
            dt = c.dtype
            with jax.named_scope("latent_attn"):
                qt = latent_absorb(lp, q_nope[:, 0], dt)
                q_row = _latent_rows(qt, q_rope[:, 0], self.width, dt)  # [B, H, W]
                row = _latent_rows(c[:, 0], k_rope[:, 0], self.width, dt)  # [B, W]
                ctx, pool = call(q_row, row, pools[0], seen, page_tables, layer,
                                 v_width=cfg.kv_lora_rank, scale=latent_scale(cfg, self.kind))  # ctx: [B, H, R]
                o = latent_values(lp, ctx, dt)
            return o[:, None], (pool,)
        return attend

    def tail_attend(self, lp, mask, ctx_rows):
        cfg = self.cfg
        R, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim

        def attend(q, c, k_rope):
            rows = _latent_rows(c[0], k_rope[0], self.width, cfg.dtype)  # [Tb, W]
            every = jnp.concatenate([ctx_rows, rows], axis=0)[None]  # [1, C*ps+Tb, W]
            k, v = latent_expand(lp, every[..., :R], every[..., R:R + rope], c.dtype)
            scores = jnp.einsum("bthk,bshk->bhts", jnp.concatenate(q, axis=-1), k).astype(jnp.float32)
            scores = jnp.where(mask[None, None], scores * latent_scale(cfg, self.kind), -1e30)
            pr = jax.nn.softmax(scores, axis=-1).astype(c.dtype)
            return jnp.einsum("bhts,bshk->bthk", pr, v), (rows,)
        return attend


def rule_for(cfg: TransformerConfig, kind: LayerKind, ec, first: int) -> CacheRule:
    """The rule of one LayerKind of ``cfg``, its pools from ``first`` on in the engine's ``cache``. ``ec``:
    the EngineConfig with max_seq and total_pages filled in."""
    # What a page's bytes are counted in when a walk's group is sized: the dtype of the cache's FIRST pool, as
    # before the rules. For a model whose first kind keeps a state that is the float32 state's, and its attention
    # layers walk half the pages a step their bfloat16 pages would allow (PERF.md section 7); a first kind that
    # keeps a tail alone has no float32 pool.
    itemsize = jnp.dtype(jnp.float32 if cfg.kinds[0].state else cfg.dtype).itemsize
    if kind.mixer == "latent":  # its walk counts pages in its own pool's dtype, whatever kind comes first
        return LatentRows(cfg, kind, ec, first, jnp.dtype(cfg.dtype).itemsize)
    if kind.recurrent:
        such = " and ".join(sorted({k.mixer for k in cfg.kinds if k.recurrent})) + " layers"
        return (SlotState if kind.state else SlotTail)(cfg, kind, ec, first, such)
    if kind.window:
        return SlotRing(cfg, kind, ec, first, itemsize, kind.window)
    return PagedRows(cfg, kind, ec, first, itemsize)
