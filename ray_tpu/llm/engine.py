"""Continuous-batching LLM engine: block-paged KV cache, bucketed prefill,
fused decode blocks.

TPU-first design (vs the reference's delegation to vLLM,
llm/_internal/serve/engines/vllm/vllm_engine.py:174):
- Static shapes everywhere: the cache is a few pools made once; prompts
  prefill through a few length-bucketed jitted programs; decoding is ONE
  jitted block over all slots per iteration — XLA sees a handful of programs
  total, not a shape per batch composition.
- Paged KV (vLLM's core idea, re-expressed for XLA): each sequence owns a
  page list, and decode carries the pools whole through its loops while the
  kernels read and write pages where they lie (no materialized gather, no
  slice of a pool). A decode step builds its walks of live pages once from
  the lengths and hands them to every layer, so a call costs the tokens in
  the cache and a slot without a request has no grid step. Memory scales
  with reserved pages, not slots × max_seq, and admission is page-budgeted.
- Continuous batching is the host loop: finished slots retire (their pages
  return to the free list) and queued requests prefill into free slots.
  Prefill groups are dispatched back-to-back asynchronously and fetched in
  order, so a request's TTFT is its own group's completion, not the whole
  admission wave's.
- The host is one decode block behind the device and never more (``step``,
  ``_Block``, ``_absorb``): while block s runs the host absorbs block s-1,
  hands its events to the serving loop and does the next step's admission,
  prefill dispatch and mirror updates. A block's outputs (last tokens,
  lengths, the pools) are the next block's inputs on the device; the host's
  ``lengths`` advance at dispatch and so are the device's. A request that
  ends by a token is found out a block late: its row rides that block and
  the walk throws the row away.
- Admission-aware decode (``_fit``): under queue pressure the decode block
  shrinks so waiting requests reach a prefill slot sooner; with an empty
  queue full blocks are what the host's work hides under.
- What a layer keeps of a sequence is its cache rule's (llm/cache_rules.py:
  K and V rows in pages, a ring a slot, a state a slot, a convolution's tail
  a slot, latent rows in pages): the engine holds one rule a LayerKind
  (``self.rules``), hands each its own pools of ``self.cache`` and asks; the
  programs here loop over them. Rules compose: a model whose layers are of
  two kinds has two (a state a slot beside K and V rows in pages, or beside
  latent rows in pages: ``SlotState`` and ``LatentRows`` in one engine), and
  nothing here names either.
- Tensor-parallel serving (EngineConfig.tensor_parallel > 1): params shard
  Megatron-style and the KV pools shard by kv_heads over a `tensor` mesh
  axis (parallel/), so a model bigger than one chip's HBM serves from a
  gang of chips; XLA inserts the ICI collectives, the Pallas kernels run
  per-shard under shard_map, and the host scheduler is unchanged. The
  reference reaches the same capability by mapping TP degrees onto
  placement-group bundles for vLLM (vllm_models.py:233-238).

TTFT is measured from request arrival to its first sampled token (prefill
completes inside that window), the standard serving definition.

Page-0 convention: page 0 is never allocated; dead page-table entries point
at it (the paged kernel masks them by length) and it absorbs the writes of
a row that overshoots: one that has ended may be written up to two blocks
past its budget, one more than ``_pages_needed`` reserves, and what passes
its last page goes through the zero tail of its table row (nobody reads
what lands there). A retired or empty slot's rows of the mirrors are zero:
the kernel walks no page and writes no row for it.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
import time
from collections import OrderedDict, deque
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as _P

from ray_tpu.accel.device import compile_stages
from ray_tpu.llm.cache_rules import ONE_CHIP, rule_for
from ray_tpu.llm.sampling import SamplingParams, sample_batch
from ray_tpu.models.transformer import (
    TransformerConfig, decoder_block, embed_tokens, hidden_logits, init_params, model_norm, param_logical_axes,
    run_layers,
)
from ray_tpu.util import tracing as _tracing

# The seams of LLMEngine.step, in the order a step passes them (PERF.md
# section 3 says what each covers and which metric reads it).
STEP_PHASES = (
    "admit", "prefix_lookup", "prefill_dispatch", "mirror_sync", "prefill_fetch",
    "decode_dispatch", "decode_fetch", "emit", "retire_sync",
)
# Finished requests and ended steps kept for LLMServer.stats(): a benchmark
# run whole (a 51 s window with ramp and drain is ~300 requests, ~250 steps).
TRACE_RING = 2048
# The most tokens a prefill group of k > 1 requests may hold (k * bucket): 8 up
# to 256 tokens, 4 up to 512, 2 up to 1,024, single requests above. The k
# requests of a group run one after another inside its program
# (_prefill_batch_impl scans them), so what a group saves is a dispatch
# (0.6 ms) and a few ms of host work a request: something beside a prefill of
# a few ms, nothing beside one of 30-280 ms, and every (bucket, k) is a
# program that set-up traces, lowers and loads (PERF.md section 6, PR 48).
GROUP_TOKENS = 2048
# Of accel/device.compile_stages, what a warmed program's entry of warmup_log carries beside its seconds.
WARMUP_STAGES = ("trace_s", "lower_s", "backend_s", "miss_s", "traces", "misses", "executables")


def bucket_ladder(prefill_buckets, page_size: int, max_seq: int) -> tuple:
    """The prefill buckets an engine pads prompts to: the configured lengths
    rounded up to whole pages, those over max_seq dropped, max_seq itself, and
    between any two neighbours lo < hi with hi >= 2 * lo the page-aligned
    midpoint (between neighbours one page apart it is hi itself: no rung)."""
    ps, S = page_size, max_seq
    named = sorted({min(ps * math.ceil(b / ps), S) for b in prefill_buckets if b <= S} | {S})
    mids = {ps * math.ceil((lo + hi) / 2 / ps) for lo, hi in zip(named, named[1:]) if hi >= 2 * lo}
    return tuple(sorted(mids.union(named)))


@dataclasses.dataclass
class EngineConfig:
    max_slots: int = 8
    max_seq: int = 0  # 0 -> model max_seq_len
    # The prompt lengths a deployment expects; a prompt is padded to the
    # shortest bucket that holds it. The engine rounds these to whole pages,
    # adds max_seq, and adds a rung halfway between any two a doubling or more
    # apart (bucket_ladder); it prefills requests of one bucket in groups of
    # 8, 4 or 2 only while a group holds at most 2,048 tokens (GROUP_TOKENS).
    prefill_buckets: tuple = (128, 256, 512, 1024, 2048)
    temperature: float = 0.0  # 0 => greedy
    eos_id: int = -1  # -1 => never stop on a token; set to the tokenizer's id
    seed: int = 0
    # Decode steps fused into one device program per host round trip. On a
    # directly attached v5e an awaited dispatch costs 0.55-0.61 ms and a
    # decode step 6-21 ms (PERF.md section 5), so the block is not there for
    # the dispatch: a step's 20-40 ms of admission, prefill dispatch, mirror
    # updates and token walk run while the block before it decodes, so a block
    # has to last about as long as they do (8 steps: 51-167 ms; the short
    # block of 2 under queue pressure hides only part). Cost: admissions
    # happen between blocks, and a slot finishing mid-block discards its tail.
    decode_block: int = 8
    # Retired key: the block-paged pool is the one KV layout (the dense
    # per-slot cache went in PR 30). Configurations under benchmarks/ still
    # spell kv_layout="paged", so the field stays until a benchmark PR drops
    # the key (ROADMAP D14); any other value is refused.
    kv_layout: str = "paged"
    # KV page size (tokens). max_seq must be a multiple; prefill buckets are
    # rounded up to multiples.
    page_size: int = 128
    # Page-pool size. 0 -> one full-length sequence a slot
    # (max_slots * max_seq / page_size) + 1. Smaller pools trade concurrency
    # ceilings for memory: admission reserves
    # ceil((prompt + max_tokens + decode_block)/page_size) pages per request
    # and queues when the pool is dry.
    total_pages: int = 0
    # Tensor-parallel serving degree (the module docstring says how): >1
    # shards the model and the KV pools over a `tensor` mesh axis of that many
    # local devices; page tables, lengths and sampling state stay replicated.
    # Requires n_heads, kv_heads, d_ff and vocab_size divisible by the degree.
    tensor_parallel: int = 1
    # Candidate cap for truncated (top-k/top-p) sampling rows; see
    # sampling.TOPK_CAP for the nucleus-width caveat. Raise for workloads
    # sampling high-entropy distributions with top_p near 1.
    sample_topk_cap: int = 128
    # Chunked prefill (vLLM's chunked-prefill idea on the tail-prefill
    # program): a prompt whose un-cached span exceeds this many tokens
    # prefills in page-aligned chunks of this size, ONE chunk per engine
    # step, interleaved with the decode blocks — a 512-token prefill can no
    # longer head-of-line-stall decoding slots for its whole length; decode
    # stall per step is bounded by one chunk's compute. Must be a multiple
    # of page_size. 0 = off (whole-prompt prefill). Refused by a cache rule
    # that cannot attend a tail over cached pages (llm/cache_rules.py).
    chunked_prefill: int = 0
    # Prefix KV cache (reference: vLLM automatic prefix caching +
    # PrefixCacheAffinityRouter, prefix_aware_router.py:39). A retired
    # request's PROMPT pages stay in an LRU cache under CHAINED
    # digests — one entry per page-aligned prefix plus the full prompt, the
    # pages refcounted across entries (vLLM's caching is block-granular for
    # the same reason):
    # - exact hit: copy the cached pages on-device (a few MB gather vs
    #   ~100s of ms of prefill compute) and start decoding at position P-1
    #   — the fused decode block re-derives that position's KV and emits
    #   the first token with NO prefill. The continuation is, token for
    #   token, that of a request which decoded its way to P-1; against the
    #   cold send, whose P-1 came from the prefill program, it is equal in
    #   f32 and in bf16 parts at the first near-tie (measured on the chip
    #   by chip_smoke.py's repeat phase — PERF.md "Bring-up").
    # - partial hit (the canonical shared-system-prompt workload: a new
    #   prompt EXTENDS a cached page-aligned prefix): copy the matched
    #   pages, then a chunked TAIL prefill embeds only the new tokens,
    #   attending to the cached pages gathered from the pool — prefill
    #   compute scales with the tail, not the prompt.
    # Refused by a cache rule that pages cannot restore (llm/cache_rules.py).
    prefix_cache: bool = False

    def __post_init__(self):
        if self.kv_layout != "paged":
            raise ValueError(f"kv_layout {self.kv_layout!r}: the dense layout was removed in PR 30 (paged only)")


@dataclasses.dataclass
class _Slot:
    req_id: str
    max_tokens: int
    pages: list  # page ids owned by this request
    emitted: list = dataclasses.field(default_factory=list)
    n_generated: int = 0  # dispatched count (values may still be on device)
    arrived_at: float = 0.0
    prefill_pos: int = 0  # tokens already prefilled (chunked-prefill progress)
    first_token_at: Optional[float] = None
    stop_ids: tuple = ()  # per-request stop tokens (on top of engine eos)
    ignore_eos: bool = False
    # Prompt tokens, kept only when this prompt's pages should enter the
    # prefix cache at retire (miss or partial hit; an exact hit adds nothing).
    prompt_tokens: Optional[np.ndarray] = None
    prompt_len: int = 0
    # The request's lifecycle record (add_request makes it; stamps on
    # time.monotonic()). arrived_at / first_token_at above stay on
    # perf_counter: the streamed ttft_s is their difference.
    life: Optional[dict] = None


@dataclasses.dataclass
class _Block:
    """A decode block the device has been handed and the host has not fetched:
    what ``LLMEngine._absorb`` needs to walk it a step later."""
    toks: Any  # [n, max_slots], on the device
    counts: Any  # held experts' (pairs, tiles), then the rules' device counts, int32 on the device; None without either
    n: int
    rec: dict  # the dispatching step's record (the ring holds this dict: counts land in it)
    rows: list  # (slot index, the _Slot that held it at dispatch) of every active row


# A traced request's phases inside the replica, as child spans of the trace
# context add_request captured: (span name, stamp it starts at, stamp it ends at).
REQUEST_SPANS = (
    ("llm.queue", "arrived", "admitted"),
    ("llm.prefill", "admitted", "first_token"),
    ("llm.prefill.enqueue", "admitted", "prefill_enqueued"),  # the host's part of llm.prefill
    ("llm.first_emit", "first_token", "first_emitted"),
    ("llm.decode", "first_emitted", "finished"),
)


def record_request_spans(life: dict) -> None:
    """Lay a finished request's lifecycle onto its own trace (no-op for an
    untraced one). Called by the thread that steps the engine, where the
    request's contextvar is not set: the context rides the record. Stamps
    are converted once to the tracing.now() clock. A phase with a stamp
    missing (an abort before admission; no serving loop around the engine)
    is left out; one that ends before it starts (a request that finished in
    the step of its first token retires before that token is emitted)
    records zero seconds."""
    ctx = life["trace"]
    if ctx is None:
        return
    to_span_clock = _tracing.now() - time.monotonic()
    for name, start, end in REQUEST_SPANS:
        t0, t1 = life[start], life[end]
        if t0 is not None and t1 is not None:
            _tracing.record_span(ctx, name, t0 + to_span_clock, t1 - t0,
                                 req_id=life["req_id"], slot=life["slot"])


class LLMEngine:
    """Host-side continuous batching over the jitted prefill/decode programs."""

    def __init__(self, cfg: TransformerConfig, params=None, engine_config: EngineConfig | None = None):
        if cfg.n_experts and not cfg.experts_held:
            raise ValueError(
                "MoE serving needs a model that says which experts this chip holds "
                "(TransformerConfig.experts_held / first_expert); the training form, every "
                "expert computed for every token, is not served")
        self.cfg = cfg
        self.ec = engine_config or EngineConfig()
        tp = self.ec.tensor_parallel
        if cfg.experts_held and tp > 1:
            raise ValueError(ONE_CHIP)
        if self.ec.max_seq <= 0:
            self.ec = dataclasses.replace(self.ec, max_seq=cfg.max_seq_len)
        S = self.ec.max_seq
        ps = self.ec.page_size
        if S % ps:
            raise ValueError(f"max_seq {S} must be a multiple of page_size {ps}")
        if self.ec.total_pages <= 0:
            self.ec = dataclasses.replace(
                self.ec, total_pages=self.ec.max_slots * (S // ps) + 1
            )
        # One cache rule a LayerKind, in the pools' order (llm/cache_rules.py).
        # Every rule is asked about every option that is on and about every
        # rule beside it; what one cannot serve is refused in its own words.
        self.rules: list = []
        for kind in cfg.kinds:
            self.rules.append(rule_for(cfg, kind, self.ec, first=self.rules[-1].sl.stop if self.rules else 0))
        options = [name for name, on in (("tensor_parallel > 1", tp > 1), ("prefix_cache", self.ec.prefix_cache),
                                         ("chunked_prefill", self.ec.chunked_prefill)) if on]
        for rule in self.rules:
            for why in (*map(rule.refuses, options), *map(rule.beside, self.rules)):
                if why:
                    raise ValueError(why)
        # The tensor-parallel mesh; what is not sharded over it is replicated.
        self.mesh = None
        param_shardings = None
        if tp > 1:
            from ray_tpu.parallel.mesh import MeshSpec
            from ray_tpu.parallel.sharding import ShardingStrategy, logical_sharding

            devs = jax.devices()
            if len(devs) < tp:
                raise ValueError(
                    f"tensor_parallel={tp} but only {len(devs)} devices visible "
                    "(gang-schedule the replica with that many chips)"
                )
            for dim_name, dim in (("n_heads", cfg.n_heads), ("kv_heads", cfg.kv_heads),
                                  ("d_ff", cfg.d_ff), ("vocab_size", cfg.vocab_size)):
                if dim % tp:
                    raise ValueError(
                        f"{dim_name}={dim} not divisible by tensor_parallel={tp}"
                    )
            self.mesh = MeshSpec(tensor=tp).build(devs[:tp])
            param_shardings = logical_sharding(
                self.mesh, ShardingStrategy.tp(), param_logical_axes(cfg)
            )
        self._param_shardings = param_shardings  # kept for hot-swap resharding
        if params is not None:
            # Externally-supplied weights (checkpoint load): reshard per-leaf.
            self.params = (
                jax.device_put(params, param_shardings) if param_shardings else params
            )
        elif param_shardings is not None:
            # Init DIRECTLY sharded: the whole point of TP serving is a model
            # bigger than one chip's HBM — materializing the full tree on one
            # device before resharding would OOM exactly that model.
            # The key is an ARGUMENT: a seed inside the program would make every seed
            # another program, compiled anew at every start (35 s of a four-chip start,
            # PERF.md section 6, PR 58); as it is the compile cache holds one for all.
            self.params = jax.jit(
                lambda key: init_params(key, cfg), out_shardings=param_shardings,
            )(jax.random.PRNGKey(self.ec.seed))
        else:
            self.params = init_params(jax.random.PRNGKey(self.ec.seed), cfg)
        B = self.ec.max_slots
        P_total = self.ec.total_pages
        self.ppseq = S // ps  # page-table width (max pages per sequence)
        # ``self.cache`` is every rule's pools, one after the other
        # (``rule.sl``); position (page, offset) lives at page*ps + offset.
        # The rule for every program that takes the pools (each donates
        # them): a pool that is CARRIED whole (argument -> loop carry ->
        # result) and updated with dynamic_update_slice, or aliased to a Mosaic
        # call's outputs, is updated in place; a pool, or a layer's slice of
        # one, passed through a scan as xs and taken back as ys is copied,
        # sliced out and stacked back every layer (PERF.md section 6, PR 25 and
        # PR 29), and so is a pool a scan only reads, if its consumer prefers
        # another layout. So decode carries the pools through both its scans
        # with the layer index in xs, and the layer scans of prefill see a
        # prompt's rows and never a pool.
        self.cache = tuple(pool for rule in self.rules for pool in rule.allocate(self.mesh))
        # stats()["startup"]: what each kind's pools take
        self.pool_bytes = {rule.name: sum(pool.nbytes for pool in self.cache[rule.sl]) for rule in self.rules}
        self.free_pages: deque = deque(range(1, P_total))  # page 0 = dead sink
        self.page_tables = np.zeros((B, self.ppseq), np.int32)
        # A mirror is replicated over the mesh whoever wrote it last (a decode
        # block, a per-row update, the host: _mirror), so a program that takes
        # one meets one layout and is compiled once.
        self._replicated = NamedSharding(self.mesh, _P()) if self.mesh is not None else None
        self.d_page_tables = self._mirror(self.page_tables)
        self.lengths = np.zeros(B, np.int32)  # host copy drives scheduling
        # Device-resident mirrors: decode blocks read/advance these without
        # any host->device transfer per step.
        self.d_lengths = self._mirror(self.lengths)
        self.d_last = self._mirror(np.zeros(B, np.int32))
        self.slots: list[Optional[_Slot]] = [None] * B
        # Per-slot sampling params (vLLM-style per-request SamplingParams,
        # llm/sampling.py): host copies set at admission, device mirrors ride
        # into every prefill/decode program as [B] arrays — a mixed batch
        # samples each row under its own request's params.
        self.samp_temps = np.full(B, self.ec.temperature, np.float32)
        self.samp_top_ps = np.ones(B, np.float32)
        self.samp_top_ks = np.zeros(B, np.int32)
        self.d_temps = self._mirror(self.samp_temps)
        self.d_top_ps = self._mirror(self.samp_top_ps)
        self.d_top_ks = self._mirror(self.samp_top_ks)
        self.waiting: deque = deque()
        self._phases = _tracing.PhaseSpans(
            "llm.step", STEP_PHASES, TRACE_RING, annotation=jax.profiler.TraceAnnotation
        )
        self.request_ring = _tracing.Ring(TRACE_RING)
        self.warmup_log: list[dict] = []  # one entry a warmed program: its seconds, and of them each stage's
        self._key = jax.random.PRNGKey(self.ec.seed + 1)
        self._prefill_jit: dict[int, Any] = {}
        self.mosaic: dict[str, bool] = {}  # filled by warmup()
        # Prefix KV cache (_cache_insert): sha1(tokens[:n]) -> {"pages": (...),
        # "prompt_len": n}, LRU-ordered; _page_refs counts the entries a page is in.
        self._prefix_cache: "OrderedDict[bytes, dict]" = OrderedDict()
        self._page_refs: dict[int, int] = {}
        self.prefix_hits = 0
        self.prefix_partial_hits = 0
        self.prefix_misses = 0
        if self.ec.chunked_prefill % ps:
            raise ValueError(
                f"chunked_prefill {self.ec.chunked_prefill} must be a "
                f"multiple of page_size {ps}"
            )
        # Slots mid chunked-prefill: slot index -> full prompt tokens (_masked).
        self._prefilling: dict[int, np.ndarray] = {}
        # Padded rows copy page 0 onto itself (the dead sink) — static [ppseq]
        # shape, one compiled program for any hit size.
        self._copy_pages_jit = jax.jit(self._copy_pages_impl, donate_argnums=(0,))
        # Context-page buckets for the tail-prefill program (partial prefix
        # hits): powers of two up to the page-table width, so the
        # compiled-program count stays |buckets| x log(ppseq).
        self.c_buckets = tuple(sorted({2 ** i for i in range(self.ppseq.bit_length()) if 2 ** i < self.ppseq}
                                      | {self.ppseq}))
        self._tail_jit: dict[tuple, Any] = {}
        self._decode_jit = jax.jit(self._decode_impl, donate_argnums=(1,), static_argnames=("n_steps",))
        # The one-block look-ahead (step()): the block the device was handed
        # last, unfetched; events absorbed outside a step (abort, set_params),
        # which the next step returns; rows retired since the mirrors' last sync.
        self._inflight: Optional[_Block] = None
        self._carry: dict[str, dict] = {}
        self._gone: list[int] = []
        self._drop_rows_jit = jax.jit(self._drop_rows_impl)
        # Page-size multiples only (a prefill writes whole pages).
        self.buckets = bucket_ladder(self.ec.prefill_buckets, ps, S)
        # Prefill group sizes, largest-first: the sizes formed at the shortest
        # bucket (group_sizes says which of them a longer bucket keeps).
        self.k_buckets = (8, 4, 2, 1)
        # Decode block sizes: full, and short under queue pressure (_fit).
        self.block_sizes = tuple(sorted({self.ec.decode_block, max(1, self.ec.decode_block // 4)}))

    # -- page accounting ---------------------------------------------------
    def _pages_needed(self, prompt_len: int, max_tokens: int) -> int:
        # + decode_block: a block may overshoot a slot's budget before the
        # host absorbs it; the slack pages keep those writes inside the
        # request's own reservation.
        total = min(prompt_len + max_tokens + self.ec.decode_block, self.ec.max_seq)
        return math.ceil(total / self.ec.page_size)

    def _mirror(self, host: np.ndarray):
        """A host copy uploaded whole as its device mirror."""
        if self._replicated is None:
            return jnp.asarray(host)
        return jax.device_put(host, self._replicated)

    # -- device-mirror masking (chunked prefill) ---------------------------
    def _masked(self, host: np.ndarray) -> np.ndarray:
        """A host mirror (lengths, page tables) with mid-prefill slots' rows
        zeroed: the decode block must treat them as empty (no page walked,
        no row written) until their final chunk installs the real length."""
        if not self._prefilling:
            return host
        m = host.copy()
        m[list(self._prefilling)] = 0
        return m

    # -- jitted programs ---------------------------------------------------
    def _rule(self, kind):
        """The cache rule of a layer's kind, as ``run_layers`` names it to a scan body."""
        return self.rules[self.cfg.kinds.index(kind)]

    def _rule_pools(self, cache, fn) -> tuple:
        """The pools with each rule's own replaced by ``fn(rule, its pools)``."""
        cache = list(cache)
        for rule in self.rules:
            cache[rule.sl] = fn(rule, cache[rule.sl])
        return tuple(cache)

    def _device_counts(self, of) -> dict:
        """name -> ``of(rule)``'s entry for it, over the rules' ``device_counts``
        in the pools' order; rules of one class count the same thing once."""
        return {name: c for rule in self.rules for name, c in zip(rule.device_counts, of(rule))}

    def _places(self, slots) -> tuple:
        """What a prefill group is told of its requests' ``slots`` [k] beside
        their pages, as the programs' last operand: the place of the first
        rule that wants one (``__init__`` has refused rules whose places
        differ), nothing where none does."""
        for rule in self.rules:
            place = rule.place(slots)
            if place is not None:
                return (jnp.asarray(place),)
        return ()

    def _prompt_layers(self, params, x, scan_fn, ctx=None):
        """A prompt's hidden state through every layer, by ``scan_fn(h, lp,
        kind, *a layer's slice of each of ctx) -> (h, rows)``; ctx: every
        layer's cached context, one array a pool. Returns (x, every layer's
        rows, one array a pool, in the pools' order: [a kind's layers, ...])."""
        xs = None
        if ctx is not None:
            xs = {rule.name: tuple(ctx[rule.sl]) for rule in self.rules}
        x, rows = run_layers(lambda h, lp, kind, _index, *c: scan_fn(h, lp, kind, *c), x, params, self.cfg, xs)
        return x, [r for rule in self.rules for r in rows[rule.name]]

    def _copy_pages_impl(self, cache, src, dst):
        """A prefix-cache hit's pages ``src`` copied onto ``dst`` ([ppseq]
        each). Every source page is read before the first is written: a
        hit's source and target pages never overlap, but padded rows all
        name page 0. A carried, donated pool is updated in place (the rule:
        where the pools are made): compiled for a v5e this program holds
        under a megabyte beside the pools, kv_heads-sharded or on one chip
        (PERF.md section 6, PR 29)."""
        return self._rule_pools(cache, lambda rule, pools: rule.write_pages(
            pools, [rule.read_pages(pool, src) for pool in pools], dst))

    def _prefill_impl(self, params, cache, tokens, length, page_idxs, key, temp, top_p, top_k, place=None):
        """tokens: [P] (padded to the bucket); page_idxs: [P // ps] page ids
        (trailing entries may be 0 = dead sink); place: what ``_places`` tells
        the program of the request's slot. Returns the pools with the
        prompt's pages written and the first generated token. Attention
        runs on the layer's fresh K/V, so the layer scan never sees a pool:
        it hands out every layer's rows as ``ys`` and the pages are written
        once, after it."""
        cfg = self.cfg
        P = tokens.shape[0]
        with jax.named_scope("embed"):
            x = embed_tokens(params, tokens, cfg)[None]  # [1,P,D]
        pos = jnp.arange(P, dtype=jnp.int32)[None]
        seg = (pos >= length).astype(jnp.int32)  # pads = their own segment

        def scan_fn(h, lp, kind):
            h, _aux, rows = decoder_block(h, lp, cfg, pos, self._rule(kind).prompt_attend(lp, seg, length), kind)
            return h, rows

        x, rows = self._prompt_layers(params, x, scan_fn)  # rows[i]: as pool i but the bucket's tokens long
        cache = self._rule_pools(cache, lambda rule, pools: rule.write_prompt(
            pools, rows[rule.sl], page_idxs, place, length))
        with jax.named_scope("lm_head"):
            x = model_norm(x, params["final_norm"], cfg)
            last = jax.lax.dynamic_index_in_dim(x[0], length - 1, axis=0, keepdims=False)
            logits = hidden_logits(params, last, cfg)
        with jax.named_scope("sample"):
            tok = sample_batch(logits.astype(jnp.float32)[None], temp[None], top_p[None],
                               top_k[None], key, cap=self.ec.sample_topk_cap)[0]
        return cache, tok

    def _decode_impl(self, params, cache, last_tokens, lengths, page_tables, key, n_steps, temps, top_ps, top_ks):
        """n_steps tokens for every slot in ONE device program (outer scan
        over steps, inner scan over layers): one host round trip per block.
        Returns (cache, toks [n_steps, B], last', lengths', counts): counts
        is None for a model without held experts whose rules count nothing.
        Held experts give two int32, the routed (token, expert) pairs that
        landed on held experts and the live tiles of the grouped matmul (a
        tile reads its expert's matrices), both summed over the block's steps
        and the routed layers; behind them what the rules count on the device
        (``device_counts``), summed over the steps.

        Both scans CARRY the pools whole (the rule: where the pools are
        made), each in its rule's ``in_pages`` shape; the layer scan's ``xs``
        are the layer's weights and its index. The one Mosaic call of a layer
        is told the layer by an operand and writes each slot's new row itself,
        into the pool its outputs alias: no other operation of this program
        has a pool, or a layer's slice of one, for operand or result."""
        cfg = self.cfg
        flat = [pool.shape for pool in cache]  # as every other program has them
        paged = [rule.in_pages(shape) for rule in self.rules for shape in flat[rule.sl]]
        walkers = {rule.walk_key: rule for rule in self.rules if rule.walk_key is not None}

        def one_step(carry, step_key):
            pools, last, lens = carry
            seen = lens + 1  # the kernel's lengths count the step's own token
            on_tpu = jax.default_backend() == "tpu"
            # 0 for a slot with no pages (empty, or masked while it
            # prefills): no grid step, no row written, zeros attended.
            seen = jnp.where(page_tables[:, 0] > 0, seen, 0)
            # The step's walks of live pages, built here, once for all layers
            # (lengths change between steps and not between layers), one for
            # the rules that share a key; None where no kernel runs.
            walks = {key: walkers[key].walk(seen, page_tables) for key in sorted(walkers)} if on_tpu else None
            with jax.named_scope("embed"):
                x = embed_tokens(params, last, cfg)[:, None, :]  # [B,1,D]

            def scan_fn(carry, lp, kind, layer):
                h, pools = carry
                rule = self._rule(kind)
                h, aux, kept = decoder_block(
                    h, lp, cfg, lens[:, None],
                    rule.decode_attend(lp, pools[rule.sl], seen, page_tables, layer, walks), kind)
                pools = pools[:rule.sl.start] + tuple(kept) + pools[rule.sl.stop:]
                return (h, pools), (aux if cfg.experts_held and "router" in lp else None)

            (x, pools), auxes = run_layers(scan_fn, (x, pools), params, cfg)
            counts = None
            for aux in auxes.values():  # of a kind's routed layers; None where it has none
                if aux is not None:
                    counts = jnp.sum(aux, axis=0) if counts is None else counts + jnp.sum(aux, axis=0)
            for rows in self._device_counts(lambda rule: rule.step_counts(seen)).values():
                counts = rows if counts is None else jnp.concatenate([counts, rows])
            with jax.named_scope("lm_head"):
                x = model_norm(x, params["final_norm"], cfg)
                logits = hidden_logits(params, x[:, 0], cfg)
            with jax.named_scope("sample"):
                toks = sample_batch(logits.astype(jnp.float32), temps, top_ps, top_ks,
                                    step_key, cap=self.ec.sample_topk_cap)
            # A slot with no pages stays at its length (the kernel walks
            # nothing for it, whatever the mirrors hold until a resync).
            return (pools, toks, jnp.where(page_tables[:, 0] > 0, lens + 1, lens)), (toks, counts)

        keys = jax.random.split(key, n_steps)
        # A slot with no pages is a greedy row whatever its last request asked
        # for (its token is thrown away): the sampler draws only where a live
        # slot samples, which is what the step record's ``sampled`` counts.
        temps = jnp.where(page_tables[:, 0] > 0, temps, 0.0)
        pools = tuple(pool.reshape(shape) for pool, shape in zip(cache, paged))
        (pools, last, lengths), (toks, counts) = jax.lax.scan(one_step, (pools, last_tokens, lengths), keys)
        if counts is not None:
            counts = jnp.sum(counts, axis=0)
        return tuple(pool.reshape(shape) for pool, shape in zip(pools, flat)), toks, last, lengths, counts

    def _prefill_batch_impl(self, params, cache, tokens, lengths, page_rows, key, temps, top_ps, top_ks,
                            places=None):
        """Prefill k requests of one length bucket in ONE device program
        (scan over requests around the single-request body): one dispatch
        and one set of host-built arrays per admitted group instead of one
        per request. On a directly attached chip an awaited dispatch costs
        0.55-0.61 ms (PERF.md section 6, bring-up), little beside a prompt's
        prefill, so the group saves host work (``prefill_dispatch``), not
        device time: the k requests run one after another and each reads
        the weights. The request scan carries the donated pools; each
        request writes its rows into them in place (``write_prompt``).
        tokens: [k, P]; page_rows: [k, P // ps], each request's pages; places:
        [k], what ``_places`` tells of each request's slot; None: nothing."""
        keys = jax.random.split(key, tokens.shape[0])

        def scan_req(cache, xs):
            return self._prefill_impl(params, cache, *xs)

        xs = (tokens, lengths, page_rows, keys, temps, top_ps, top_ks)
        return jax.lax.scan(  # (cache, toks [k])
            scan_req, cache, xs if places is None else (*xs, places))

    def _tail_prefill_impl(self, params, cache, tokens, start, length,
                           ctx_pages, tail_pages, key, temp, top_p, top_k):
        """Chunked prefill over a cached prefix (partial-prefix KV reuse):
        the prompt's first `start` tokens (page-aligned) already sit in this
        request's pages, copied from the prefix cache; only the tail is
        embedded and projected here. The tail's rows scatter into the request's
        remaining pages; queries attend to the cached context pages
        (gathered from the pool) plus causally to the tail itself, so the
        sampled first token is that of a cold full prefill (in f32; in bf16
        this is einsum attention where the cold prefill runs the flash
        kernel — not compared on the chip) while prefill compute scales
        with the tail length. Only a rule that keeps pages has the three
        methods this program calls.

        tokens: [Tb] padded tail; start/length: scalars (start page-aligned);
        ctx_pages: [C] context page ids (trailing 0 = dead, masked by
        position < start); tail_pages: [Tb//ps] (trailing 0 = dead sink)."""
        cfg = self.cfg
        ps = self.ec.page_size
        Tb = tokens.shape[0]
        C = ctx_pages.shape[0]
        x = embed_tokens(params, tokens, cfg)[None]  # [1,Tb,D]
        tpos = jnp.arange(Tb, dtype=jnp.int32)
        pos = (start + tpos)[None]  # [1,Tb] absolute positions
        # Key-validity mask [Tb, C*ps + Tb]: context keys are valid iff
        # their absolute position < start (cached region; always <= any
        # query position); tail keys are causal within the tail and must be
        # real (not padding past the prompt length).
        ctx_mask = jnp.broadcast_to(
            (jnp.arange(C * ps, dtype=jnp.int32) < start)[None, :], (Tb, C * ps)
        )
        tail_mask = (tpos[None, :] <= tpos[:, None]) & ((start + tpos)[None, :] < length)
        mask = jnp.concatenate([ctx_mask, tail_mask], axis=1)
        def scan_fn(h, lp, kind, *ctx):
            h, _aux, rows = decoder_block(h, lp, cfg, pos, self._rule(kind).tail_attend(lp, mask, *ctx), kind)
            return h, rows

        # The cached context of every layer, gathered once from the whole
        # pools, so that the layer scan sees a prompt's rows and never a pool.
        x, rows = self._prompt_layers(params, x, scan_fn, [
            rule.read_pages(pool, ctx_pages) for rule in self.rules for pool in cache[rule.sl]])
        # The gather above reads positions < start and this lands on the
        # tail's pages, so the write can follow the scan.
        cache = self._rule_pools(cache, lambda rule, pools: rule.write_pages(pools, rows[rule.sl], tail_pages))
        x = model_norm(x, params["final_norm"], cfg)
        last = jax.lax.dynamic_index_in_dim(x[0], length - 1 - start, axis=0, keepdims=False)
        logits = hidden_logits(params, last, cfg)
        toks = sample_batch(logits.astype(jnp.float32)[None], temp, top_p, top_k, key,
                            cap=self.ec.sample_topk_cap)
        return cache, toks  # toks: [1]

    def _tail_prefill(self, tail_bucket: int, n_ctx: int):
        fn = self._tail_jit.get((tail_bucket, n_ctx))
        if fn is None:
            fn = self._tail_jit[(tail_bucket, n_ctx)] = jax.jit(
                self._tail_prefill_impl, donate_argnums=(1,)
            )
        return fn

    def _dispatch_tail(self, i: int, tail, start: int, length: int):
        """Enqueue the tail-prefill program for slot ``i``: ``tail`` are the
        prompt's tokens from position ``start`` on (page-aligned; all before
        it already sits in the slot's pages), masked at ``length``. Tail and
        context sizes snap to buckets, one compiled program per
        (tail_bucket, ctx_bucket). Returns (the sampled token [1], still on
        the device; the tail bucket)."""
        ps = self.ec.page_size
        tb = self._bucket_for(len(tail))
        j = start // ps
        C = next(c for c in self.c_buckets if c >= max(j, 1))
        padded = np.zeros(tb, np.int32)
        padded[: len(tail)] = tail
        ctx = np.zeros(C, np.int32)
        ctx[:j] = self.page_tables[i, :j]
        n_tpg = tb // ps
        tpg = np.zeros(n_tpg, np.int32)
        m = min(n_tpg, self.ppseq - j)
        tpg[:m] = self.page_tables[i, j:j + m]  # zeros past need -> dead sink
        self._key, sub = jax.random.split(self._key)
        self.cache, toks_dev = self._tail_prefill(tb, C)(
            self.params, self.cache,
            jnp.asarray(padded), jnp.int32(start), jnp.int32(length),
            jnp.asarray(ctx), jnp.asarray(tpg), sub,
            jnp.asarray(self.samp_temps[i:i + 1]),
            jnp.asarray(self.samp_top_ps[i:i + 1]),
            jnp.asarray(self.samp_top_ks[i:i + 1]),
        )
        return toks_dev, tb

    def _bucket_for(self, n_tokens: int) -> int:
        """The bucket that many tokens are padded to: the shortest that holds them."""
        return next(b for b in self.buckets if b >= n_tokens)

    def _prefill(self, bucket: int, k: int):
        fn = self._prefill_jit.get((bucket, k))
        if fn is None:
            fn = self._prefill_jit[(bucket, k)] = jax.jit(
                self._prefill_batch_impl, donate_argnums=(1,)
            )
        return fn

    def group_sizes(self, bucket: int) -> tuple:
        """The group sizes a prefill of this bucket is dispatched in, largest
        first: those of k_buckets whose group holds at most GROUP_TOKENS
        tokens, and 1. What warmup compiles and what step forms both come
        from here."""
        return tuple(k for k in self.k_buckets if k == 1 or k * bucket <= GROUP_TOKENS)

    def warmup(self, buckets=None, k_values=None):
        """Compile every (bucket, k) prefill program step can dispatch and both
        decode block sizes before serving (the vLLM-style startup warmup): a
        cold compile costs seconds and would otherwise land inside the first
        loaded requests' TTFT. Executes each program once against the dead
        page (page 0), then resets the device mirrors it dirtied. ``buckets``
        names lengths (a deployment's configured buckets, a raw prompt
        length): every engine bucket from the one the shortest of them pads
        to up to the one the longest pads to is warmed, the rungs between
        them too, each at its ``group_sizes`` (those in ``k_values``, if given).

        Also records, once, in ``self.mosaic`` whether the first prefill
        program and the full decode block, compiled, hold a Mosaic custom
        call: how a caller tells that the Pallas kernels run and not the jnp
        references (off the TPU they cannot). The first prefill entry and
        each decode entry of ``warmup_log`` carry ``temp_bytes``, what the
        compiled program holds on the device beside its arguments, on any
        backend: a program that moved the KV pools would need room for them
        there. Every entry carries, beside its ``seconds``, ``t`` (its start
        on time.monotonic()) and ``WARMUP_STAGES``: what JAX reported inside
        those seconds of tracing, lowering and its backend, with the
        executables started and how many of them the compile cache did not
        hold. A prefill entry brackets its program and its per-k mirror
        updates, a decode entry the compile ahead AND the call."""
        if buckets is None:
            buckets = self.buckets
        else:
            # Snap the caller's shortest and longest length to the buckets
            # admit selects for them, and warm those and every bucket between:
            # a prompt between two named lengths may pad to a rung the caller
            # cannot name, and a cold one would compile inside a request's TTFT.
            lo, hi = (self._bucket_for(min(x, self.buckets[-1])) for x in (min(buckets), max(buckets)))
            buckets = tuple(b for b in self.buckets if lo <= b <= hi)
        ps = self.ec.page_size
        key = jax.random.PRNGKey(0)
        on_tpu = jax.default_backend() == "tpu"

        def compiled_ahead(jitted, args):
            # Ahead of the jitted call, which then starts nothing: on the chip a decode entry,
            # bracketed over both, reads ONE lowering and ONE executable (PERF.md section 5, PR 57).
            return jitted.lower(*args).compile()

        def holds_mosaic(compiled) -> bool:
            return "tpu_custom_call" in compiled.as_text()

        log = self.warmup_log

        def note(entry, t0, before):
            # An entry's start and seconds and, of the seconds, what JAX reported of each stage between the
            # two readings (accel/device.compile_stages): Python over the program's body, jaxpr to MLIR,
            # the backend (a compile, or the cache's read and its load). The rest is the program's first
            # run, its arrays and its fetch.
            after = compile_stages()
            log.append({**entry, "t": t0, "seconds": time.monotonic() - t0,
                        **{key: after[key] - before[key] for key in WARMUP_STAGES}})

        for b in buckets:
            for k in (k for k in self.group_sizes(b) if k_values is None or k in k_values):
                t0, before = time.monotonic(), compile_stages()
                toks = jnp.zeros((k, b), jnp.int32)
                lens = jnp.ones(k, jnp.int32)
                page_rows = jnp.zeros((k, b // ps), jnp.int32)  # writes -> dead page
                args = (
                    self.params, self.cache, toks, lens, page_rows, key,
                    jnp.zeros(k, jnp.float32), jnp.ones(k, jnp.float32),
                    jnp.zeros(k, jnp.int32),
                )
                # slot 0 takes the dummy rows: a length masks whatever its ring held before, and the
                # prefill of its next request replaces its state and tail
                args += self._places(np.zeros(k, np.int32))
                entry = {"program": "prefill", "bucket": b, "k": k}
                if "prefill" not in self.mosaic:
                    # The first program only, which is all ``mosaic`` and ``temp_bytes`` need. (It was kept to
                    # one for fear of a second lowering: the record shows none, jax 0.9.0, PERF.md section 5.)
                    compiled = compiled_ahead(self._prefill(b, k), args)
                    self.mosaic["prefill"] = on_tpu and holds_mosaic(compiled)
                    entry["temp_bytes"] = compiled.memory_analysis().temp_size_in_bytes
                self.cache, td = self._prefill(b, k)(*args)
                # The admit path's per-group mirror updates are their own tiny
                # jitted programs, one shape variant per k — compile them here
                # too or they land in the first loaded step's TTFT.
                idxs = jnp.zeros(k, jnp.int32)
                self.d_lengths = self.d_lengths.at[idxs].set(lens)
                self.d_last = self.d_last.at[idxs].set(td)
                jax.device_get(td)
                note(entry, t0, before)
        for n in self.block_sizes:
            t0, before = time.monotonic(), compile_stages()
            args = (self.params, self.cache, self.d_last, self.d_lengths,
                    self.d_page_tables, key, n, self.d_temps, self.d_top_ps, self.d_top_ks)
            compiled = compiled_ahead(self._decode_jit, args)
            temp_bytes = compiled.memory_analysis().temp_size_in_bytes
            if n == self.block_sizes[-1]:
                self.mosaic["decode"] = on_tpu and holds_mosaic(compiled)
            out = self._decode_jit(*args)
            self.cache = out[0]
            jax.device_get(out[1])
            note({"program": "decode", "block": n, "temp_bytes": temp_bytes}, t0, before)
        # A retire's per-row write of the mirrors, against the lengths as a
        # decode block leaves them and as the host wrote them (one layout,
        # _mirror, so one compile), and the step's key split.
        t0, before = time.monotonic(), compile_stages()
        nobody = jnp.zeros(self.ec.max_slots, bool)
        for lens in (out[3], self.d_lengths):
            jax.block_until_ready(self._drop_rows_jit(lens, self.d_page_tables, nobody))
        _key, _sub = jax.random.split(self._key)  # split and unpacked, as a dispatch does
        note({"program": "drop_rows"}, t0, before)
        if self.ec.prefix_cache:
            # Compile the prefix-cache page copy (padded rows hit page 0).
            t0, before = time.monotonic(), compile_stages()
            z = jnp.zeros(self.ppseq, jnp.int32)
            self.cache = self._copy_pages_jit(self.cache, z, z)
            jax.block_until_ready(self.cache)
            note({"program": "copy_pages"}, t0, before)
        # Reset device mirrors dirtied by the dummy executions.
        self.d_lengths = self._mirror(self.lengths)
        self.d_last = self._mirror(np.zeros(self.ec.max_slots, np.int32))

    # -- request lifecycle -------------------------------------------------
    def add_request(self, req_id: str, tokens, max_tokens: int = 64,
                    sampling: SamplingParams | None = None):
        """Queue a request. `sampling` carries the per-request decode params
        (temperature/top_p/top_k/max_tokens/stop_token_ids); without it the
        engine-global defaults (EngineConfig.temperature, greedy top) apply.

        Returns the request's lifecycle record: one stamp an event, on
        time.monotonic(), each written where the event happens. This engine
        writes arrived, admitted (with slot, prefix_hit_len, bucket),
        prefill_enqueued (the call that enqueues the request's prefill
        program has returned: of a chunked prompt its last chunk's; None for
        an exact prefix hit, which has none), first_token (the token is on
        the host), finished (the slot retired; with n_out, finish_reason)
        and then pushes the record to
        request_ring; a serving loop around the engine writes first_emitted
        and first_yielded into the same dict. `trace` is the caller's active
        trace context or None: the one ContextVar.get an untraced request
        pays (util/tracing's cost contract)."""
        if sampling is None:
            sampling = SamplingParams(
                temperature=self.ec.temperature, max_tokens=max_tokens
            )
        if len(tokens) >= self.ec.max_seq:
            raise ValueError(f"prompt length {len(tokens)} >= max_seq {self.ec.max_seq}")
        need = self._pages_needed(len(tokens), sampling.max_tokens)
        if need > self.ec.total_pages - 1:
            raise ValueError(
                f"request needs {need} pages > pool size {self.ec.total_pages - 1}"
            )
        life = {
            "req_id": req_id, "arrived": time.monotonic(), "admitted": None, "slot": None,
            "prompt_len": len(tokens), "prefix_hit_len": None, "bucket": None,
            "prefill_enqueued": None, "first_token": None, "first_emitted": None, "first_yielded": None,
            "finished": None, "n_out": None, "finish_reason": None,
            "trace": _tracing.current_trace(),
        }
        self.waiting.append(
            (req_id, np.asarray(tokens, np.int32), sampling, time.perf_counter(), life)
        )
        return life

    def _close_life(self, life: dict, n_out: int, reason: str) -> None:
        life["finished"] = time.monotonic()
        life["n_out"] = n_out
        life["finish_reason"] = reason
        self.request_ring.push(life)

    def set_params(self, params) -> None:
        """In-place weight hot-swap (ckpt publication plane): reshard the
        new tree onto this engine's layout and flip the pointer. The caller
        must exclude step() for the duration (LLMServer holds its swap
        lock), so an in-flight batch finishes entirely on the old weights
        and the next step reads entirely the new — never a mix: a block the
        device still holds is absorbed here (its events ride the next
        step's). KV cache is kept: a fine-tuned refresh of the same model
        keeps generating coherently; swapping an unrelated model needs a
        redeploy."""
        self._absorb_outside_step()
        self.params = (
            jax.device_put(params, self._param_shardings)
            if self._param_shardings else jax.device_put(params)
        )

    def abort(self, req_id: str) -> None:
        """Drop a request whose consumer went away: dequeue it, or free its
        slot so decode stops spending steps on it (a block in flight is
        absorbed first: the request's count is of tokens the host has seen).
        Call from the stepping thread only (mutates scheduler state + device
        mirrors)."""
        for w in self.waiting:
            if w[0] == req_id:
                self._close_life(w[4], 0, "abort")
        self.waiting = deque(w for w in self.waiting if w[0] != req_id)
        if not any(s is not None and s.req_id == req_id for s in self.slots):
            return
        self._absorb_outside_step()
        for i, s in enumerate(self.slots):  # looked up again: the absorbed block may have ended it
            if s is not None and s.req_id == req_id:
                self._close_life(s.life, len(s.emitted), "abort")
                self._retire(i)
                break
        self._sync_retired()
        self._carry.pop(req_id, None)  # nobody reads what the absorbed block held of it

    def has_work(self) -> bool:
        """True while a request waits or holds a slot, and while a block's
        tokens or absorbed events have not been returned by a step."""
        return (bool(self.waiting) or any(s is not None for s in self.slots)
                or self._inflight is not None or bool(self._carry))

    def _prefix_digests(self, tokens) -> list:
        """(covered_len, digest) pairs for every page-aligned prefix of the
        prompt plus the full prompt — one incremental sha1 pass. Ascending;
        lookups probe in reverse (longest first)."""
        ps = self.ec.page_size
        buf = np.ascontiguousarray(tokens, dtype=np.int32)
        h = hashlib.sha1()
        out = []
        j = 0
        while (j + 1) * ps <= len(buf):
            h.update(buf[j * ps:(j + 1) * ps].tobytes())
            j += 1
            out.append((j * ps, h.copy().digest()))
        if len(buf) % ps:
            h.update(buf[j * ps:].tobytes())
            out.append((len(buf), h.digest()))
        return out

    def _cache_insert(self, slot: _Slot) -> set:
        """Move this retired slot's prompt pages into the prefix cache: one
        entry per page-aligned prefix plus the full prompt (chained
        digests), sharing + refcounting the pages. When a shorter prefix is
        ALREADY cached (the common partial-hit retire), new longer entries
        reference the existing entry's pages for the shared region — the
        slot's own byte-identical copies of those pages are freed, so N
        requests extending one system prompt do not hold N copies of it.
        Returns the slot pages the cache now owns; the caller frees the
        rest."""
        ps = self.ec.page_size
        slot_pages = set(slot.pages)
        used: set = set()
        base: tuple = ()  # longest already-cached page run for this prefix
        for n, dg in self._prefix_digests(slot.prompt_tokens):
            n_pg = -(-n // ps)
            if n_pg > len(slot.pages):
                break
            existing = self._prefix_cache.get(dg)
            if existing is not None:
                self._prefix_cache.move_to_end(dg)
                if len(existing["pages"]) >= len(base):
                    base = tuple(existing["pages"])
                continue
            pages = base + tuple(slot.pages[len(base):n_pg])
            self._prefix_cache[dg] = {"pages": pages, "prompt_len": n}
            for p in pages:
                self._page_refs[p] = self._page_refs.get(p, 0) + 1
            used.update(pages)
            base = pages
        return used & slot_pages

    def _retire(self, i: int) -> None:
        """Free slot i's pages and zero its table row (dead slots must write
        only into page 0 while they keep decoding inside a block). With the
        prefix cache on, an uncached prompt's pages MOVE into the cache
        instead of the free list."""
        slot = self.slots[i]
        if slot is not None:
            kept: set = set()
            if slot.prompt_tokens is not None and i not in self._prefilling:
                # A half-prefilled prompt never enters the prefix cache: its
                # later pages hold no KV yet.
                kept = self._cache_insert(slot)
            self.free_pages.extend(p for p in slot.pages if p not in kept)
        self._prefilling.pop(i, None)
        self.slots[i] = None
        self.lengths[i] = 0
        self.page_tables[i, :] = 0
        self._gone.append(i)

    def _evict_prefix_cache(self, need_pages: int, protect: frozenset = frozenset()) -> None:
        """LRU-evict cache entries until need_pages pages are back in the
        free list (admission pressure beats cached prefixes). A page shared
        by several chain entries frees only when its LAST referencing entry
        goes. `protect` exempts the entry the current admission is about to
        hit — evict-before-lookup used to let a request evict its own
        cached prefix to fund its allocation."""
        while need_pages > 0:
            victim = next((k for k in self._prefix_cache if k not in protect), None)
            if victim is None:
                return
            entry = self._prefix_cache.pop(victim)
            for p in entry["pages"]:
                self._page_refs[p] -= 1
                if not self._page_refs[p]:
                    del self._page_refs[p]
                    self.free_pages.append(p)
                    need_pages -= 1

    @property
    def prefix_cache_stats(self) -> dict:
        return {
            "hits": self.prefix_hits,
            "partial_hits": self.prefix_partial_hits,
            "misses": self.prefix_misses,
            "entries": len(self._prefix_cache),
            "cached_pages": len(self._page_refs),  # distinct pages held
        }

    def trace_snapshot(self) -> dict:
        """What the program recorded of itself, for LLMServer.stats(): the
        finished requests' lifecycle records and the ended steps' phase
        records still in their rings (stamps on time.monotonic()), the
        cumulative seconds and entries of each phase, the pages a step's
        pages_reserved is a share of, and what the rings dropped.
        Reads only, takes no lock; any thread may call it."""
        steps = self._phases
        return {
            "clock": "monotonic", "now": time.monotonic(),
            "requests": self.request_ring.snapshot(), "requests_total": self.request_ring.total,
            "steps": steps.ring.snapshot(), "steps_total": steps.ring.total,
            "phase_s": dict(steps.phase_s), "phase_n": dict(steps.phase_n),
            "pages_total": self.ec.total_pages - 1,  # page 0 is the dead sink
            "dropped": {"requests": self.request_ring.dropped, "steps": steps.ring.dropped},
        }

    def step(self) -> dict:
        """One engine iteration: admit waiting requests into free slots +
        free pages (prefill, grouped by length bucket, groups dispatched
        async), enqueue one decode block for all slots, fetch the prefill
        groups in order (first tokens), then fetch and walk the decode block
        the step BEFORE enqueued: the device runs this step's block while
        the host does that, returns, and prepares the next step. So a decode
        token is returned by the step after the one that dispatched it, and
        the step that returns a request's last event may leave a block on
        the device (``has_work`` stays true until a step has walked it).
        Returns {req_id: {"token": int, "new_tokens": [...], "finished":
        bool, "ttft_s": float|None, "tokens": [..] when done}}.

        The step's seams are phase spans (STEP_PHASES; util/tracing.PhaseSpans):
        each `ph.to(...)` below ends one phase and starts the next, and the
        step's record goes to the ring that LLMServer.stats() returns."""
        ph = self._phases
        ph.begin("admit", waiting=len(self.waiting), n_admitted=0, n_prefill=0, prefill_tokens=0, prefill_padded=0,
                 pages_reserved=0, block=0, ahead=0, dropped_rows=0, active=0, sampled=0, live_pages=0, grid_steps=0,
                 expert_pairs=0, expert_tiles=0, **{key: 0 for rule in self.rules for key in rule.zeroes})
        try:
            return self._step(ph)
        finally:
            ph.end()

    def _step(self, ph) -> dict:
        events, self._carry = self._carry, {}  # what abort / set_params absorbed since the last step
        ps = self.ec.page_size
        # 1. admit: page-budgeted assignment of waiting requests to free slots.
        admitted: list[tuple[int, str, np.ndarray, int, int, float]] = []
        cache_hits: list[tuple[int, int]] = []  # (slot, last prompt token)
        tail_admitted: list[tuple[int, str, np.ndarray, int, int, float]] = []
        use_cache = self.ec.prefix_cache
        use_chunked = self.ec.chunked_prefill > 0
        chunk_size = self.ec.chunked_prefill
        for i in range(self.ec.max_slots):
            if not self.waiting or self.slots[i] is not None:
                continue
            req_id, tokens, sp, arrived, life = self.waiting[0]
            P = len(tokens)
            need = self._pages_needed(P, sp.max_tokens)
            # Cache lookup BEFORE eviction: longest match first — the full
            # prompt (exact hit, no prefill at all), then page-aligned
            # prefixes descending (partial hit, tail prefill only).
            hit_dg = hit_entry = None
            hit_len = 0
            if use_cache:
                ph.to("prefix_lookup")
                for n, dg in reversed(self._prefix_digests(tokens)):
                    e = self._prefix_cache.get(dg)
                    if e is not None and e["prompt_len"] == n and (n == P or n % ps == 0):
                        hit_dg, hit_entry, hit_len = dg, e, n
                        break
                ph.to("admit")
            if need > len(self.free_pages):
                self._evict_prefix_cache(
                    need - len(self.free_pages),
                    protect=frozenset((hit_dg,)) if hit_dg is not None else frozenset(),
                )
            if need > len(self.free_pages):
                # Protected-entry corner: if nothing is running (no retire
                # will ever free pages) and the only reclaimable pages are
                # the would-be hit's own, degrade to a miss rather than
                # livelock the queue.
                if hit_dg is not None and not any(s is not None for s in self.slots):
                    hit_dg = hit_entry = None
                    self._evict_prefix_cache(need - len(self.free_pages))
                if need > len(self.free_pages):
                    break  # head-of-line blocks until pages free (FIFO fairness)
            self.waiting.popleft()
            life["admitted"] = time.monotonic()
            life["slot"] = i
            life["prefix_hit_len"] = hit_len if hit_entry is not None else 0
            ph.rec["n_admitted"] += 1
            pages = [self.free_pages.popleft() for _ in range(need)]
            exact = hit_entry is not None and hit_len == P
            self.slots[i] = _Slot(
                req_id=req_id, max_tokens=sp.max_tokens, pages=pages,
                n_generated=0 if exact else 1, arrived_at=arrived,
                stop_ids=tuple(sp.stop_token_ids), ignore_eos=sp.ignore_eos,
                prompt_tokens=(
                    np.asarray(tokens, np.int32) if (use_cache and not exact) else None
                ),
                prompt_len=P, life=life,
            )
            self.samp_temps[i] = sp.temperature
            self.samp_top_ps[i] = sp.top_p
            self.samp_top_ks[i] = sp.top_k
            row = np.zeros(self.ppseq, np.int32)
            row[: len(pages)] = pages
            self.page_tables[i] = row
            if hit_entry is not None:
                # Copy the matched pages into this request's own pages. The
                # copy happens INLINE, before the next admission can
                # LRU-evict this entry and recycle its pages (same-step
                # evict-after-claim would otherwise read pages already back
                # on the free list).
                self._prefix_cache.move_to_end(hit_dg)
                n_pp = len(hit_entry["pages"])
                src = np.zeros(self.ppseq, np.int32)
                src[:n_pp] = hit_entry["pages"]
                dst = np.zeros(self.ppseq, np.int32)
                dst[:n_pp] = pages[:n_pp]
                ph.to("prefill_dispatch")  # the copy stands where a prefill would
                self.cache = self._copy_pages_jit(self.cache, jnp.asarray(src), jnp.asarray(dst))
                ph.to("admit")
                if exact:
                    # Decode from position P-1: the block re-derives that
                    # position's KV and emits the first token — no prefill.
                    self.prefix_hits += 1
                    self.lengths[i] = P - 1
                    cache_hits.append((i, int(tokens[-1])))
                elif use_chunked and P - hit_len > chunk_size:
                    # Partial hit with a long tail: chunk the tail too —
                    # progress starts at the cached (page-aligned) prefix.
                    self.prefix_partial_hits += 1
                    self.lengths[i] = P
                    self.slots[i].n_generated = 0
                    self.slots[i].prefill_pos = hit_len
                    self._prefilling[i] = np.asarray(tokens, np.int32)
                else:
                    # Partial hit: prefill only the tail over the cached
                    # context (dispatched with the prefill groups below).
                    self.prefix_partial_hits += 1
                    self.lengths[i] = P
                    tail_admitted.append((i, req_id, tokens, hit_len, sp.max_tokens, arrived))
            elif use_chunked and P > chunk_size:
                # Chunked prefill: ONE chunk per step, interleaved with the
                # decode blocks (phase 2c) — a long prompt can no longer
                # stall every decoding slot for its whole prefill.
                if use_cache:
                    self.prefix_misses += 1
                self.lengths[i] = P
                self.slots[i].n_generated = 0
                self.slots[i].prefill_pos = 0
                self._prefilling[i] = np.asarray(tokens, np.int32)
            else:
                if use_cache:
                    self.prefix_misses += 1
                self.lengths[i] = P
                bucket = life["bucket"] = self._bucket_for(P)
                admitted.append((i, req_id, tokens, bucket, sp.max_tokens, arrived))
        # the pages slots hold once the step has admitted: what is neither free nor the prefix cache's
        ph.rec["pages_reserved"] = self.ec.total_pages - 1 - len(self.free_pages) - len(self._page_refs)
        if cache_hits:
            ph.to("mirror_sync")
            idx = jnp.asarray(np.array([h[0] for h in cache_hits], np.int32))
            self.d_lengths = self.d_lengths.at[idx].set(
                jnp.asarray(np.array([self.lengths[h[0]] for h in cache_hits], np.int32))
            )
            self.d_last = self.d_last.at[idx].set(
                jnp.asarray(np.array([h[1] for h in cache_hits], np.int32))
            )
        # 2. dispatch prefill groups back-to-back (async), fetch in order so
        # each group's TTFT is its own completion time.
        if admitted or tail_admitted or self._prefilling:
            ph.to("prefill_dispatch")
        by_bucket: dict[int, list] = {}
        for item in admitted:
            by_bucket.setdefault(item[3], []).append(item)
        dispatched: list[tuple[list, Any]] = []  # (chunk, toks_dev)
        for bucket, group in by_bucket.items():
            n_pg = bucket // ps
            while group:
                k = next(kb for kb in self.group_sizes(bucket) if kb <= len(group))
                chunk, group = group[:k], group[k:]
                idxs = [it[0] for it in chunk]
                padded = np.zeros((k, bucket), np.int32)
                lens = np.zeros(k, np.int32)
                pgs = np.zeros((k, n_pg), np.int32)
                for j, (i, _rid, tokens, _b, _mt, _arr) in enumerate(chunk):
                    padded[j, : len(tokens)] = tokens
                    lens[j] = len(tokens)
                    pgs[j] = self.page_tables[i, :n_pg]  # trailing zeros -> dead sink
                idx_arr = jnp.asarray(np.asarray(idxs, np.int32))
                self._key, sub = jax.random.split(self._key)
                self.cache, toks_dev = self._prefill(bucket, k)(
                    self.params, self.cache,
                    jnp.asarray(padded), jnp.asarray(lens), jnp.asarray(pgs), sub,
                    jnp.asarray(self.samp_temps[idxs]),
                    jnp.asarray(self.samp_top_ps[idxs]),
                    jnp.asarray(self.samp_top_ks[idxs]),
                    *self._places(np.asarray(idxs, np.int32)),
                )
                enqueued = time.monotonic()
                for i in idxs:
                    self.slots[i].life["prefill_enqueued"] = enqueued
                ph.to("mirror_sync")
                self.d_lengths = self.d_lengths.at[idx_arr].set(jnp.asarray(lens))
                self.d_last = self.d_last.at[idx_arr].set(toks_dev)
                ph.to("prefill_dispatch")
                ph.rec["n_prefill"] += 1
                ph.rec["prefill_tokens"] += int(lens.sum())
                ph.rec["prefill_padded"] += k * bucket
                dispatched.append((chunk, toks_dev))
        # Partial-prefix hits: per-request tail prefill over the cached
        # context pages.
        for (i, req_id, tokens, start, _mt, arrived) in tail_admitted:
            P = len(tokens)
            life = self.slots[i].life
            toks_dev, life["bucket"] = self._dispatch_tail(i, tokens[start:], start, P)
            life["prefill_enqueued"] = time.monotonic()
            ph.to("mirror_sync")
            self.d_lengths = self.d_lengths.at[i].set(P)
            self.d_last = self.d_last.at[i].set(toks_dev[0])
            ph.to("prefill_dispatch")
            ph.rec["n_prefill"] += 1
            dispatched.append(([(i, req_id, tokens, None, _mt, arrived)], toks_dev))
        # 2c. chunked prefill: advance every mid-prefill slot by ONE chunk —
        # at most one chunk of prefill compute PER IN-FLIGHT PREFILL between
        # consecutive decode blocks (a burst of N long prompts stalls decode N
        # chunks per step: bounded and spread, vs N whole prompts back to
        # back). The final chunk samples the request's first token and
        # installs the slot's device mirrors (_masked: zeroed until then).
        chunk_dispatched = bool(self._prefilling)
        for i in sorted(self._prefilling):
            slot = self.slots[i]
            tokens = self._prefilling[i]
            P = len(tokens)
            start = slot.prefill_pos
            n_tok = min(chunk_size, P - start)
            last_chunk = start + n_tok >= P
            # Intermediate chunks mask at the chunk's end (all its tokens are
            # real); the last chunk masks at the true prompt length and its
            # sampled token is the request's first.
            length = P if last_chunk else start + n_tok
            toks_dev, _tb = self._dispatch_tail(i, tokens[start:start + n_tok], start, length)
            ph.rec["n_prefill"] += 1
            if last_chunk:
                slot.life["prefill_enqueued"] = time.monotonic()
                del self._prefilling[i]
                slot.prefill_pos = P
                slot.n_generated = 1
                ph.to("mirror_sync")
                self.d_lengths = self.d_lengths.at[i].set(P)
                self.d_last = self.d_last.at[i].set(toks_dev[0])
                ph.to("prefill_dispatch")
                dispatched.append(
                    ([(i, slot.req_id, tokens, None, slot.max_tokens,
                       slot.arrived_at)], toks_dev))
            else:
                slot.prefill_pos = start + n_tok
        if admitted or cache_hits or tail_admitted or chunk_dispatched:
            ph.to("mirror_sync")
            self.d_page_tables = self._mirror(self._masked(self.page_tables))
            self.d_temps = self._mirror(self.samp_temps)
            self.d_top_ps = self._mirror(self.samp_top_ps)
            self.d_top_ks = self._mirror(self.samp_top_ks)
        # 3. decode: this step's block goes to the device BEFORE anything is
        # fetched, so the device holds it while the host walks the block before.
        block = self._dispatch_decode(ph, events)
        # Fetch per group, in dispatch order: group g's fetch returns while
        # g+1 still runs on device (async dispatch), so TTFT is per-group.
        for chunk, toks_dev in dispatched:
            ph.to("prefill_fetch")
            group_toks = np.asarray(jax.device_get(toks_dev)).tolist()
            now = time.perf_counter()
            now_mono = time.monotonic()
            ph.to("emit")
            for (i, req_id, tokens, _b, _mt, arrived), tok in zip(chunk, group_toks):
                slot = self.slots[i]
                tok = int(tok)
                slot.first_token_at = now
                slot.life["first_token"] = now_mono
                slot.emitted.append(tok)
                events[req_id] = {
                    "token": tok,
                    "new_tokens": [tok],
                    "finished": False,
                    "ttft_s": now - arrived,
                }
                self._maybe_finish(i, events)
        # 4. the block before: fetched and walked while the device runs this
        # step's (the context-cap path of _dispatch_decode may have absorbed it).
        if self._inflight is not None:
            ph.rec["dropped_rows"] = self._absorb(events, ph.to)
        self._inflight = block
        if block is None:
            self._retire_at_cap(events)
        if self._gone:
            ph.to("retire_sync")
            self._sync_retired()
        return events

    def _fit(self) -> tuple[list[int], int, bool]:
        """The rows a decode block would advance now, the compiled size to
        run (0: none), and whether none runs only because the longest row's
        headroom is under the smallest compiled block. Queue pressure shrinks
        the block so the next admission wave starts sooner. Slots mid
        chunked-prefill ride along masked (nothing written, tokens
        discarded) but do not drive the block's budget arithmetic."""
        active = [i for i, s in enumerate(self.slots)
                  if s is not None and i not in self._prefilling]
        if not active:
            return active, 0, False
        remaining = [self.slots[i].max_tokens - self.slots[i].n_generated for i in active]
        cap = self.ec.max_seq - 1 - int(self.lengths[active].max())
        if not any(r > 0 for r in remaining) or cap <= 0:
            return active, 0, False  # every budget is on the device already, or a row at the cap waits to be walked
        # Short block under queue pressure (admissions land sooner)
        # OR while any slot still owes its FIRST token to a decode block
        # (prefix-cache hits skip prefill; their TTFT is the first decode
        # block — a full block would pay block_size steps of latency for it).
        awaiting_first = bool(self._prefilling) or any(
            self.slots[i].n_generated == 0 for i in active)
        want = self.block_sizes[0] if (self.waiting or awaiting_first) else self.block_sizes[-1]
        # Snap DOWN to a compiled size: an oversized block advances
        # lengths past max_seq-1 and the clamped device writes would
        # scribble over the longest slot's earlier KV.
        fits = [b for b in self.block_sizes if b <= min(want, cap)]
        return active, (fits[-1] if fits else 0), not fits

    def _dispatch_decode(self, ph, events: dict) -> Optional[_Block]:
        """Enqueue one fused decode block over all slots and return its
        record, the tokens still on the device (None: nothing to decode, or
        no room: ``_retire_at_cap``). ``lengths`` and every slot's
        ``n_generated`` advance by the block here, at dispatch: they are the
        device's, whatever the host has walked."""
        ph.to("decode_dispatch")
        active, n, short = self._fit()
        if short and self._inflight is not None:
            # The headroom is short by tokens the host has not seen: a row that
            # only waits to be walked must end by its own tokens. Absorb, take
            # what ended off the mirrors (no block goes out over a retired
            # row's pages), look again.
            ph.rec["dropped_rows"] = self._absorb(events, ph.to)
            ph.to("retire_sync")
            self._sync_retired()
            ph.to("decode_dispatch")
            active, n, short = self._fit()
        if not n:
            return None
        rec = ph.rec
        rec["block"] = n
        rec["ahead"] = int(self._inflight is not None)
        rec["active"] = len(active)
        rec["sampled"] = int(np.count_nonzero(self.samp_temps[active] > 0))
        self._key, sub = jax.random.split(self._key)
        self.cache, toks, self.d_last, self.d_lengths, counts = self._decode_jit(
            self.params, self.cache, self.d_last,
            self.d_lengths, self.d_page_tables, sub, n,
            self.d_temps, self.d_top_ps, self.d_top_ks,
        )
        lengths = self.lengths[active]  # as the block was dispatched
        for rule in self.rules:
            rec.update(rule.block_counts(lengths, n))
        self.lengths[active] += n
        for i in active:
            self.slots[i].n_generated += n
        return _Block(toks, counts, n, rec, [(i, self.slots[i]) for i in active])

    def _retire_at_cap(self, events: dict) -> None:
        """No compiled block fits the headroom left by the longest slot(s):
        retire them (they are within block_sizes[0] tokens of max_seq) so the
        next step has room to decode. Runs where a step dispatched no block,
        after its fetches, so the host has seen every token there is: a
        request admitted in this step has its first token."""
        active, _n, short = self._fit()
        if not short:
            return
        for i in active:
            if int(self.lengths[i]) + self.block_sizes[0] >= self.ec.max_seq:
                slot = self.slots[i]
                ev = events.setdefault(slot.req_id, {"ttft_s": None})
                ev["finished"] = True
                ev["finish_reason"] = "length"  # context-cap retirement
                ev["tokens"] = list(slot.emitted)
                ev["ttft_s"] = ev.get("ttft_s") or (
                    (slot.first_token_at or slot.arrived_at) - slot.arrived_at
                )
                self._close_life(slot.life, len(slot.emitted), "length")
                self._retire(i)

    def _absorb(self, events: dict, to=lambda _phase: None) -> int:
        """Fetch the block in flight and walk its tokens into ``events``;
        returns the rows it dropped. A row's tokens go to the request that
        held the row when the block was dispatched, or nowhere: a request
        that ended in the block before (EOS, a stop id, its budget, an abort)
        was found out after this block was enqueued with its row still live,
        and its slot may hold another request by now. ``to`` is the step's
        ``PhaseSpans.to`` (outside a step nothing is timed)."""
        blk, self._inflight = self._inflight, None
        to("decode_fetch")
        if blk.counts is None:
            block_toks = np.asarray(jax.device_get(blk.toks))  # [n, B]
        else:  # held experts' counts, then the rules': they ride the same fetch
            block_toks, counts = jax.device_get((blk.toks, blk.counts))
            names = ("expert_pairs", "expert_tiles") if self.cfg.experts_held else ()
            blk.rec.update(zip((*names, *self._device_counts(lambda rule: rule.device_counts)), map(int, counts)))
        to("emit")
        rows = [(i, slot) for i, slot in blk.rows if self.slots[i] is slot]
        for step_i in range(blk.n):
            for i, slot in rows:
                if self.slots[i] is not slot:
                    continue  # finished inside this block: its tail is thrown away
                tok = int(block_toks[step_i, i])
                slot.emitted.append(tok)
                ev = events.setdefault(slot.req_id, {"finished": False, "ttft_s": None})
                if slot.first_token_at is None:
                    # Prefix-cache hits skip prefill; their first token
                    # comes out of the decode block.
                    slot.first_token_at = time.perf_counter()
                    slot.life["first_token"] = time.monotonic()
                    ev["ttft_s"] = slot.first_token_at - slot.arrived_at
                ev["token"] = tok
                ev.setdefault("new_tokens", []).append(tok)
                self._maybe_finish(i, events)
        return len(blk.rows) - len(rows)

    def _absorb_outside_step(self) -> None:
        """For a caller between steps that needs the host to have seen every
        token (abort, set_params, generate's return): the block in flight
        walked into events the next step hands out."""
        if self._inflight is not None:
            self._absorb(self._carry)
        self._sync_retired()

    @staticmethod
    def _drop_rows_impl(lengths, page_tables, gone):
        """The two mirrors a retire touches, zeroed in the rows ``gone``
        [max_slots] marks and in no other: the device may be a block ahead of
        the host, so the live rows' lengths are the device's to keep. d_last
        needs none: a slot without pages is a dead, greedy row."""
        return jnp.where(gone, 0, lengths), jnp.where(gone[:, None], 0, page_tables)

    def _sync_retired(self) -> None:
        """Retired slots stop advancing their (now meaningless) lengths
        toward max_seq and are walked no more: their rows of d_lengths
        and d_page_tables go to zero, behind whatever block is in flight."""
        if not self._gone:
            return
        gone = np.zeros(self.ec.max_slots, bool)
        gone[self._gone] = True
        self._gone.clear()
        self.d_lengths, self.d_page_tables = self._drop_rows_jit(
            self.d_lengths, self.d_page_tables, jnp.asarray(gone))

    def _maybe_finish(self, i: int, events: dict) -> bool:
        slot = self.slots[i]
        # Retire cause rides the event as OpenAI-style finish_reason: a
        # token-triggered stop (eos / per-request stop ids) is "stop"; any
        # budget cap (max_tokens, or forced retirement at the max_seq
        # context ceiling) is "length" — previously a max_seq retirement
        # was mislabeled "stop" by the under-max_tokens heuristic upstream.
        stopped = (
            (not slot.ignore_eos and self.ec.eos_id >= 0 and slot.emitted[-1] == self.ec.eos_id)
            or slot.emitted[-1] in slot.stop_ids
        )
        # The context the host has walked (``lengths`` is the device's, up to
        # two blocks further): the prompt and every token out but this one.
        capped = (
            len(slot.emitted) >= slot.max_tokens
            or slot.prompt_len + len(slot.emitted) >= self.ec.max_seq
        )
        done = stopped or capped
        if done:
            ev = events.setdefault(slot.req_id, {"ttft_s": None})
            ev["finished"] = True
            ev["finish_reason"] = "stop" if stopped else "length"
            ev["tokens"] = list(slot.emitted)
            ev["ttft_s"] = ev.get("ttft_s") or (slot.first_token_at - slot.arrived_at)
            self._close_life(slot.life, len(slot.emitted), ev["finish_reason"])
            self._retire(i)
        return bool(done)

    def generate(self, tokens, max_tokens: int = 64,
                 sampling: SamplingParams | None = None) -> dict:
        """Synchronous single-request convenience: returns {"tokens", "ttft_s"}."""
        req_id = f"g{time.monotonic_ns()}"
        # No-op unless a distributed trace is active in this thread.
        with _tracing.child_span("llm.engine.generate", max_tokens=max_tokens):
            self.add_request(req_id, tokens, max_tokens, sampling=sampling)
            ttft = None
            while True:
                events = self.step()
                ev = events.get(req_id)
                if ev and ev.get("ttft_s") is not None:
                    ttft = ev["ttft_s"]
                if ev and ev.get("finished"):
                    self._absorb_outside_step()  # it ended a block ago on the device: leave none behind
                    return {"tokens": ev["tokens"], "ttft_s": ttft}
