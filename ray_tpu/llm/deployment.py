"""LLM serving deployment: continuous-batching replica for ray_tpu.serve.

Role-equivalent to the reference's LLMServer deployment
(llm/_internal/serve/core/server/llm_server.py:99) plus its OpenAI-style SSE
ingress (llm/_internal/serve/core/ingress/): a serve replica hosting one
engine; concurrent generate() calls from the router land in the engine's
waiting queue and are batched at iteration level by a background loop thread,
so max_ongoing_requests concurrency maps directly onto engine slots. Token
streaming: generate_stream() yields per-decode-block events as they leave the
device; through serve's streaming call path + the proxy's chunked writer a
client sees the first token at engine TTFT, not at completion time.
"""
from __future__ import annotations

import json
import threading
import time
from collections import deque
from typing import Optional


def _coerce_sampling(sampling):
    """Accept a SamplingParams, a kwargs dict (the over-the-wire form), or
    None."""
    if sampling is None or not isinstance(sampling, dict):
        return sampling
    from ray_tpu.llm.sampling import SamplingParams

    return SamplingParams(**sampling)


class LLMServer:
    """Serve-deployable callable: hosts an LLMEngine + stepping thread.

    Use through build_llm_app() or directly:
        app = serve.deployment(LLMServer).options(...).bind(cfg_kwargs, engine_kwargs)
    """

    def __init__(self, model_config: dict, engine_config: Optional[dict] = None,
                 warmup_buckets: Optional[tuple] = None, params=None,
                 weights_channel: Optional[str] = None):
        ctor_began = time.monotonic()
        import ray_tpu as rt
        from ray_tpu.accel import device as _device

        self._compile_cache_dir = _device.enable_compile_cache()
        chips = (
            rt.get_runtime_context().get_assigned_resources().get("TPU", 0)
            if rt.is_initialized() else 0
        )
        if chips:
            _device.require_tpu(chips, "LLM replica")

        from ray_tpu.llm.engine import EngineConfig, LLMEngine
        from ray_tpu.models.transformer import TransformerConfig

        # The process has its imports and its backend (require_tpu starts it):
        # from here on the constructor is timed (stats()["startup"]).
        init_began = time.monotonic()
        before = _device.compile_stages()  # the process's totals here, then after LLMEngine(...), after warmup
        cfg = TransformerConfig(**model_config)
        ec = EngineConfig(**(engine_config or {}))
        # train->serve weight handoff: `params` may be an ObjectRef to a
        # trained (possibly SHARDED) param tree in the object store — each
        # replica fetches it here, in its own process, and sharded leaves
        # arrive one OOB buffer per shard and reassemble onto this replica's
        # devices (core/serialization.py; reference: tensor_transport
        # keeping tensors off the generic path, gpu_object_manager.py:55-75).
        t0 = time.perf_counter()
        if params is not None:
            from ray_tpu.core.object_ref import ObjectRef

            if isinstance(params, ObjectRef):
                params = rt.get(params, timeout=300.0)
        t1 = time.perf_counter()
        self.engine = LLMEngine(cfg, params=params, engine_config=ec)
        t2 = time.perf_counter()
        built = _device.compile_stages()
        if warmup_buckets:
            # Compile prefill/decode programs before the replica reports
            # healthy (vLLM-style startup warmup): cold compiles belong to
            # startup, never to a request's TTFT.
            self.engine.warmup(buckets=tuple(warmup_buckets))
        self._warmup_s = time.perf_counter() - t2
        warmed = _device.compile_stages()
        # Where start-up went (stats()["startup"]): fetching the params,
        # building the engine (weights made or resharded, KV pool), and each
        # warmed program with its seconds (a cold compile or a cache read);
        # init_began / init_ended put the three on time.monotonic(), the clock
        # of the lifecycle stamps and of a client on this machine: a client's
        # set-up is what went before the first (the process, its imports, the
        # backend), the three durations, and what came after the second.
        # ctor_began is the constructor's first statement: from it to
        # init_began the compile cache was placed, the chip claimed and the
        # engine's modules imported. "stages" says of what JAX traced,
        # lowered and handed its backend (accel/device.compile_stages) how
        # much lay before init_began, inside engine_init_s and inside warmup_s.
        self._startup = {
            "ctor_began": ctor_began, "init_began": init_began, "init_ended": None,
            "stages": {"before": before,
                       "engine_init": {key: built[key] - before[key] for key in before},
                       "warmup": {key: warmed[key] - built[key] for key in before}},
            "fetch_params_s": t1 - t0, "engine_init_s": t2 - t1, "warmup_s": self._warmup_s,
            "programs": self.engine.warmup_log,
            # the KV pools' bytes, by layer kind (one kind for a model whose layers are alike)
            "pool_bytes": self.engine.pool_bytes,
        }
        self._cond = threading.Condition()
        self._done: dict[str, dict] = {}
        self._ttft: dict[str, float] = {}
        # Lifecycle records (engine.add_request) of the requests in flight:
        # the loop stamps first_emitted into them, their streams first_yielded.
        self._life: dict[str, dict] = {}
        # TTFT distribution (serve.ttft_s): the SLO engine's third metric —
        # an LLM objective on time-to-first-token reads this histogram the
        # same way latency objectives read serve.request.latency_s.
        from ray_tpu.util import metrics as _metrics

        self._ttft_hist = _metrics.Histogram(
            "serve.ttft_s", "time to first token per request",
            boundaries=[0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1, 5, 30],
            tag_keys=("deployment",),
        ).bind(tags={"deployment": "llm"})
        # Per-request event streams for generate_stream subscribers.
        self._streams: dict[str, deque] = {}
        # Requests whose stream consumer disconnected; the loop thread aborts
        # them in the engine (frees their slots) before its next step.
        self._aborts: set[str] = set()
        self._counter = 0
        self._stop = False
        # Weight hot-swap gate: step() and set_params() exclude each other,
        # so a swap lands between engine iterations — in-flight batches
        # finish on the old weights, no request ever reads a mixed tree.
        self._swap_lock = threading.Lock()
        self._weights_sub = None
        if weights_channel:
            # ckpt publication plane: subscribe this replica to the named
            # checkpoint channel; committed manifests hot-swap in place
            # (fetch + digest-verify happen OFF the swap lock).
            from ray_tpu.ckpt import WeightSubscriber

            self._weights_sub = WeightSubscriber(weights_channel, self._swap_weights)
        self._thread = threading.Thread(target=self._loop, name="llm-engine", daemon=True)
        self._thread.start()
        self._startup["init_ended"] = time.monotonic()

    def _swap_weights(self, tree, summary):
        with self._swap_lock:
            self.engine.set_params(tree)

    def apply_weights(self, tree) -> bool:
        """Push-style weight refresh (tests / manual rollout): same gate as
        the subscription path."""
        self._swap_weights(tree, None)
        return True

    def weights_version(self) -> Optional[str]:
        sub = self._weights_sub
        return sub.current_version if sub is not None else None

    def _loop(self):
        from ray_tpu.llm.engine import record_request_spans

        while not self._stop:
            with self._cond:
                aborts, self._aborts = self._aborts, set()
                for rid in aborts:
                    self._life.pop(rid, None)
                if not aborts and not self.engine.has_work():
                    self._cond.wait(timeout=0.05)
                    continue
            with self._swap_lock:
                for rid in aborts:
                    self.engine.abort(rid)
                if not self.engine.has_work():
                    continue
                events = self.engine.step()
            if not events:
                continue
            first, traced = [], []
            with self._cond:
                for rid, ev in events.items():
                    if ev.get("ttft_s") is not None:
                        self._ttft[rid] = ev["ttft_s"]
                        life = self._life.get(rid)
                        if life is not None and life["first_emitted"] is None:
                            # once a request: its closing event, steps later, carries ttft_s again
                            self._ttft_hist.observe(ev["ttft_s"])
                            first.append(life)
                    stream = self._streams.get(rid)
                    if stream is not None:
                        stream.append(ev)
                    if ev.get("finished"):
                        self._done[rid] = {
                            "tokens": ev["tokens"],
                            "ttft_s": self._ttft.pop(rid, ev.get("ttft_s")),
                            "finish_reason": ev.get("finish_reason"),
                        }
                        life = self._life.pop(rid, None)
                        if life is not None and life["trace"] is not None:
                            traced.append(life)
                # The events are where their consumers will find them: one
                # stamp for every request whose first token is among them.
                now = time.monotonic()
                for life in first:
                    life["first_emitted"] = now
                self._cond.notify_all()
            for life in traced:
                record_request_spans(life)

    def _new_rid(self) -> str:
        self._counter += 1
        return f"r{self._counter}-{time.monotonic_ns()}"

    def generate(self, tokens, max_tokens: int = 64, timeout_s: float = 300.0,
                 sampling=None) -> dict:
        """Blocking generate; safe to call from many router threads at once —
        the engine batches all in-flight requests per decode iteration.
        sampling: per-request SamplingParams (or kwargs dict for one).

        QoS: an active RequestContext caps the wait at the request's
        deadline, and a caller that gave up (qos.cancel_requested(), fired
        by the serve handle's cancel path) ABORTS the engine request — in
        both cases the engine slot frees immediately instead of decoding
        to completion for nobody."""
        from ray_tpu.qos import context as _qos
        from ray_tpu.util import tracing as _tracing

        sampling = _coerce_sampling(sampling)
        qctx = _qos.current()
        rem = qctx.remaining() if qctx is not None else None
        if rem is not None:
            timeout_s = min(timeout_s, max(rem, 0.0))
        cancellable = _qos.cancel_event() is not None
        # Short wait slices only when there is a cancel/deadline to notice.
        slice_s = 0.25 if (cancellable or rem is not None) else 1.0
        # child_span: free no-op unless the request arrived with a trace
        # (serve proxy/handle context rides the actor call into this thread).
        with _tracing.child_span("llm.generate", max_tokens=max_tokens):
            with self._cond:
                rid = self._new_rid()
                self._life[rid] = self.engine.add_request(
                    rid, tokens, max_tokens, sampling=sampling)
                self._cond.notify_all()
                deadline = time.time() + timeout_s
                while rid not in self._done:
                    if cancellable and _qos.cancel_requested():
                        self._aborts.add(rid)
                        self._cond.notify_all()
                        raise _qos.RequestCancelled(
                            "caller abandoned generate(); engine slot freed")
                    remaining = deadline - time.time()
                    if remaining <= 0:
                        # Free the slot: a timed-out request must not keep
                        # decoding to completion (the orphaned-work bug).
                        self._aborts.add(rid)
                        self._cond.notify_all()
                        if qctx is not None and qctx.expired():
                            _qos.raise_expired("llm", "generate")
                        raise TimeoutError(f"generate timed out after {timeout_s}s")
                    self._cond.wait(timeout=min(remaining, slice_s))
                return self._done.pop(rid)

    def generate_stream(self, tokens, max_tokens: int = 64, timeout_s: float = 300.0,
                        sampling=None):
        """Streaming generate: yields one event dict per engine step that
        produced tokens for this request ({"new_tokens": [...], "ttft_s":
        float|None, "finished": bool}, final event carries "tokens"). Each
        event leaves this replica the moment the decode block lands on host.

        QoS: the wait is capped at the request's deadline and a cancelled
        caller aborts the engine request between yields (the finally already
        aborts on early generator close)."""
        from ray_tpu.qos import context as _qos

        sampling = _coerce_sampling(sampling)
        qctx = _qos.current()
        rem = qctx.remaining() if qctx is not None else None
        if rem is not None:
            timeout_s = min(timeout_s, max(rem, 0.0))
        cancellable = _qos.cancel_event() is not None
        slice_s = 0.25 if (cancellable or rem is not None) else 1.0
        with self._cond:
            rid = self._new_rid()
            self._streams[rid] = deque()
            life = self._life[rid] = self.engine.add_request(
                rid, tokens, max_tokens, sampling=sampling)
            self._cond.notify_all()
        deadline = time.time() + timeout_s
        finished = False
        try:
            while True:
                with self._cond:
                    while not self._streams[rid]:
                        if cancellable and _qos.cancel_requested():
                            raise _qos.RequestCancelled(
                                "caller abandoned generate_stream(); engine slot freed")
                        remaining = deadline - time.time()
                        if remaining <= 0:
                            if qctx is not None and qctx.expired():
                                _qos.raise_expired("llm", "generate_stream")
                            raise TimeoutError(f"generate timed out after {timeout_s}s")
                        self._cond.wait(timeout=min(remaining, slice_s))
                    ev = self._streams[rid].popleft()
                if life["first_yielded"] is None:
                    life["first_yielded"] = time.monotonic()
                out = {
                    "new_tokens": ev.get("new_tokens", []),
                    "ttft_s": ev.get("ttft_s"),
                    "finished": bool(ev.get("finished")),
                }
                if out["finished"]:
                    out["tokens"] = ev.get("tokens", [])
                    out["finish_reason"] = ev.get("finish_reason")
                    finished = True
                yield out
                if finished:
                    return
        finally:
            with self._cond:
                self._streams.pop(rid, None)
                self._done.pop(rid, None)
                if not finished:
                    # Consumer left early (client disconnect): free the slot.
                    self._aborts.add(rid)
                    self._cond.notify_all()

    def _sse_stream(self, tokens, max_tokens: int, sampling=None):
        """OpenAI-style SSE frames (reference: llm ingress SSE): one
        `data: {json}` frame per event, then `data: [DONE]`."""
        for ev in self.generate_stream(tokens, max_tokens, sampling=sampling):
            yield f"data: {json.dumps(ev)}\n\n"
        yield "data: [DONE]\n\n"

    def __call__(self, request):
        """Accepts a serve HTTP Request (JSON body) or a plain dict:
        {"tokens": [...], "max_tokens": N, "stream": bool, plus optional
        per-request sampling: temperature/top_p/top_k/ignore_eos}. With
        stream=true returns a generator of SSE frames (the proxy sends it
        chunked as text/event-stream); otherwise blocks and returns the
        full completion."""
        if hasattr(request, "json") and not isinstance(request, dict):
            payload = request.json()
        else:
            payload = request
        tokens = payload["tokens"]
        max_tokens = int(payload.get("max_tokens", 64))
        sampling = {
            k: payload[k]
            for k in ("temperature", "top_p", "top_k", "ignore_eos")
            if k in payload
        }
        if sampling:
            # A partial dict must not silently flip temperature to greedy:
            # absent keys inherit the engine's configured default.
            sampling.setdefault("temperature", self.engine.ec.temperature)
            sampling = dict(sampling, max_tokens=max_tokens)
        else:
            sampling = None
        if payload.get("stream"):
            return self._sse_stream(tokens, max_tokens, sampling)
        return self.generate(tokens, max_tokens, sampling=sampling)

    def check_health(self) -> bool:
        return self._thread.is_alive()

    def stats(self) -> dict:
        """The operator's pull surface (openai.py's stats route returns it).
        "trace" is what the program recorded of itself, on time.monotonic():
        the lifecycle records of the finished requests and the phase records
        of the ended steps still in their rings (engine.TRACE_RING each; what
        fell off is counted in "dropped"), cumulative seconds by step phase,
        and the executables this process's backend started (compiled, or read
        from the compile cache). "startup" says where start-up went: three
        durations between the stamps init_began (the process has its imports
        and its backend) and init_ended (the constructor's last statement) on
        the same clock, so a client that timed its own set-up finds what went
        before the first and what came after the second; ctor_began, the
        constructor's first statement, from which to init_began the compile
        cache was placed, the chip claimed and the engine imported; "stages",
        what JAX reported of tracing, lowering and its backend
        (accel/device.compile_stages: trace_s, lower_s, backend_s, of it
        miss_s compiling what the cache did not hold, retrieval_s, hits,
        misses, executables) "before" init_began, inside "engine_init" and
        inside "warmup"; and "programs", an entry a warmed program in warm-up's
        order: t, its start, seconds, and of the seconds trace_s, lower_s,
        backend_s and miss_s with the executables it started and the misses
        among them. What is left of an entry's seconds is the program's first
        run, its arrays and its fetch. A warm start reads misses only for the
        programs under JAX's threshold for caching (a second of compiling);
        more misses than the start before is a cache that lost entries.
        Reads only; takes no lock."""
        from ray_tpu.accel import device as _device

        active = sum(1 for s in self.engine.slots if s is not None)
        out = {"active_slots": active, "waiting": len(self.engine.waiting)}
        if self.engine.ec.prefix_cache:
            out["prefix_cache"] = self.engine.prefix_cache_stats
        compiles = _device.compile_events()
        out["trace"] = {
            **self.engine.trace_snapshot(),
            "compiles": compiles["recent"], "compiles_total": compiles["count"],
        }
        out["startup"] = self._startup
        return out

    def device_report(self) -> dict:
        """Where this replica runs, as its OWN process sees it — a driver
        must not touch JAX to find out, since that would claim the chip:
        platform, device kind and count, the compile cache in use, warm-up
        (compile) seconds, which programs hold the Mosaic kernels (recorded
        by the engine's warm-up), and what one device holds of the weights
        and the KV pool (a tensor-parallel replica: one shard of each, and
        its share of the memory). Reads only; takes no lock."""
        import jax

        from ray_tpu.accel import device as _device

        eng = self.engine
        # the last layers' stack (of a model with a layer pattern: its first kind's that attends with queries)
        name = "wq_b" if eng.cfg.latent else "wq"  # heads on axis 2 either way
        stacks = [eng.params["layers"]] if "layers" in eng.params else eng.params["kind_layers"].values()
        wq = next(stack[name] for stack in stacks if name in stack)
        per_device = {
            "wq": list(wq.sharding.shard_shape(wq.shape)),
            # the first pool: a head's K rows, or a latent layer's one pool
            "k_pages": list(eng.cache[0].sharding.shard_shape(eng.cache[0].shape)),
            "bytes_in_use": [
                (d.memory_stats() or {}).get("bytes_in_use") for d in jax.local_devices()],
        }
        return {
            **_device.device_report(),
            "compile_cache_dir": self._compile_cache_dir,
            "warmup_s": round(self._warmup_s, 3),
            "mosaic": dict(eng.mosaic),
            "per_device": per_device,
        }

    def __raytpu_exit__(self):
        self._stop = True
        if self._weights_sub is not None:
            self._weights_sub.stop()


def build_llm_app(model_config: dict, engine_config: Optional[dict] = None,
                  num_replicas: int = 1, max_ongoing_requests: Optional[int] = None,
                  warmup_buckets: Optional[tuple] = None,
                  ray_actor_options: Optional[dict] = None,
                  params=None, weights_channel: Optional[str] = None,
                  autoscaling_config=None):
    """Build a serve application serving this model. max_ongoing_requests
    defaults to the engine's slot count (router admission == engine capacity).
    params: trained weights — a param tree or an ObjectRef to one (the
    train->serve handoff; sharded trees move per-shard, see LLMServer).
    weights_channel: subscribe every replica to this named checkpoint
    channel — committed manifests hot-swap weights in place, no restart.
    autoscaling_config: AutoscalingConfig (or kwargs dict) — replica count
    then floats between min/max, driven by the scale plane's demand + QoS
    signals (ray_tpu/scale/) instead of num_replicas."""
    from ray_tpu import serve
    from ray_tpu.llm.engine import EngineConfig

    ec = EngineConfig(**(engine_config or {}))
    if isinstance(autoscaling_config, dict):
        autoscaling_config = serve.AutoscalingConfig(**autoscaling_config)
    aopts = dict(ray_actor_options or {})
    if ec.tensor_parallel > 1:
        # Tensor-parallel replica: gang-schedule it onto a host advertising
        # that many chips (reference: TP degree -> placement-group bundles,
        # vllm_models.py:233-238). The reservation is bookkeeping only: no
        # per-worker chip assignment exists, so the replica's process claims
        # every chip on its host and the engine meshes the first `tp` of them.
        aopts.setdefault("resources", {}).setdefault(
            "TPU", float(ec.tensor_parallel)
        )
    dep = serve.deployment(LLMServer).options(
        name="llm",
        num_replicas=num_replicas,
        max_ongoing_requests=max_ongoing_requests or ec.max_slots,
        ray_actor_options=aopts,
        autoscaling_config=autoscaling_config,
    )
    return dep.bind(model_config, engine_config, warmup_buckets, params, weights_channel)
