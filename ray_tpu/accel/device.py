"""What a process does before its first device work: place the compile
cache, and check that it sees the chips it was scheduled onto.

A chip belongs to one process at a time, and initialising a JAX backend is
what claims it. Only the processes that run device programs (serve replica,
train worker, a bench or smoke phase) call into this module; daemons,
drivers, proxies and controllers never do. Importable without jax.
"""
from __future__ import annotations

import math
import os
import sys
import threading
import time

from ray_tpu.util.tracing import Ring

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The path is part of the cache key, so it is fixed: never a temp name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")
# What jax.monitoring reports of a program on its way to the device, each as the stage ends and with the
# function's name: its trace (Python over the function's body; a jit called inside reports its own trace
# first, inside its caller's), its lowering (jaxpr to MLIR, each pallas_call through Mosaic's lowering), and
# the backend, once for each executable it STARTS: compiled, or read from the persistent cache and loaded, which
# reports the seconds of the read a moment before. (So a hit is reported too, jax 0.9.0: on the chip a warm start
# of a serve replica counts its 100-150 executables cold or warm, a fifth of them hits at 0.3-1.0 s each, the
# rest programs under JAX's threshold for caching, compiled again every start: PERF.md section 5, PR 57.)
TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
# The function ``pl.pallas_call`` traces a kernel's body in: a jit of its own, made anew at every call (jax 0.9.0,
# jax/_src/pallas/pallas_call.py ``wrapped``), so each of its TRACE_EVENTs is a body really traced. Not so a
# TRACE_EVENT as such: JAX reports one for every call of a jit inside a trace, those its trace cache served too
# (``pjit._trace_for_jit`` brackets the cached ``trace_to_jaxpr``), and a model's every jax.numpy call is one.
KERNEL_TRACE_FUN = "wrapped"
_compiles = Ring(512)  # (time.monotonic() at the end of a backend event, its seconds)
_compile_counter = None  # the metrics plane's jax.compiles, once listening
# This process's seconds and counts by stage since enable_compile_cache (compile_stages). Any thread may trace
# or compile: the totals under a lock, a trace's depth and a cache read's verdict by thread.
_stages = {"trace_s": 0.0, "lower_s": 0.0, "backend_s": 0.0, "miss_s": 0.0, "retrieval_s": 0.0,
           "traces": 0, "hits": 0, "misses": 0, "executables": 0}
_stages_lock = threading.Lock()
_in_thread = threading.local()  # depth: traces begun and not ended; hit: the cache held the executable on its way


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory.
    From here on this process also counts what its programs' stages cost
    (``compile_events``, ``compile_stages``).

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing — the operator placed the cache. Otherwise the cache sits at
    one fixed directory inside the checkout. Worker processes inherit the
    variable from the daemon's environment, so a whole cluster shares it."""
    _count_compiles()
    placed = os.environ.get(COMPILE_CACHE_ENV)
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def _count_compiles() -> None:
    """Register, once, this process's listeners for what JAX reports of a program's stages."""
    global _compile_counter
    if _compile_counter is not None:
        return
    import jax.monitoring

    from ray_tpu.util import metrics

    _compile_counter = metrics.Counter(
        "jax.compiles", "executables this process's JAX backend started: compiled, or read from the cache")

    def add(**stages):
        with _stages_lock:
            for key, value in stages.items():
                _stages[key] += value

    def on_start(name, _stamp, **_kw):
        # JAX reports a stage's start as a scalar (the stamp) under the stage's name
        if name == TRACE_EVENT:
            _in_thread.depth = getattr(_in_thread, "depth", 0) + 1

    def on_duration(name, seconds, fun_name=None, **_kw):
        if name == TRACE_EVENT:
            _in_thread.depth = depth = max(getattr(_in_thread, "depth", 0) - 1, 0)
            if not depth:  # a trace inside another is seconds of that one: counted once, with it
                add(trace_s=seconds)
            if fun_name == KERNEL_TRACE_FUN:
                add(traces=1)
        elif name == LOWER_EVENT:
            add(lower_s=seconds)
        elif name == RETRIEVAL_EVENT:  # inside the backend event that follows, and only on a hit
            _in_thread.hit = True
            add(retrieval_s=seconds)
        elif name == COMPILE_EVENT:
            _compiles.push((time.monotonic(), seconds))
            _compile_counter.inc()
            if getattr(_in_thread, "hit", False):
                _in_thread.hit = False
                add(backend_s=seconds, executables=1, hits=1)
            else:  # the cache did not hold it, or was not asked: compiled
                add(backend_s=seconds, executables=1, misses=1, miss_s=seconds)

    jax.monitoring.register_scalar_listener(on_start)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_events() -> dict:
    """Executables the backend started since ``enable_compile_cache``, compiled or read from the persistent
    cache (a read reports a backend event too): their count, and the most recent as (time.monotonic() stamp
    at the event's end, seconds). One inside a serving window is a request that waited for it."""
    return {"count": _compiles.total, "recent": _compiles.snapshot()}


def compile_stages() -> dict:
    """This process's cumulative seconds and counts by stage since ``enable_compile_cache``: ``trace_s``
    (a nested jit's trace counted once, inside its caller's), ``traces`` (kernel bodies traced, each inside
    ``trace_s``: a kernel whose call JAX's trace cache served is none, so one traced once a shape signature
    counts once and one traced at every call once a call), ``lower_s``, ``backend_s`` (inside the backend
    events, whatever the cache said), ``miss_s`` (the part of ``backend_s`` whose executable the cache did not
    hold or was not asked for: a compile), ``retrieval_s`` (the cache reads of the hits, part of
    ``backend_s``), ``hits``, ``misses`` and ``executables`` (backend events: hits + misses). JAX writes to
    the cache only what took ``jax_persistent_cache_min_compile_time_secs`` to compile, so a warm start still
    counts its small programs as misses. Two readings' difference is what lay between them."""
    with _stages_lock:
        return dict(_stages)


def backend_initialized() -> bool:
    """Whether this process has initialised a JAX backend (and so holds
    whatever chip that backend claims). Never imports anything and never
    initialises anything: it is called from metrics ticks while another
    thread may be half-way through ``import jax``, and an import statement
    here would race that one. A process that has not imported jax, or is
    still importing it, has no backend. Once the module is there the probe
    must be too: if a later jax moves it, this raises rather than answering
    "no backend" for ever."""
    xla_bridge = sys.modules.get("jax._src.xla_bridge")
    if xla_bridge is None or getattr(xla_bridge.__spec__, "_initializing", False):
        return False
    return bool(xla_bridge.backends_are_initialized())


def device_report() -> dict:
    """This process's devices as JAX reports them (initialises the backend).
    ``device_count`` is every process's devices once jax.distributed is up;
    ``local_device_count`` is what this process itself holds."""
    import jax

    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "local_device_count": jax.local_device_count(),
        "jax": jax.__version__,
        "pid": os.getpid(),
    }


def require_tpu(chips: float, who: str) -> None:
    """Raise unless this process's backend is a TPU and the process itself
    holds at least ``chips`` devices (local ones: in a multi-process gang the
    global count is every host's). For a process the scheduler placed onto
    ``TPU`` resources: one that came up on another platform, or with fewer
    chips than it was given, would otherwise serve or train under a name it
    has not earned."""
    report = device_report()
    need = max(1, math.ceil(chips))
    if report["platform"] != "tpu" or report["local_device_count"] < need:
        raise RuntimeError(
            f"{who} was scheduled onto {chips:g} TPU chip(s) but its process "
            f"sees platform {report['platform']!r} with "
            f"{report['local_device_count']} local device(s) "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); refusing "
            f"to run on another backend"
        )
