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
import time

from ray_tpu.util.tracing import Ring

COMPILE_CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# The path is part of the cache key, so it is fixed: never a temp name.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_compile_cache")
# What jax.monitoring reports once for each program the backend compiles (a
# program found in the persistent cache is not compiled and not reported).
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_compiles = Ring(512)  # (time.monotonic() at the end of a compile, its seconds)
_compile_counter = None  # the metrics plane's jax.compiles, once listening


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns the directory.
    From here on this process also counts its compilations
    (``compile_events``).

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
    """Register, once, this process's listener for backend compilations."""
    global _compile_counter
    if _compile_counter is not None:
        return
    import jax.monitoring

    from ray_tpu.util import metrics

    _compile_counter = metrics.Counter(
        "jax.compiles", "programs this process's JAX backend compiled")

    def on_duration(name, seconds, **_kw):
        if name == COMPILE_EVENT:
            _compiles.push((time.monotonic(), seconds))
            _compile_counter.inc()

    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_events() -> dict:
    """Compilations since ``enable_compile_cache``: their count, and the most
    recent as (time.monotonic() stamp at the end of the compile, seconds).
    A compile inside a serving window is a request that waited for it."""
    return {"count": _compiles.total, "recent": _compiles.snapshot()}


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
