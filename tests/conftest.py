"""Test harness config.

JAX tests run on a virtual 8-device CPU mesh (the reference tests multi-host
TPU scheduling with fake resources the same way — SURVEY §4 "fake TPU
topology"); the chip is exercised by chip_smoke.py through the chip tool.
"""
import os

# Tests run on the virtual 8-device CPU mesh whatever the ambient platform;
# spawned workers inherit the variable through the daemon's environment.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("RAYTPU_OBJECT_STORE_MEMORY", str(64 * 1024 * 1024))
# Disarm the always-on profiler for suites that don't exercise it: on the
# 1-core CI box every armed process's 19 Hz frame-walk steals ~0.7% of the
# one core, and a multi-node test runs ~10 processes — enough aggregate drag
# (~15-20% measured on worker-heavy modules) to push tier-1 past its wall
# budget. Profiler tests arm explicitly (profiler.arm(...) ignores the env;
# cluster fixtures set cfg.profile_hz after apply_env), and chaos scenarios
# that assert the alert->flamegraph chain pin cfg.profile_hz themselves, so
# coverage of the armed path is unchanged. setdefault: export a nonzero
# RAYTPU_PROFILE_HZ to run the whole suite armed.
os.environ.setdefault("RAYTPU_PROFILE_HZ", "0")

import pytest


@pytest.fixture(scope="session")
def cpu_tick():
    """The smallest step time.thread_time() takes on this host, in seconds:
    under a microsecond where the kernel accounts a thread's CPU time at the
    context switch, a scheduler tick (10 ms on the chip's host) where it
    accounts by the tick; clock_getres says 1 ns of both. A tick clock charges
    a whole tick to whoever runs when it fires, so an interval's CPU seconds
    may read up to a tick over its wall seconds: tests of the two clocks side
    by side allow for it."""
    import time

    steps, last, until = [], time.thread_time(), time.monotonic() + 0.05
    while time.monotonic() < until or not steps:
        now = time.thread_time()
        if now > last:
            steps.append(now - last)
        last = now
    return min(steps)


@pytest.fixture(scope="module")
def shared_ray():
    import ray_tpu as rt

    rt.init(num_cpus=8)
    yield rt
    rt.shutdown()


@pytest.fixture
def fresh_cluster():
    from ray_tpu.core.api import Cluster
    from ray_tpu.core.config import get_config

    # Tests tune the cluster's knobs (inline caps, chunk sizes) through
    # cluster.config — which IS the process-global Config. Snapshot and
    # restore it, or one test's tuning silently reshapes every later module
    # (a 4 MiB inline cap left by test_object_transfer flipped
    # test_state_api's shm attribution to "memory" 40 tests later).
    cfg = get_config()
    snap = cfg.to_dict()
    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()
    for k, v in snap.items():
        setattr(cfg, k, v)


@pytest.fixture(autouse=True, scope="module")
def _no_cluster_leaks(request):
    """Module-boundary leak sentinel (round-5 verdict action item): a module
    that leaves a live in-process Cluster, an initialized driver session, or
    a session auth token behind fails HERE — at the leak's source — instead
    of poisoning whatever module happens to run 40 tests later (the
    test_start_cli order-sensitivity was exactly such a leak: clusters whose
    tests called only rt.shutdown(), which detaches the driver but never
    stops an address-connected cluster). The sentinel also cleans up so one
    leaky module still can't cascade."""
    from ray_tpu.core import api, rpc
    from ray_tpu.core.config import get_config

    before = list(api._LIVE_CLUSTERS)
    cfg_before = get_config().to_dict()
    yield
    leaks = []
    if api._global_worker is not None:
        leaks.append("driver session left initialized (missing rt.shutdown())")
        try:
            api.shutdown()
        except Exception:
            pass
    for c in [c for c in list(api._LIVE_CLUSTERS) if c not in before]:
        leaks.append(
            f"in-process Cluster {getattr(c, 'controller_addr', '?')} left running "
            "(rt.shutdown() detaches the driver; call cluster.shutdown() too)"
        )
        try:
            c.shutdown()
        except Exception:
            pass
    cfg = get_config()
    env_token = type(cfg)().apply_env().auth_token
    if cfg.auth_token and cfg.auth_token != env_token and not api._token_owned_by_live_cluster(cfg.auth_token):
        leaks.append(f"session auth token '{cfg.auth_token[:8]}…' leaked into the global config")
        cfg.auth_token = env_token
        rpc.set_auth_token(env_token or None)
    # Config drift: tests tune cluster knobs through the process-global
    # Config (cluster.config aliases it); a module must put back what it
    # changed or it silently reshapes every later module's clusters.
    drift = {
        k: (cfg_before[k], v) for k, v in get_config().to_dict().items()
        if k != "auth_token" and v != cfg_before[k]
    }
    if drift:
        leaks.append(f"process-global Config drifted: {drift}")
        for k, v in cfg_before.items():
            if k != "auth_token":
                setattr(cfg, k, v)
    assert not leaks, f"{request.module.__name__} leaked cross-test state:\n  " + "\n  ".join(leaks)


# Per-test timeout (reference: pytest.ini's 180s default): one hung
# collective/RPC must not eat the whole suite. SIGALRM-based (no
# pytest-timeout in this image); generous default because CartPole learning
# tests legitimately run minutes on this 1-core host.
import signal

TEST_TIMEOUT_S = int(os.environ.get("RAYTPU_TEST_TIMEOUT_S", "600"))


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    def _handler(signum, frame):
        raise TimeoutError(f"test exceeded {TEST_TIMEOUT_S}s timeout")

    old = signal.signal(signal.SIGALRM, _handler)
    signal.alarm(TEST_TIMEOUT_S)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
