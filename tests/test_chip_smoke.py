"""chip_smoke.py's contract off the chip, and the rules it stands on: no
fallback that hides the device, one process for each chip, a compile cache
that can be placed from outside."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(args, **env):
    return subprocess.run(
        [sys.executable, *args], cwd=REPO, capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO, **env},
    )


def test_bare_smoke_fails_without_chip():
    proc = _run([SMOKE])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and "platform 'cpu'" in proc.stderr
    assert '"ok"' not in proc.stdout  # no result line


@pytest.mark.slow
def test_rehearsal_end_to_end():
    proc = _run([SMOKE, "--rehearsal"])
    assert proc.returncode == 0, proc.stdout[-3000:] + proc.stderr[-3000:]
    assert proc.stdout.startswith("REHEARSAL")
    assert proc.stdout.rstrip().endswith("rehearsal complete: no result")
    assert '"ok"' not in proc.stdout
    for phase in ("kernels", "train", "repeat", "serve"):
        assert f"[{phase}] platform=cpu" in proc.stdout
    # The exact hit is the ordinary decode, on the engine and through the proxy.
    assert "position P-1: 8 of 8 tokens equal" in proc.stdout
    assert "exact repeat == the engine's hit (== its ordinary decode): True" in proc.stdout


_OFF_CHIP_PROCESS = """
import sys, numpy as np
import jax  # imported, as in any driver that imports ray_tpu.llm; never used
import ray_tpu as rt
from ray_tpu.accel.device import backend_initialized, enable_compile_cache
from ray_tpu.core import serialization
from ray_tpu.obs import profiler
rt.init(num_cpus=1)
try:
    value = serialization.deserialize(open(sys.argv[1], "rb").read())
    assert type(value["x"]) is np.ndarray and type(value["sharded"]) is np.ndarray, value
    assert value["x"].tolist() == [1.0, 2.0] and value["sharded"].shape == (8, 4)
    assert profiler.device_memory_records() == []
    assert not backend_initialized(), "fetching a device array initialised a backend"
finally:
    rt.shutdown()
print("CACHE", enable_compile_cache(), jax.config.jax_compilation_cache_dir)
"""


def test_driver_stays_off_the_chip_and_cache_path_is_fixed(tmp_path, monkeypatch):
    """A driver that holds no device gets host arrays back from a fetch and
    reports no device metrics — neither initialises a backend (that would
    claim the chip). Its default compile cache is the one in-checkout path
    this process computes too; with the variable set, code sets nothing."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.accel import device
    from ray_tpu.core import serialization
    from ray_tpu.parallel import MeshSpec

    sharded = jax.device_put(
        jnp.ones((8, 4)), NamedSharding(MeshSpec(data=-1).build(), P("data")))
    blob = tmp_path / "blob"
    blob.write_bytes(serialization.serialize({"x": jnp.array([1.0, 2.0]), "sharded": sharded})[0])
    assert device.backend_initialized()  # this process made the arrays

    env = {k: v for k, v in os.environ.items() if k != device.COMPILE_CACHE_ENV}
    proc = subprocess.run(
        [sys.executable, "-c", _OFF_CHIP_PROCESS, str(blob)], cwd=str(tmp_path),
        capture_output=True, text=True, timeout=300,
        env={**env, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO},
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    fixed = os.path.join(REPO, ".jax_compile_cache")
    assert proc.stdout.split()[-3:] == ["CACHE", fixed, fixed]
    assert device.DEFAULT_COMPILE_CACHE_DIR == fixed

    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(device.COMPILE_CACHE_ENV, str(tmp_path / "placed"))
    assert device.enable_compile_cache() == str(tmp_path / "placed")
    assert jax.config.jax_compilation_cache_dir == before  # JAX reads the variable itself


def test_explicit_kernels_raise_without_tpu():
    from ray_tpu.models.transformer import TransformerConfig, _attention
    from ray_tpu.ops.attention import flash_attention
    from ray_tpu.ops.paged_attention import paged_attention

    q = jnp.zeros((1, 128, 2, 64))
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        flash_attention(q, q, q)
    with pytest.raises(ValueError, match="S % 128"):
        flash_attention(q[:, :100], q[:, :100], q[:, :100], interpret=True)
    cfg = TransformerConfig(d_model=128, n_heads=2, attention_impl="flash")
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        _attention(q, q, q, cfg)
    pages = jnp.zeros((1, 2, 3, 32, 64))
    with pytest.raises(RuntimeError, match="needs a TPU backend"):
        paged_attention(q[:, 0], q[:, 0], q[:, 0], pages, pages, jnp.ones(1, jnp.int32),
                        jnp.zeros((1, 2), jnp.int32), 0)


def test_flash_runs_on_its_batch_shard_under_a_mesh(monkeypatch):
    """Under a data-parallel mesh the train path's flash call is shard_map'd:
    each device's kernel sees its own rows (and heads, under tp), not the
    gathered batch a bare custom call would get from GSPMD."""
    import ray_tpu.ops.attention as att
    from ray_tpu.models.transformer import TransformerConfig, _attention
    from ray_tpu.parallel import MeshSpec, ShardingStrategy
    from ray_tpu.parallel.sharding import use_strategy

    seen, kernel = [], att.flash_attention

    def interpreted(q, k, v, **kw):
        seen.append((q.shape, k.shape))
        return kernel(q, k, v, **{**kw, "interpret": True})

    monkeypatch.setattr(att, "flash_attention", interpreted)
    cfg = TransformerConfig(d_model=256, n_heads=4, n_kv_heads=2, attention_impl="flash",
                            attention_block_q=128, attention_block_k=128, dtype=jnp.float32)
    B, S = 8, 128
    q, k, v = (jax.random.normal(jax.random.PRNGKey(i), (B, S, h, 64))
               for i, h in enumerate((4, 2, 2)))
    want = att.mha_reference(q, k, v, causal=True)
    for strategy, mesh, shard in (
        (ShardingStrategy.dp(), MeshSpec(data=-1).build(), ((1, S, 4, 64), (1, S, 2, 64))),
        (ShardingStrategy.dp() | ShardingStrategy.tp(), MeshSpec(data=-1, tensor=2).build(),
         ((2, S, 2, 64), (2, S, 1, 64))),
    ):
        seen.clear()
        with use_strategy(strategy), mesh:
            got = jax.jit(lambda q, k, v: _attention(q, k, v, cfg))(q, k, v)
        assert seen == [shard]
        np.testing.assert_allclose(got, want, atol=2e-5)


def test_train_worker_scheduled_on_tpu_raises_on_cpu_host(tmp_path):
    import ray_tpu as rt
    from ray_tpu import train

    rt.init(num_cpus=2, resources={"TPU": 1.0})
    try:
        trainer = train.JaxTrainer(
            lambda config: None,
            # The trigger is the resource the scheduler assigned, as for the
            # LLM replica: use_tpu need not be said.
            scaling_config=train.ScalingConfig(
                num_workers=1, resources_per_worker={"TPU": 1.0}),
            run_config=train.RunConfig(name="cpu_host", storage_path=str(tmp_path)),
        )
        with pytest.raises(Exception, match="scheduled onto 1 TPU chip.*platform 'cpu'"):
            result = trainer.fit()
            raise RuntimeError(result.error)
    finally:
        rt.shutdown()


def test_require_tpu_counts_this_process_not_the_gang(monkeypatch):
    """After jax.distributed.initialize the global count is every host's
    chips; a worker short of its own must still be refused."""
    from ray_tpu.accel import device

    gang = {"platform": "tpu", "device_kind": "TPU v5 lite", "device_count": 8,
            "local_device_count": 2, "jax": jax.__version__, "pid": 0}
    monkeypatch.setattr(device, "device_report", lambda: gang)
    device.require_tpu(2, "worker")
    with pytest.raises(RuntimeError, match="scheduled onto 4 TPU chip.*2 local device"):
        device.require_tpu(4, "worker")


def test_backend_probe_is_loud_if_jax_moves_it(monkeypatch):
    """No module: no backend. A module still importing: no backend. A module
    without the probe: an error, never a standing 'no'."""
    import types

    from ray_tpu.accel import device

    name = "jax._src.xla_bridge"
    assert device.backend_initialized() == sys.modules[name].backends_are_initialized()
    monkeypatch.delitem(sys.modules, name)
    assert device.backend_initialized() is False
    moved = types.ModuleType(name)
    moved.__spec__ = types.SimpleNamespace(_initializing=True)
    monkeypatch.setitem(sys.modules, name, moved)
    assert device.backend_initialized() is False
    moved.__spec__ = types.SimpleNamespace(_initializing=False)
    with pytest.raises(AttributeError, match="backends_are_initialized"):
        device.backend_initialized()


_ZOMBIE_LEADER = """
import ctypes, threading, time
threading.Thread(target=time.sleep, args=(60,)).start()
print("up", flush=True)
ctypes.CDLL(None).syscall(60, 0)  # SYS_exit: this thread only, the leader
"""


def test_a_zombie_leader_with_a_running_thread_is_still_live():
    """The smoke waits for a phase's processes to be gone before the next
    phase reaches for the chip. A killed chip holder's leader turns zombie
    while its other threads are still releasing the device (seen on the
    four-chip host): such a process must still count as live."""
    import importlib.util
    import signal
    import time

    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    smoke = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(smoke)
    proc = subprocess.Popen([sys.executable, "-c", _ZOMBIE_LEADER], stdout=subprocess.PIPE,
                            start_new_session=True)
    try:
        assert proc.stdout.readline() == b"up\n"
        for _ in range(100):
            with open(f"/proc/{proc.pid}/stat") as f:
                if f.read().rsplit(")", 1)[1].split()[0] == "Z":
                    break
            time.sleep(0.05)
        else:
            pytest.fail("the leader never turned zombie")
        assert smoke._session_pids(proc.pid) == [proc.pid]
    finally:
        os.killpg(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
    for _ in range(100):
        if not smoke._session_pids(proc.pid):
            break
        time.sleep(0.05)
    assert smoke._session_pids(proc.pid) == []
