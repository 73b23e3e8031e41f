"""WorkerGroup: gang of train-worker actors pinned to placement-group bundles.

Role-equivalent to the reference's WorkerGroup
(/root/reference/python/ray/train/v2/_internal/execution/worker_group/
worker_group.py:104 — PG creation at :269, one actor per bundle at :376-391,
health barrier) plus the JAX backend's rendezvous
(v2/jax/config.py:103 `_JaxBackend.on_start`: rank-0 address broadcast then
``jax.distributed.initialize`` on every worker). On the fake CPU topology the
distributed init is skipped — collectives run inside the single-process mesh
(SURVEY §4 fake-TPU testing technique).
"""
from __future__ import annotations

import os
import socket
import threading
import traceback
from typing import Any, Callable, Optional

import ray_tpu as rt
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.session import TrainSession, _set_session


class TrainWorker:
    """Actor hosting one rank of the SPMD gang; runs the user fn in a thread."""

    def __init__(self, world_rank: int, world_size: int, experiment_name: str,
                 storage_path: str):
        self.world_rank = world_rank
        self.world_size = world_size
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self.session: Optional[TrainSession] = None
        self.thread: Optional[threading.Thread] = None
        self.error: Optional[str] = None
        self.finished = False

    # -- rendezvous --------------------------------------------------------
    def get_address(self) -> dict:
        host = socket.gethostname()
        try:
            ip = socket.gethostbyname(host)
        except OSError:
            ip = "127.0.0.1"
        from ray_tpu.core import api as _api

        core = _api._require_worker()
        # The coordinator port must be free on THIS host (rank 0 binds it);
        # picking it elsewhere (driver/controller) races other machines.
        # node_id/worker_addr: preemption-notice attribution + the elastic
        # plane's raw-lane transfer endpoint.
        return {"hostname": host, "ip": ip, "pid": os.getpid(),
                "free_port": _free_port(), "node_id": core.node_id,
                "worker_addr": core.address}

    def setup_distributed(self, coordinator_addr: str, num_processes: int,
                          process_id: int, use_tpu: bool) -> bool:
        """jax.distributed bootstrap (reference: _setup_jax_distributed_environment,
        v2/jax/config.py:30-86), skipped when the gang is a single process or
        on the fake topology. A worker that was scheduled onto TPU resources
        (or told use_tpu) then claims its chips here and raises unless its
        own process sees a TPU with that many chips — never trains from
        another backend under a TPU's name. Same trigger as the LLM replica:
        the resources the scheduler assigned."""
        os.environ["RAYTPU_COORDINATOR"] = coordinator_addr
        chips = rt.get_runtime_context().get_assigned_resources().get("TPU", 0)
        if not (use_tpu or chips):
            return True
        from ray_tpu.accel import device as _device

        _device.enable_compile_cache()
        if use_tpu and num_processes > 1:
            import jax

            jax.distributed.initialize(
                coordinator_address=coordinator_addr,
                num_processes=num_processes,
                process_id=process_id,
            )
        _device.require_tpu(chips, f"train worker {self.world_rank}")
        return True

    # -- training lifecycle ------------------------------------------------
    def start(self, train_fn: Callable, config: dict,
              resume_checkpoint_path: Optional[str] = None,
              dataset_shards: Optional[dict] = None,
              resume_live: Optional[dict] = None) -> bool:
        resume = Checkpoint(resume_checkpoint_path) if resume_checkpoint_path else None
        self.session = TrainSession(
            world_rank=self.world_rank,
            world_size=self.world_size,
            local_rank=0,
            experiment_name=self.experiment_name,
            storage_path=self.storage_path,
            resume_checkpoint=resume,
            dataset_shards=dataset_shards,
            resume_live=resume_live,
        )
        self.error = None
        self.finished = False

        def run():
            _set_session(self.session)
            try:
                if _fn_wants_config(train_fn):
                    train_fn(config)
                else:
                    train_fn()
            except BaseException:  # noqa: BLE001
                self.error = traceback.format_exc()
            finally:
                self.finished = True
                _set_session(None)

        self.thread = threading.Thread(target=run, name="train_fn", daemon=True)
        self.thread.start()
        return True

    def poll(self) -> dict:
        reports = self.session.drain_reports() if self.session else []
        return {"reports": reports, "finished": self.finished, "error": self.error}

    def stop(self) -> bool:
        if self.session:
            self.session.stop_event.set()
        return True

    # -- elastic plane (live N->M reshard, ray_tpu/elastic/) ---------------
    def reshard_export(self, tid: str) -> Optional[dict]:
        """Park this rank's last keep_live() snapshot for transfer ``tid``;
        returns the export's wire metadata (None when the fn never
        registered live state — the controller falls back to checkpoints)."""
        from ray_tpu.core import api as _api
        from ray_tpu.elastic import transfer as _transfer

        snap = self.session.live_snapshot() if self.session else None
        if snap is None:
            return None
        # copy=False: the snapshot's leaves are either the session's private
        # keep_live(copy=True) copies (never mutated once parked) or
        # immutable jax arrays from keep_live(copy=False) — export_state
        # parks references and the old per-leaf memcpy disappears from the
        # preemption-to-export critical path.
        meta = _transfer.export_state(
            tid, self.world_rank, snap["state"], snap["sharded"],
            seq=snap["seq"], meta=snap["meta"], copy=False)
        meta["addr"] = _api._require_worker().address
        return meta

    def reshard_pull(self, tid: str, sources: list, world: int, rank: int,
                     self_old_rank: Optional[int] = None) -> dict:
        """Assemble this worker's slice of the new mesh's state from the
        gang's live exports (raw-lane pulls; own-export runs are local
        memcpys). The payload parks on the actor until restart_live()."""
        from ray_tpu.core import api as _api
        from ray_tpu.elastic import transfer as _transfer

        core = _api._require_worker()
        res = core._run(
            _transfer.pull_state(core, tid, sources, world, rank,
                                 self_rank=self_old_rank),
            timeout=core.config.elastic_transfer_timeout_s * 4 + 10)
        self._resumed = res
        return res["stats"]

    def reshard_release(self, tid: str) -> bool:
        from ray_tpu.elastic import transfer as _transfer

        return _transfer.release(tid)

    def restart_live(self, train_fn: Callable, config: dict, world_rank: int,
                     world_size: int,
                     dataset_shards: Optional[dict] = None) -> bool:
        """Resume the train fn on the resized mesh: adopt the (possibly
        changed) rank/world, hand the fn the resharded payload via
        train.live_resume(), and leave checkpoints out of the loop."""
        resumed = getattr(self, "_resumed", None)
        self._resumed = None
        self.world_rank = world_rank
        self.world_size = world_size
        return self.start(train_fn, config, None, dataset_shards,
                          resume_live=resumed)


def _fn_wants_config(fn) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return True
    return len(sig.parameters) >= 1


class WorkerGroup:
    """Creates the PG + actors; knows how to poll and tear down the gang.

    ``gang_pg=False`` (the elastic-live mode) schedules workers by plain
    resources instead of one N-bundle placement group: a live resize keeps
    surviving actors and adds/drops members, which a fixed-bundle PG cannot
    express — elastic gangs trade strict gang placement for resize-in-place.
    """

    def __init__(self, scaling: ScalingConfig, experiment_name: str,
                 storage_path: str, gang_pg: bool = True):
        self.scaling = scaling
        self.experiment_name = experiment_name
        self.storage_path = storage_path
        self.gang_pg = gang_pg
        self.pg = None
        self.reservation = None
        self.workers: list = []
        self.node_ids: list = []  # parallel to workers (preemption matching)
        self._split_coordinators: list = []

    def _spawn(self, rank: int, n: int):
        res = self.scaling.worker_resources()
        worker_cls = rt.remote(TrainWorker)
        opts: dict = {"resources": dict(res),
                      "max_concurrency": 4}  # poll/stop can't block start()
        if self.pg is not None:
            opts.update(placement_group=self.pg,
                        placement_group_bundle_index=rank)
        if self.reservation is not None:
            opts.update(label_selector=dict(self.reservation.label_selector))
        return worker_cls.options(**opts).remote(
            rank, n, self.experiment_name, self.storage_path)

    def start(self) -> None:
        n = self.scaling.num_workers
        res = self.scaling.worker_resources()
        label_selector: dict = {}
        if self.scaling.use_tpu and self.scaling.accelerator_type:
            from ray_tpu.accel.tpu import reserve_tpu_slice

            self.reservation = reserve_tpu_slice(
                self.scaling.accelerator_type, self.scaling.topology,
                num_slices=self.scaling.num_slices,
            )
            if self.reservation is not None:
                label_selector.update(self.reservation.label_selector)
        if self.gang_pg:
            bundles = [dict(res) for _ in range(n)]
            self.pg = rt.placement_group(
                bundles, strategy=self.scaling.placement_strategy,
                name=f"{self.experiment_name}-gang",
                label_selector=label_selector,
            )
            if not self.pg.ready(timeout=60.0):
                raise TimeoutError(
                    f"placement group for {n} train workers not schedulable: {bundles}"
                )
        self.workers = [self._spawn(i, n) for i in range(n)]
        # Health barrier + rendezvous.
        addrs = rt.get([w.get_address.remote() for w in self.workers], timeout=60)
        self.node_ids = [a.get("node_id", "") for a in addrs]
        coordinator = f"{addrs[0]['ip']}:{addrs[0]['free_port']}"
        rt.get(
            [
                w.setup_distributed.remote(
                    coordinator, n, i, self.scaling.use_tpu
                )
                for i, w in enumerate(self.workers)
            ],
            timeout=120,
        )

    def make_shards(self, datasets: Optional[dict], n: int) -> list[dict]:
        """Fresh streaming splits per gang incarnation (and per live
        resize): a restarted/resized gang must not consume a half-drained
        epoch from the previous one (reference: DataConfig.configure runs
        per worker-group start). The PREVIOUS incarnation's split
        coordinators die here — a long-lived elastic job resizes in place
        without ever reaching shutdown(), and keeping one coordinator per
        dataset per resize alive would leak them for the run's lifetime."""
        for coord in self._split_coordinators:
            try:
                rt.kill(coord)
            except Exception:
                pass
        self._split_coordinators = []
        shards_per_worker: list[dict] = [{} for _ in range(n)]
        for ds_name, ds in (datasets or {}).items():
            iterators = ds.streaming_split(n)
            # Coordinator actors die with the gang (shutdown), not the cluster.
            self._split_coordinators.append(iterators[0]._coord)
            for i, it in enumerate(iterators):
                shards_per_worker[i][ds_name] = it
        return shards_per_worker

    def run(self, train_fn: Callable, config: dict,
            resume_checkpoint_path: Optional[str] = None,
            datasets: Optional[dict] = None) -> None:
        shards_per_worker = self.make_shards(datasets, len(self.workers))
        rt.get(
            [
                w.start.remote(train_fn, config, resume_checkpoint_path,
                               shards_per_worker[i])
                for i, w in enumerate(self.workers)
            ],
            timeout=60,
        )

    def poll(self) -> list[dict]:
        # Per-worker gets: a dead rank must not mask the survivors' reports
        # (rank 0's checkpoints especially — they are the restart point).
        refs = [w.poll.remote() for w in self.workers]
        out = []
        for i, r in enumerate(refs):
            try:
                out.append(rt.get(r, timeout=60))
            except Exception as e:
                out.append(
                    {"reports": [], "finished": False,
                     "error": f"worker {i} died: {e}"}
                )
        return out

    def stop_all(self) -> None:
        """Graceful stop: set every rank's stop event (its next report()
        raises, ending the train thread) — used by elastic resize so final
        checkpoints drain before teardown."""
        refs = [w.stop.remote() for w in self.workers]
        for r in refs:
            try:
                rt.get(r, timeout=10)
            except Exception:
                pass

    def spawn_extra(self, k: int) -> list:
        """Fresh member actors for a live grow (ranks assigned later by
        restart_live). Only valid without a gang PG — a fixed-bundle PG
        cannot grow."""
        if self.pg is not None:
            raise RuntimeError("cannot grow a PG-pinned gang in place")
        spawned = [self._spawn(len(self.workers) + i, len(self.workers) + k)
                   for i in range(k)]
        try:
            addrs = rt.get([w.get_address.remote() for w in spawned], timeout=60)
        except Exception:
            # A failed health barrier must not orphan the actors: they are
            # not yet in self.workers, so nothing else would ever kill
            # them, and their reservations would starve the fallback gang.
            for w in spawned:
                try:
                    rt.kill(w)
                except Exception:
                    pass
            raise
        self.workers += spawned
        self.node_ids += [a.get("node_id", "") for a in addrs]
        return spawned

    def adopt(self, workers: list, node_ids: list) -> None:
        """Live resize membership swap: ``workers`` (old-rank order becomes
        new-rank order) stay; every other current member is killed."""
        keep = {id(w) for w in workers}
        for w in self.workers:
            if id(w) not in keep:
                try:
                    rt.kill(w)
                except Exception:
                    pass
        self.workers = list(workers)
        self.node_ids = list(node_ids)

    def shutdown(self) -> None:
        for w in self.workers:
            try:
                rt.kill(w)
            except Exception:
                pass
        self.workers = []
        for coord in self._split_coordinators:
            try:
                rt.kill(coord)
            except Exception:
                pass
        self._split_coordinators = []
        if self.pg is not None:
            try:
                rt.remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
        if self.reservation is not None:
            self.reservation.release()
            self.reservation = None


def _free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port
