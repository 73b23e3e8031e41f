"""TPU accelerator manager: topology detection, labels, chip isolation.

Role-equivalent to the reference's TPU accelerator plugin
(/root/reference/python/ray/_private/accelerators/tpu.py, 683 LoC): autodetect
the slice from GCE metadata / GKE env vars (tpu.py:19-35 uses
TPU_ACCELERATOR_TYPE / TPU_TOPOLOGY / TPU_NAME / TPU_WORKER_ID), compute
chips-per-host (tpu.py:136), validate topology strings (tpu.py:89), expose
TPU_VISIBLE_CHIPS-style isolation (tpu.py:37), and advertise node labels
(slice name, worker id, pod type) plus the ``TPU-{pod}-head`` gang-resource
on worker 0 (tpu.py:224 reserve_tpu_slice).

No GCE metadata server is assumed here: detection reads the environment and
counts the chip device files. It never asks JAX: initialising a backend is
what claims a chip, and the daemon that advertises chips must leave them to
its workers. This module must stay importable without jax.
"""
from __future__ import annotations

import os
import re
from typing import Optional

# Node label keys (reference: ray_constants RAY_NODE_TPU_* keys).
TPU_SLICE_NAME_LABEL = "raytpu.io/tpu-slice-name"
TPU_WORKER_ID_LABEL = "raytpu.io/tpu-worker-id"
TPU_POD_TYPE_LABEL = "raytpu.io/tpu-pod-type"
TPU_TOPOLOGY_LABEL = "raytpu.io/tpu-topology"
TPU_VERSION_LABEL = "raytpu.io/tpu-version"

VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"

# generation -> chips per host for full hosts (v4/v5p: 4 chips/host;
# v5e/v6e: 8 for 16+ chip slices, else chips==slice size on one host).
_GEN_CHIPS_PER_HOST = {"v2": 4, "v3": 4, "v4": 4, "v5p": 4, "v5litepod": 8, "v5e": 8, "v6e": 8}


def _accelerator_type() -> Optional[str]:
    return os.environ.get("TPU_ACCELERATOR_TYPE")


def parse_accelerator_type(acc_type: str) -> tuple[str, int]:
    """'v4-16' -> ('v4', 16 logical devices); 'v5litepod-8' -> ('v5litepod', 8)."""
    m = re.fullmatch(r"(v\d+[a-z]*)-(\d+)", acc_type)
    if not m:
        raise ValueError(f"invalid TPU accelerator type {acc_type!r}")
    return m.group(1), int(m.group(2))


def validate_topology(topology: str) -> tuple[int, ...]:
    """'2x2x2' -> (2, 2, 2). Reference validates the same way (tpu.py:89)."""
    if not re.fullmatch(r"\d+(x\d+)*", topology):
        raise ValueError(f"invalid TPU topology {topology!r}")
    return tuple(int(x) for x in topology.split("x"))


def get_num_tpu_chips(acc_type: str) -> int:
    gen, count = parse_accelerator_type(acc_type)
    # v2/v3/v5p counts are in TensorCores (2 cores per chip); v4 counts are in
    # chips for the -8 form... The reference normalizes via topology; we treat
    # v2/v3 counts as cores (//2) and everything else as chips.
    if gen in ("v2", "v3"):
        return max(1, count // 2)
    if gen == "v5p":
        return max(1, count // 2)
    return count


def get_chips_per_host(acc_type: str) -> int:
    gen, _ = parse_accelerator_type(acc_type)
    per_host = _GEN_CHIPS_PER_HOST.get(gen, 4)
    chips = get_num_tpu_chips(acc_type)
    return min(per_host, chips)


def get_num_hosts(acc_type: str) -> int:
    chips = get_num_tpu_chips(acc_type)
    return max(1, chips // get_chips_per_host(acc_type))


def get_tpu_slice_name() -> Optional[str]:
    return os.environ.get("TPU_NAME")


def get_tpu_worker_id() -> Optional[int]:
    wid = os.environ.get("TPU_WORKER_ID")
    return int(wid) if wid is not None else None


def get_tpu_pod_type() -> Optional[str]:
    return _accelerator_type()


def chip_device_files() -> list[str]:
    """This host's TPU chips as the kernel exposes them, one file a chip:
    ``/dev/accel<N>`` on older hosts, ``/dev/vfio/<N>`` on v5e and newer.
    Listing them claims nothing; a process that has initialised the TPU
    backend holds them open."""
    import glob

    return sorted(glob.glob("/dev/accel[0-9]*") + glob.glob("/dev/vfio/[0-9]*"))


def get_visible_chips() -> Optional[list[str]]:
    raw = os.environ.get(VISIBLE_CHIPS_ENV)
    if raw is None:
        return None
    return [c for c in raw.split(",") if c != ""]


def set_visible_chips(chip_ids: list[int] | list[str], env: dict | None = None):
    """Restrict a worker process to a subset of the host's chips (reference:
    TPU_VISIBLE_CHIPS isolation, tpu.py:37)."""
    target = env if env is not None else os.environ
    target[VISIBLE_CHIPS_ENV] = ",".join(str(c) for c in chip_ids)
    # JAX honors TPU chip visibility through these:
    target["TPU_CHIPS_PER_PROCESS_BOUNDS"] = f"1,1,{len(chip_ids)}" if chip_ids else ""


def preemption_notice(node_id: str, labels: Optional[dict] = None):
    """Consult the chaos plane for an injected TPU-preemption notice for
    this host (reference: GCE preempts TPU VMs with a short notice; the
    reference's chaos suites simulate it by killing raylets on a timer —
    here it is a seeded, replayable schedule decision). Called once per
    daemon heartbeat; returns the Fault (its ``delay_s`` is the grace
    window) or None. Real-metadata-server detection would slot in here
    alongside the injected path.
    """
    from ray_tpu import chaos

    labels = labels or {}
    return chaos.maybe_inject(
        "tpu.preempt",
        node=node_id[:12],
        worker_id=labels.get(TPU_WORKER_ID_LABEL, ""),
        slice=labels.get(TPU_SLICE_NAME_LABEL, ""),
    )


class TPUAcceleratorManager:
    """Accelerator manager ABC-equivalent (reference: accelerators/accelerator.py)."""

    RESOURCE_NAME = "TPU"

    @staticmethod
    def detect() -> tuple[dict, dict]:
        return detect_tpu_resources()

    @staticmethod
    def slice_head_resource(pod_type: str) -> str:
        # Reference: f"TPU-{pod_type}-head" (tpu.py:224): worker 0 of a slice
        # advertises 1 unit; reserving it gang-locks the slice.
        return f"TPU-{pod_type}-head"


def detect_tpu_resources() -> tuple[dict, dict]:
    """Returns (resources, labels) the node daemon should advertise, from
    the TPU runtime's environment variables (set on TPU VMs and GKE) and the
    chip device files. A host without the variables advertises no chips;
    pass ``resources={"TPU": n}``."""
    resources: dict = {}
    labels: dict = {}
    acc_type = _accelerator_type()
    num_chips = 0
    if acc_type:
        try:
            # What is present beats what the environment describes: a host
            # may carry a slice's variables and expose fewer chips.
            visible = get_visible_chips()
            files = chip_device_files()
            num_chips = (
                len(visible) if visible is not None
                else len(files) if files
                else get_chips_per_host(acc_type)
            )
            labels[TPU_POD_TYPE_LABEL] = acc_type
            gen, _ = parse_accelerator_type(acc_type)
            labels[TPU_VERSION_LABEL] = gen
        except ValueError:
            return {}, {}
    if num_chips <= 0:
        return {}, {}
    resources["TPU"] = float(num_chips)
    topology = os.environ.get("TPU_TOPOLOGY")
    if topology:
        labels[TPU_TOPOLOGY_LABEL] = topology
    slice_name = get_tpu_slice_name()
    if slice_name:
        labels[TPU_SLICE_NAME_LABEL] = slice_name
    worker_id = get_tpu_worker_id()
    if worker_id is not None:
        labels[TPU_WORKER_ID_LABEL] = str(worker_id)
        if worker_id == 0 and acc_type:
            resources[TPUAcceleratorManager.slice_head_resource(acc_type)] = 1.0
    return resources, labels


# ---------------------------------------------------------------------------
# Slice gang reservation (reference: reserve_tpu_slice, tpu.py:224 +
# SlicePlacementGroup, util/tpu.py:181)
# ---------------------------------------------------------------------------


class SliceReservation:
    """A held TPU slice: slice-name label selector + the head-resource PG
    that locks the slice. Release it when the gang is torn down, or the
    slice stays locked against future reservations (incl. our own gang
    restart)."""

    def __init__(self, label_selector: dict, head_pg):
        self.label_selector = label_selector
        self.head_pg = head_pg
        self._released = False

    def release(self):
        if self._released or self.head_pg is None:
            return
        self._released = True
        import ray_tpu as rt

        try:
            rt.remove_placement_group(self.head_pg)
        except Exception:
            pass


def reserve_tpu_slice(accelerator_type: str, topology: Optional[str] = None,
                      num_slices: int = 1, timeout: float = 60.0) -> Optional[SliceReservation]:
    """Reserve whole TPU slice(s) for gang scheduling.

    Places one bundle per slice on the slice-head resource (``TPU-{pod}-head``,
    advertised only by worker 0 of each slice, STRICT_SPREAD so each bundle
    locks a distinct slice), then reads each head node's slice-name label.
    Returns None when no slice-head resource exists in the cluster (CPU test
    topologies without TPU labels).
    """
    import ray_tpu as rt

    if topology is not None:
        dims = validate_topology(topology)
        chips = 1
        for d in dims:
            chips *= d
        expect = get_num_tpu_chips(accelerator_type)
        if chips != expect:
            raise ValueError(
                f"topology {topology} has {chips} chips but {accelerator_type} has {expect}"
            )
    head_res = TPUAcceleratorManager.slice_head_resource(accelerator_type)
    if rt.cluster_resources().get(head_res, 0) < num_slices:
        return None
    pg = rt.placement_group(
        [{head_res: 1.0} for _ in range(num_slices)],
        strategy="STRICT_SPREAD" if num_slices > 1 else "STRICT_PACK",
        name=f"slice-{accelerator_type}",
    )
    if not pg.ready(timeout=timeout):
        rt.remove_placement_group(pg)
        raise TimeoutError(
            f"no {num_slices} free {accelerator_type} slice(s) (resource {head_res})"
        )
    node_labels = {n["NodeID"]: n.get("labels", {}) for n in rt.nodes()}
    names = [
        node_labels.get(nid, {}).get(TPU_SLICE_NAME_LABEL)
        for nid in pg.bundle_nodes()
    ]
    names = [n for n in names if n]
    if not names:
        return SliceReservation({}, pg)
    # Selector syntax per the controller's matcher: "v" or "in(a,b)".
    selector = {
        TPU_SLICE_NAME_LABEL: names[0] if len(names) == 1 else f"in({','.join(names)})"
    }
    return SliceReservation(selector, pg)


class SlicePlacementGroup:
    """Multi-host slice gang: one bundle per TPU host, STRICT_SPREAD and
    label-pinned to the reserved slice(s) (reference: util/tpu.py:181)."""

    def __init__(self, accelerator_type: str, topology: Optional[str] = None,
                 num_slices: int = 1):
        import ray_tpu as rt

        self.accelerator_type = accelerator_type
        self.num_hosts = get_num_hosts(accelerator_type) * num_slices
        chips = get_chips_per_host(accelerator_type)
        self.reservation = reserve_tpu_slice(
            accelerator_type, topology, num_slices=num_slices
        )
        selector = self.reservation.label_selector if self.reservation else {}
        self.pg = rt.placement_group(
            [{"TPU": float(chips)} for _ in range(self.num_hosts)],
            strategy="STRICT_SPREAD" if self.num_hosts > 1 else "PACK",
            name=f"slice-pg-{accelerator_type}",
            label_selector=selector,
        )

    @property
    def label_selector(self) -> dict:
        return self.reservation.label_selector if self.reservation else {}

    def ready(self, timeout: float = 60.0) -> bool:
        return self.pg.ready(timeout=timeout)

    def release(self):
        import ray_tpu as rt

        try:
            rt.remove_placement_group(self.pg)
        except Exception:
            pass
        if self.reservation:
            self.reservation.release()
