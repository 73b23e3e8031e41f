"""Device-mesh construction for single-slice and multi-slice TPU topologies.

The mesh is the primary scheduling domain of this framework (SURVEY.md §7):
every parallelism strategy is a mapping of logical array axes onto these mesh
axes, and XLA inserts the ICI/DCN collectives. Canonical axis order puts the
slowest-varying (DCN-crossing) axes first so that inner axes ride ICI:

    ("replica", "data", "fsdp", "stage", "expert", "seq", "tensor")

- replica: multi-slice data parallelism over DCN (one slice per replica).
- data:    per-slice batch data parallelism.
- fsdp:    ZeRO-3 style parameter/optimizer sharding (combines with data for
           the batch axis).
- stage:   pipeline-parallel stages.
- expert:  MoE expert parallelism.
- seq:     sequence/context parallelism (ring attention neighbours).
- tensor:  Megatron-style tensor parallelism (innermost: highest-bandwidth
           ICI neighbours).

Role-equivalent to the reference's device-group bootstrap
(/root/reference/python/ray/util/collective/collective.py:171
`init_collective_group` + NCCL rendezvous): there, process groups are built at
runtime over NCCL; here, the mesh is a compile-time object and the "group" is
a mesh axis.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Sequence

AXIS_ORDER = ("replica", "data", "fsdp", "stage", "expert", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Declarative mesh shape. -1 on exactly one axis means "infer".

    Example::

        MeshSpec(data=-1, tensor=4).build()   # DP over all but 4-way TP
    """

    replica: int = 1
    data: int = 1
    fsdp: int = 1
    stage: int = 1
    expert: int = 1
    seq: int = 1
    tensor: int = 1

    def sizes(self) -> dict[str, int]:
        return {a: getattr(self, a) for a in AXIS_ORDER}

    def resolved_sizes(self, n_devices: int) -> dict[str, int]:
        sizes = self.sizes()
        unknown = [a for a, s in sizes.items() if s == -1]
        if len(unknown) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {unknown}")
        known = math.prod(s for s in sizes.values() if s != -1)
        if unknown:
            if n_devices % known:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {known}"
                )
            sizes[unknown[0]] = n_devices // known
        elif known != n_devices:
            raise ValueError(f"mesh spec {sizes} needs {known} devices, have {n_devices}")
        return sizes

    def build(self, devices: Optional[Sequence] = None) -> "jax.sharding.Mesh":
        """Materialize a jax Mesh over `devices` (default: all visible)."""
        import jax
        from jax.experimental import mesh_utils
        from jax.sharding import Mesh

        if devices is None:
            devices = jax.devices()
        sizes = self.resolved_sizes(len(devices))
        # mesh_utils lays devices out so inner axes land on ICI neighbours; a
        # shape it cannot lay out is an error, not an enumeration-order mesh.
        dev_array = mesh_utils.create_device_mesh(
            tuple(sizes[a] for a in AXIS_ORDER), devices=list(devices)
        )
        return Mesh(dev_array, AXIS_ORDER)

    def replace_inferred(self, n_devices: int) -> "MeshSpec":
        return MeshSpec(**self.resolved_sizes(n_devices))

    @property
    def n_required(self) -> int:
        """Device count if fully specified; raises if any axis is -1."""
        sizes = self.sizes()
        if any(s == -1 for s in sizes.values()):
            raise ValueError("mesh spec has an inferred axis; pass n_devices")
        return math.prod(sizes.values())


def mesh_shape_for(
    n_devices: int,
    *,
    tensor: int = 1,
    fsdp: int = 1,
    stage: int = 1,
    seq: int = 1,
    expert: int = 1,
    replica: int = 1,
) -> MeshSpec:
    """Convenience: fix the model-parallel axes, infer the data axis."""
    return MeshSpec(
        replica=replica,
        data=-1,
        fsdp=fsdp,
        stage=stage,
        expert=expert,
        seq=seq,
        tensor=tensor,
    ).replace_inferred(n_devices)


def create_mesh(n_devices: Optional[int] = None, **axis_sizes) -> "jax.sharding.Mesh":
    """One-call mesh: create_mesh(tensor=4) -> DP x TP mesh over all devices."""
    import jax

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    if "data" not in axis_sizes and not any(
        axis_sizes.get(a, 1) == -1 for a in AXIS_ORDER
    ):
        axis_sizes["data"] = -1
    return MeshSpec(**axis_sizes).build(devices)


def local_mesh() -> "jax.sharding.Mesh":
    """Trivial single-host mesh: all local devices on the data axis."""
    import jax

    return MeshSpec(data=-1).build(jax.local_devices())
