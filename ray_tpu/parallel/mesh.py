"""Device-mesh construction and TPU topology modeling.

The reference treats accelerators as scalar resources and delegates all
communicator topology to NCCL process groups bootstrapped out-of-band
(reference: python/ray/train/torch/config.py:66 _setup_torch_process_group,
python/ray/util/collective/collective.py:120 init_collective_group). The
TPU-native design inverts this: the topology is a first-class
`jax.sharding.Mesh` over named axes, and every collective is an XLA-program
collective laid out on ICI. This module owns mesh construction.

Axis vocabulary (the framework standard, used by sharding rules, trainers
and learners):

    "data"    - pure data parallelism (batch split, gradient psum)
    "fsdp"    - sharded data parallelism (params/opt-state sharded, ZeRO-3)
    "stage"   - pipeline parallelism (GPipe microbatches over ppermute)
    "tensor"  - tensor/model parallelism (weight matrices split)
    "seq"     - sequence/context parallelism (ring attention / Ulysses)
    "expert"  - expert parallelism (MoE dispatch)

A `MeshSpec` names the axis sizes; `build_mesh` lays devices out so that the
innermost axes land on physically adjacent chips (ICI neighbours), which is
what makes tensor/seq collectives ride ICI bandwidth rather than DCN.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

# Canonical axis order: outermost (slowest-varying, cheapest link) first.
# data/fsdp/stage ride DCN across hosts if they must (pipeline transfers
# are point-to-point and latency-tolerant); tensor/seq/expert want ICI.
AXIS_ORDER = ("data", "fsdp", "stage", "expert", "seq", "tensor")


@dataclasses.dataclass(frozen=True)
class TpuTopology:
    """Physical description of a TPU slice.

    Mirrors what the reference reads from GCE metadata
    (reference: python/ray/_private/accelerators/tpu.py:198
    accelerator_type + topology detection) but models it natively instead
    of flattening to a scalar resource count.
    """

    generation: str = "cpu"  # e.g. "v5e", "v5p", "v4", or "cpu" for tests
    chips_per_host: int = 1
    num_hosts: int = 1
    mesh_shape: Tuple[int, ...] = ()  # physical ICI torus, e.g. (8, 8) for v5e-64

    @property
    def num_chips(self) -> int:
        return self.chips_per_host * self.num_hosts

    @staticmethod
    def detect() -> "TpuTopology":
        devs = jax.devices()
        kind = devs[0].platform
        if kind != "tpu":
            return TpuTopology(generation=kind, chips_per_host=len(devs), num_hosts=1)
        n_hosts = max(d.process_index for d in devs) + 1
        per_host = len([d for d in devs if d.process_index == 0])
        gen = getattr(devs[0], "device_kind", "tpu").lower().replace(" ", "")
        coords = [getattr(d, "coords", None) for d in devs]
        shape: Tuple[int, ...] = ()
        if all(c is not None for c in coords):
            dims = len(coords[0])
            shape = tuple(max(c[i] for c in coords) + 1 for i in range(dims))
        return TpuTopology(gen, per_host, n_hosts, shape)


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """Logical parallelism layout: axis name -> size.

    Sizes of -1 mean "absorb remaining devices" (at most one axis may be -1).
    Axes of size 1 are kept in the mesh so PartitionSpecs mentioning them
    remain valid at any scale — a spec written for v5e-64 runs unchanged on
    one chip.
    """

    data: int = -1
    fsdp: int = 1
    stage: int = 1
    tensor: int = 1
    seq: int = 1
    expert: int = 1

    def resolve(self, n_devices: int) -> Dict[str, int]:
        sizes = {
            "data": self.data,
            "fsdp": self.fsdp,
            "stage": self.stage,
            "expert": self.expert,
            "seq": self.seq,
            "tensor": self.tensor,
        }
        wild = [k for k, v in sizes.items() if v == -1]
        if len(wild) > 1:
            raise ValueError(f"at most one mesh axis may be -1, got {wild}")
        fixed = math.prod(v for v in sizes.values() if v != -1)
        if wild:
            if n_devices % fixed:
                raise ValueError(
                    f"{n_devices} devices not divisible by fixed axes product {fixed}"
                )
            sizes[wild[0]] = n_devices // fixed
        if math.prod(sizes.values()) != n_devices:
            raise ValueError(
                f"mesh {sizes} needs {math.prod(sizes.values())} devices, have {n_devices}"
            )
        return {k: sizes[k] for k in AXIS_ORDER}


def build_mesh(
    spec: Optional[MeshSpec] = None,
    *,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Builds a `jax.sharding.Mesh` with the framework's canonical axes.

    Device order: jax returns devices in row-major physical order; reshaping
    with the canonical axis order (data outermost, tensor innermost) puts
    tensor-parallel neighbours on adjacent chips — the XLA partitioner then
    lowers tensor-axis collectives to single-hop ICI transfers. This replaces
    the reference's rank-ordering of NCCL communicators
    (reference: python/ray/util/collective/collective_group/nccl_collective_group.py:128).
    """
    devices = list(devices) if devices is not None else jax.devices()
    axis_sizes = (spec or MeshSpec()).resolve(len(devices))
    arr = np.array(devices).reshape(tuple(axis_sizes[a] for a in AXIS_ORDER))
    return Mesh(arr, AXIS_ORDER)


def single_device_mesh() -> Mesh:
    return build_mesh(MeshSpec(data=1))


def mesh_shape(mesh: Mesh) -> Dict[str, int]:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def named_sharding(mesh: Mesh, *spec) -> NamedSharding:
    return NamedSharding(mesh, PartitionSpec(*spec))


def data_parallel_rank(mesh: Mesh) -> int:
    """The (data x fsdp) coordinate of this host's first in-mesh device; used
    by data sharding to pick which shard of the global batch this host loads.

    Raises if none of this host's devices are in the mesh — silently
    defaulting would make every host load shard 0 (identical batches,
    silent training corruption)."""
    this_process = jax.process_index()
    local = [d for d in mesh.devices.flat if d.process_index == this_process]
    if not local:
        raise ValueError(
            f"no devices of process {this_process} are in the mesh; "
            "cannot determine this host's data-parallel rank"
        )
    idx = np.argwhere(mesh.devices == local[0])
    coords = dict(zip(mesh.axis_names, idx[0]))
    return int(coords["data"] * mesh.devices.shape[mesh.axis_names.index("fsdp")] + coords["fsdp"])
