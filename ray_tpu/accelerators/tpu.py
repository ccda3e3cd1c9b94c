"""TpuAcceleratorManager: chip counting, pod-slice topology, visibility.

Re-design of the reference's TPU accelerator module (reference:
python/ray/_private/accelerators/tpu.py: /dev/accel* probing :98, metadata
reads :150-210, pod-type parsing :240-300, TPU_VISIBLE_CHIPS visibility
:360-397). Detection order per question:

  chips      /dev/accel* -> /dev/vfio/<n> -> TPU_CHIPS_PER_HOST_BOUNDS
             -> derived from type
  pod type   TPU_ACCELERATOR_TYPE (GKE) -> GCE metadata accelerator-type
  worker idx TPU_WORKER_ID (GKE)        -> GCE metadata agent-worker-number
  slice name TPU_NAME                   -> GCE metadata instance-id
  topology   TPU_TOPOLOGY (GKE)         -> GCE metadata topology -> derived

Everything is injectable (device dir, env mapping, metadata transport) so
tests assert the full resolution chain with zero hardware or network.
"""

from __future__ import annotations

import os
import re
from typing import Dict, List, Mapping, Optional, Tuple

from .accelerator import AcceleratorManager
from .gce import (
    ACCEL_TYPE_ATTR,
    INSTANCE_ID_ATTR,
    TOPOLOGY_ATTR,
    WORKER_NUMBER_ATTR,
    HttpTransport,
    gce_metadata,
)

TPU_VISIBLE_CHIPS_ENV = "TPU_VISIBLE_CHIPS"
# Generations whose pod-type suffix counts TensorCORES (8 per host, 2 per
# chip): v2/v3 and also v4/v5p — a v4-16 is 8 chips on 2 hosts. The
# chip-suffixed generations are v5e/v6e (reference: tpu.py
# cores-vs-chips split).
_CORE_COUNT_GENERATIONS = ("v2", "v3", "v4", "v5p")
# Max chips that fit one host before the slice spans hosts. Keys are the
# generation with any "pod" suffix already stripped by the parse regex
# ("v5litepod-16" captures gen "v5lite").
_SINGLE_HOST_CHIPS = {"v5lite": 8, "v5e": 8, "v6e": 8}
_DEFAULT_CHIPS_PER_HOST = 4

_POD_TYPE_RE = re.compile(r"^(?P<gen>[a-z0-9]+?)(?:pod)?-(?P<count>\d+)$")


def parse_pod_type(pod_type: str) -> Optional[Tuple[str, int, int, int]]:
    """(version, total_chips, chips_per_host, hosts_per_slice) for a pod
    type like "v5litepod-16" / "v5e-64" / "v3-32"; None if unparseable.

    A v5e-64, for example, is 64 chips over 16 hosts of 4 chips — exactly
    the shape TpuSliceSpec carries for gang scheduling."""
    m = _POD_TYPE_RE.match(pod_type.strip().lower())
    if m is None:
        return None
    gen, count = m.group("gen"), int(m.group("count"))
    if count <= 0:
        return None
    version = {"v5lite": "v5e"}.get(gen, gen)
    if gen in _CORE_COUNT_GENERATIONS:
        # Suffix counts cores: 8 cores (4 chips) per host; a sub-host
        # suffix (v4-8's single host) clamps chips to cores//2.
        hosts = max(1, count // 8)
        chips_per_host = min(4, max(1, count // 2))
        total = chips_per_host * hosts
        return version, total, chips_per_host, hosts
    single_host = _SINGLE_HOST_CHIPS.get(gen, _DEFAULT_CHIPS_PER_HOST)
    if count <= single_host:
        return version, count, count, 1
    chips_per_host = _DEFAULT_CHIPS_PER_HOST
    hosts = max(1, count // chips_per_host)
    return version, chips_per_host * hosts, chips_per_host, hosts


def _derive_topology(total_chips: int) -> str:
    """Squarest 2D chip grid for a slice ("8x8" for 64) — used only when
    neither env nor metadata names the real topology."""
    if total_chips <= 0:
        return ""
    best = 1
    i = 1
    while i * i <= total_chips:
        if total_chips % i == 0:
            best = i
        i += 1
    return f"{best}x{total_chips // best}"


class TpuAcceleratorManager(AcceleratorManager):
    def __init__(
        self,
        dev_dir: str = "/dev",
        env: Optional[Mapping[str, str]] = None,
        transport: Optional[HttpTransport] = None,
        metadata_timeout_s: float = 0.5,
    ):
        self._dev_dir = dev_dir
        self._env = env if env is not None else os.environ
        self._transport = transport or HttpTransport()
        self._metadata_timeout_s = metadata_timeout_s
        self._metadata_cache: Dict[str, Optional[str]] = {}

    # ------------------------------------------------------------ identity
    def get_resource_name(self) -> str:
        return "TPU"

    def get_visible_accelerator_ids_env_var(self) -> Optional[str]:
        return TPU_VISIBLE_CHIPS_ENV

    # ----------------------------------------------------------- detection
    def _metadata(self, path: str) -> Optional[str]:
        if path not in self._metadata_cache:
            self._metadata_cache[path] = gce_metadata(
                path, self._transport, timeout=self._metadata_timeout_s
            )
        return self._metadata_cache[path]

    def _count_device_nodes(self) -> int:
        """Chips this host exposes as device nodes: /dev/accel<n> (the
        accel driver) or, on VMs that pass chips through VFIO (v5e, v6e),
        the numbered IOMMU groups /dev/vfio/<n> (reference: tpu.py
        get_current_node_num_accelerators globs both)."""

        def names(path: str) -> List[str]:
            try:
                return os.listdir(path)
            except OSError:
                return []

        n_accel = sum(d.startswith("accel") for d in names(self._dev_dir))
        return n_accel or sum(
            d.isdigit() for d in names(os.path.join(self._dev_dir, "vfio"))
        )

    def get_current_node_num_accelerators(self) -> int:
        # Device nodes first: they are what a process can actually open.
        # The bounds variable describes the slice image, not this host — a
        # one-chip v5e machine was seen with TPU_CHIPS_PER_HOST_BOUNDS=2,2,1
        # and a single /dev/vfio/0, and jax found one device there.
        n_dev = self._count_device_nodes()
        if n_dev:
            return n_dev
        bounds = self._env.get("TPU_CHIPS_PER_HOST_BOUNDS")
        if bounds:
            try:
                n = 1
                for part in bounds.split(","):
                    n *= int(part)
                return n
            except ValueError:
                pass
        # Last resort: a declared pod type implies this host's chip count
        # (GKE sets the type env without exposing device nodes to the probe).
        pod_type = self.get_current_node_accelerator_type()
        if pod_type:
            parsed = parse_pod_type(pod_type)
            if parsed:
                return parsed[2]
        return 0

    def get_current_node_accelerator_type(self) -> Optional[str]:
        return self._env.get("TPU_ACCELERATOR_TYPE") or self._metadata(
            ACCEL_TYPE_ATTR
        )

    def get_current_node_tpu_worker_index(self) -> int:
        raw = self._env.get("TPU_WORKER_ID") or self._metadata(WORKER_NUMBER_ATTR)
        try:
            return int(raw) if raw is not None else 0
        except ValueError:
            return 0

    def get_current_node_tpu_name(self) -> str:
        return (
            self._env.get("TPU_NAME") or self._metadata(INSTANCE_ID_ATTR) or ""
        )

    def get_current_node_tpu_topology(self) -> str:
        explicit = self._env.get("TPU_TOPOLOGY") or self._metadata(TOPOLOGY_ATTR)
        if explicit:
            return explicit
        pod_type = self.get_current_node_accelerator_type()
        parsed = parse_pod_type(pod_type) if pod_type else None
        return _derive_topology(parsed[1]) if parsed else ""

    def detect_slice_spec(self):
        """The TpuSliceSpec of the slice this host belongs to, or None when
        the host is not (detectably) part of one. This is what raylet
        registration folds into node labels so SLICE_GANG placement sees
        real slices exactly like the test fixtures' fake ones."""
        pod_type = self.get_current_node_accelerator_type()
        if not pod_type:
            return None
        parsed = parse_pod_type(pod_type)
        if parsed is None:
            return None
        from ..core.resources import TpuSliceSpec

        version, total, chips_per_host, hosts = parsed
        local = self.get_current_node_num_accelerators() or chips_per_host
        return TpuSliceSpec(
            version=version,
            slice_name=self.get_current_node_tpu_name() or pod_type,
            topology=self.get_current_node_tpu_topology(),
            chips_per_host=min(local, chips_per_host) or chips_per_host,
            hosts_per_slice=hosts,
            worker_index=self.get_current_node_tpu_worker_index(),
        )

    # ---------------------------------------------------------- visibility
    def get_current_process_visible_accelerator_ids(self) -> Optional[List[str]]:
        raw = self._env.get(TPU_VISIBLE_CHIPS_ENV)
        if raw is None:
            return None
        return [p for p in raw.split(",") if p != ""]

    def visible_chip_ids(self, total_chips: int) -> List[int]:
        """The physical chip indices this raylet may lease to bundles: the
        process's own visibility restriction when set (a raylet running
        inside a chip lease must sublease only those), else 0..n-1."""
        visible = self.get_current_process_visible_accelerator_ids()
        if visible is not None:
            ids = []
            for v in visible:
                try:
                    ids.append(int(v))
                except ValueError:
                    pass
            return ids[: total_chips or len(ids)]
        return list(range(int(total_chips)))

    def worker_visibility_env(self, ids: List[int], **extra) -> Dict[str, str]:
        """The spawn-time env making a worker see exactly `ids` (reference:
        tpu.py set_accelerator_visible + the TPU runtime's host-bounds
        vars). `extra` carries slice identity: slice_name, worker_index."""
        env = {
            TPU_VISIBLE_CHIPS_ENV: ",".join(str(c) for c in ids),
            # One host, one row of chips: the leased subset is presented as
            # its own single-host topology so jax initializes locally.
            "TPU_CHIPS_PER_HOST_BOUNDS": f"1,1,{len(ids)}",
        }
        slice_name = extra.get("slice_name")
        if slice_name:
            env["TPU_SLICE_NAME"] = str(slice_name)
        env["TPU_WORKER_ID"] = str(extra.get("worker_index", 0))
        return env
