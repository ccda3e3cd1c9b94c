"""JaxBackendConfig: the multi-host JAX runtime rendezvous.

Re-design of the reference's collective-backend bootstrap (reference:
python/ray/train/_internal/backend_executor.py:135 start -> Backend.on_start;
train/torch/config.py:66 _setup_torch_process_group — NCCL/Gloo rendezvous
over a TCP store). TPU-native shape: every worker (= one host of a pod
slice) calls `jax.distributed.initialize` against a coordinator owned by the
gang, after which `jax.devices()` is the GLOBAL device list and one jitted
SPMD program spans all hosts — collectives compile into the program over
ICI/DCN; there is no out-of-band process group.

CPU emulation (how multi-host is tested without a pod, mirroring the
reference's single-machine multi-node strategy, python/ray/tests/
conftest.py:500): each worker process forces N virtual CPU devices
(`--xla_force_host_platform_device_count`) and the cpu platform, giving a
world of world_size*N devices with real cross-process collectives (Gloo).
"""

from __future__ import annotations

import dataclasses
import socket
from typing import Any, Dict, Optional


@dataclasses.dataclass
class JaxBackendConfig:
    """(reference analogue: train/torch/config.py TorchConfig)

    platform: None = what the worker's environment selects (JAX_PLATFORMS,
        else jax's own discovery — TPU on real pods); "cpu" = emulation,
        combined with devices_per_worker.
    devices_per_worker: virtual CPU device count per worker process
        (emulation only; None on real TPU hosts where local chips are real).
    coordinator_host: rank-0 rendezvous host. None = loopback (emulated
        cluster / single machine); real pods pass the rank-0 host address.
    init_timeout_s: rendezvous timeout.
    """

    platform: Optional[str] = None
    devices_per_worker: Optional[int] = None
    coordinator_host: Optional[str] = None
    coordinator_port: Optional[int] = None
    init_timeout_s: float = 60.0


def free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def setup_jax_distributed(
    rank: int,
    world_size: int,
    coordinator: str,
    platform: Optional[str] = None,
    devices_per_worker: Optional[int] = None,
    init_timeout_s: float = 60.0,
) -> Dict[str, Any]:
    """Worker-side rendezvous. MUST run before the process initializes any
    jax backend (worker processes import jax lazily, so this holds when it
    is the first jax-touching call of the actor)."""
    import os
    import re

    if devices_per_worker:
        flags = os.environ.get("XLA_FLAGS", "")
        flags = re.sub(r"--xla_force_host_platform_device_count=\d+", "", flags)
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices_per_worker}".strip()
        )
    resolved_platform = platform or os.environ.get("JAX_PLATFORMS")
    if world_size > 1 and resolved_platform == "cpu":
        # Deflake (tier-1 "gloo reset"): the CPU thunk runtime executes
        # independent collective thunks CONCURRENTLY, and two in-flight
        # all-reduces of different sizes on one gloo context collide on a
        # pair slot — `gloo::EnforceNotMet pair.cc:446 op.preamble.length
        # <= op.nbytes. 16 vs 4` aborts the process (~1-in-3 repro on the
        # 2-learner gang). The legacy executor runs thunks sequentially,
        # which serializes same-context collectives. Must be set before
        # this process's first backend init (this call is the actor's
        # first jax-touching code).
        flags = os.environ.get("XLA_FLAGS", "")
        if "--xla_cpu_use_thunk_runtime" not in flags:
            os.environ["XLA_FLAGS"] = (
                f"{flags} --xla_cpu_use_thunk_runtime=false".strip()
            )

    import jax

    if platform:
        # jax snapshots JAX_PLATFORMS at import; the config update is the
        # reliable override for processes where jax is already imported.
        jax.config.update("jax_platforms", platform)
    if resolved_platform == "cpu" and world_size > 1:
        # Cross-process collectives on the host platform go through gloo
        # (the emulation analogue of ICI; the reference's CPU fallback is
        # GLOOGroup, gloo_collective_group.py:184).
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
        # Deflake, part 2 (same root cause as the thunk-runtime flag
        # above): async dispatch lets a later program's gloo op go in
        # flight while an earlier one is still posting on the same pair,
        # and the two processes need not interleave identically.
        jax.config.update("jax_cpu_enable_async_dispatch", False)

    if world_size > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world_size,
            process_id=rank,
            initialization_timeout=int(init_timeout_s),
        )
    return {
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "local_devices": len(jax.local_devices()),
        "global_devices": len(jax.devices()),
    }


def coordinator_address(cfg: JaxBackendConfig) -> str:
    host = cfg.coordinator_host or "127.0.0.1"
    port = cfg.coordinator_port or free_port()
    return f"{host}:{port}"
