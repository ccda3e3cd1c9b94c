"""Train/AIR config dataclasses.

Mirrors the reference's ScalingConfig/RunConfig/FailureConfig/
CheckpointConfig surface (reference: python/ray/air/config.py) with
TPU-native additions: ScalingConfig speaks a `MeshSpec` instead of
`use_gpu` (chips are requested like any resource, through
`resources_per_worker={"TPU": n}`), and placement is slice-gang-aware.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Any, Dict, Optional

from ..parallel.mesh import MeshSpec


@dataclasses.dataclass
class ScalingConfig:
    """How many workers (= hosts for multi-host TPU) and what mesh each
    training job uses (reference: python/ray/air/config.py ScalingConfig,
    plus the TPU pod-slice semantics of
    python/ray/_private/accelerators/tpu.py:334-397)."""

    num_workers: int = 1
    mesh: MeshSpec = dataclasses.field(default_factory=MeshSpec)
    resources_per_worker: Optional[Dict[str, float]] = None
    placement_strategy: str = "PACK"
    # Multi-host runtime rendezvous; None with num_workers>1 uses defaults
    # (loopback coordinator — the emulated-cluster / single-machine case).
    backend: Optional[Any] = None  # JaxBackendConfig
    # Elastic world size: when capacity does not return within the wait
    # budget after a preemption, an elastic trainer re-forms the gang at
    # the largest feasible world >= min_workers and resumes same-step
    # from the (world-size-independent) checkpoint, then grows back to
    # num_workers at a checkpoint boundary once capacity returns.
    elastic: bool = False
    min_workers: Optional[int] = None  # elastic floor; None -> 1
    # Seconds fit() waits for replacement capacity after a preemption
    # before downsizing (elastic) or failing fast (CapacityTimeoutError);
    # None -> trainer.CAPACITY_WAIT_S.
    capacity_wait_s: Optional[float] = None

    @property
    def total_workers(self) -> int:
        return max(1, self.num_workers)

    @property
    def elastic_floor(self) -> int:
        return max(1, self.min_workers if self.min_workers is not None else 1)


@dataclasses.dataclass
class FailureConfig:
    """(reference: python/ray/air/config.py FailureConfig)"""

    max_failures: int = 0


@dataclasses.dataclass
class CheckpointConfig:
    """Keep-K + score-attribute retention
    (reference: python/ray/air/config.py CheckpointConfig,
    python/ray/train/_internal/checkpoint_manager.py)."""

    num_to_keep: Optional[int] = None
    checkpoint_score_attribute: Optional[str] = None
    checkpoint_score_order: str = "max"
    checkpoint_frequency: int = 0


@dataclasses.dataclass
class RunConfig:
    """(reference: python/ray/air/config.py RunConfig)"""

    name: Optional[str] = None
    storage_path: Optional[str] = None
    failure_config: FailureConfig = dataclasses.field(default_factory=FailureConfig)
    checkpoint_config: CheckpointConfig = dataclasses.field(default_factory=CheckpointConfig)
    verbose: int = 1

    def resolved_storage_path(self) -> str:
        return self.storage_path or os.path.expanduser("~/ray_tpu_results")
