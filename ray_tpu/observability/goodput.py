"""Goodput accounting + MFU helpers: wall time a trainer can defend.

Google's Goodput methodology: goodput = productive step time / total
wall time, with everything the fleet did that did NOT advance the model
(checkpoint writes, drain waits after a preemption notice, recomputing
steps lost since the last checkpoint, setup) accounted explicitly.
Podracer (arXiv:2104.06272) makes the same argument for accelerator
idle time. PR 7's preemption machinery generates exactly these events;
this module is the ledger that classifies them.

`GoodputAccountant` is a segment clock: the supervisor (JaxTrainer.fit)
switches it between categories as the run moves through its lifecycle —
setup -> productive -> (checkpoint persist) -> productive -> drain_wait
on a preemption notice -> restart_rework on the restored attempt until
the first fresh step lands -> productive again. `fraction()` is the
goodput number `ray-tpu status`, the result metrics, and the
goodput_floor watchdog rule consume.

MFU: `peak_flops()` resolves this host's peak FLOP/s (env
RAY_TPU_PEAK_FLOPS override, else the public per-chip spec table by
device kind x local device count, None when no backend is live), so
`mfu(tokens_per_s, flops_per_token)` turns a reported throughput into
model-FLOPs utilization using `models/transformer.py:flops_per_token`.
"""

from __future__ import annotations

import os
import time
from typing import Callable, Dict, Optional

SETUP = "setup"
PRODUCTIVE = "productive"
CHECKPOINT = "checkpoint"
DRAIN_WAIT = "drain_wait"
RESTART_REWORK = "restart_rework"
# Elastic runs: steps ARE advancing but the gang is below its target
# world size (capacity never came back inside the wait budget and the
# trainer re-formed smaller). Weighted into goodput by world/target —
# half the chips productive is half the goodput, not zero and not full.
DEGRADED = "degraded"

CATEGORIES = (SETUP, PRODUCTIVE, CHECKPOINT, DRAIN_WAIT, RESTART_REWORK, DEGRADED)

# Peak bf16 FLOP/s per chip by generation, keyed by a substring of
# jax's device_kind (public spec sheets; v5e: Google Cloud "TPU v5e", 197
# TFLOP/s).
PEAK_FLOPS_PER_CHIP = {
    "v6e": 918e12,
    "v5p": 459e12,
    "v5e": 197e12,
    "v5litepod": 197e12,
    "v5lite": 197e12,
    "v4": 275e12,
}


class GoodputAccountant:
    """Wall-clock ledger over the run's lifecycle categories. Not
    thread-safe by design: exactly one supervisor drives it."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._category: Optional[str] = None
        self._since: float = 0.0
        self.seconds: Dict[str, float] = {c: 0.0 for c in CATEGORIES}
        # Category -> goodput weight. PRODUCTIVE counts 1.0; DEGRADED is
        # set by the supervisor to world/target when it downsizes.
        self._weights: Dict[str, float] = {PRODUCTIVE: 1.0}

    def set_weight(self, category: str, weight: float) -> None:
        """Credit `category` seconds at `weight` (0..1) in fraction()."""
        if category not in self.seconds:
            raise ValueError(f"unknown goodput category {category!r}")
        self._weights[category] = max(0.0, min(1.0, float(weight)))

    @property
    def category(self) -> Optional[str]:
        return self._category

    def begin(self, category: str) -> None:
        """Close the running segment and start `category`."""
        if category not in self.seconds:
            raise ValueError(f"unknown goodput category {category!r}")
        now = self._clock()
        if self._category is not None:
            self.seconds[self._category] += now - self._since
        self._category = category
        self._since = now

    def finish(self) -> None:
        """Close the running segment (end of run)."""
        if self._category is not None:
            self.seconds[self._category] += self._clock() - self._since
            self._category = None

    def total(self) -> float:
        extra = self._clock() - self._since if self._category else 0.0
        return sum(self.seconds.values()) + extra

    def fraction(self) -> float:
        """Weighted productive time / total (PRODUCTIVE at 1.0, DEGRADED
        at its world/target weight); 1.0 for a run too short to have
        history (an empty ledger must not trip the goodput_floor
        watchdog)."""
        total = self.total()
        if total <= 0:
            return 1.0
        seconds = dict(self.seconds)
        if self._category is not None:
            seconds[self._category] += self._clock() - self._since
        productive = sum(
            seconds[c] * w for c, w in self._weights.items() if w > 0
        )
        return productive / total

    def snapshot(self) -> Dict[str, object]:
        """Breakdown with the in-flight segment included."""
        seconds = dict(self.seconds)
        if self._category is not None:
            seconds[self._category] += self._clock() - self._since
        return {
            "goodput": self.fraction(),
            "seconds": {k: round(v, 4) for k, v in seconds.items()},
        }


def peak_flops() -> Optional[float]:
    """This process's peak FLOP/s: RAY_TPU_PEAK_FLOPS wins; otherwise
    per-chip spec x local device count — but ONLY when a jax backend is
    already initialized (probing would trigger accelerator discovery
    from processes that never use jax). None = unknown, skip MFU."""
    env = os.environ.get("RAY_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    try:
        from jax._src import xla_bridge

        if not getattr(xla_bridge, "_backends", None):
            return None
        import jax

        total = 0.0
        for d in jax.local_devices():
            kind = getattr(d, "device_kind", "").lower().replace(" ", "")
            for key, val in PEAK_FLOPS_PER_CHIP.items():
                if key in kind:
                    total += val
                    break
        return total or None
    except Exception:
        return None


def mfu(
    tokens_per_s: float,
    flops_per_token: float,
    peak_flops_per_s: Optional[float] = None,
) -> Optional[float]:
    """Model-FLOPs utilization; None when the peak is unknown (an MFU
    against a made-up denominator is worse than no MFU)."""
    peak = peak_flops_per_s if peak_flops_per_s is not None else peak_flops()
    if not peak or peak <= 0:
        return None
    return tokens_per_s * flops_per_token / peak
