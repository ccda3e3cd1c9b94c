"""Seeded, deterministic fault injection for the runtime's hot paths.

The runtime has every primitive a preemption-tolerant system needs —
task retries, `max_restarts` actor restore, gang checkpointing, the
autoscaler's replace loop — but none of it is *provable* without a way
to make the failures happen on demand. This module is that way: a small
rule engine whose injection points are compiled into the runtime
(worker task execution, the raylet heartbeat, channel reads/writes,
collective rendezvous/ops, the node provider's poll loop) and which is
COMPLETELY inert unless armed.

Design constraints, in order:

1. **Disabled cost ~zero.** Every injection site calls
   ``maybe_inject(point, detail)``; with no controller armed that is one
   global load and a ``None`` check — the same budget class as the
   always-on flight recorder.
2. **Deterministic.** Each rule owns a ``random.Random`` seeded from
   (global seed, rule index), and fire decisions depend only on the
   rule's own hit counter — two runs with the same seed and the same
   sequence of hits inject identically. CI chaos tests replay exactly.
3. **Post-mortem first.** Every injection is stamped into the flight
   recorder (``chaos.inject``) *before* the fault is applied, so a trace
   export shows cause strictly before symptom, and counted in
   ``raytpu_chaos_injections_total``.

Arming:

- env: ``RAY_TPU_CHAOS='[{"point": "task.exec", "action": "kill",
  "match": "flaky", "times": 1}]'`` (a single rule object also works).
  Workers and daemons inherit the driver's environment, so exporting the
  variable before ``ray_tpu.init()`` arms the whole cluster.
- ``RAY_TPU_CHAOS_SEED=<int>`` seeds the per-rule RNGs (default 0).
- programmatic: ``chaos.configure([...], seed=7)`` / ``chaos.disable()``
  arm only the calling process (tests; provider-side injection).

Rule fields:

- ``point``: the injection site name (see POINTS).
- ``action``: what the site should do — ``kill`` (SIGKILL the process),
  ``raise`` (raise a fault), ``delay`` (sleep ``delay_s``), ``drop``
  (swallow the message), ``preempt`` (synthesize a preemption notice;
  provider sites only).
- ``match``: substring the site's detail string must contain ("" = all).
- ``after``: skip the first N *matching* hits before becoming eligible.
- ``times``: fire at most N times (-1 = unlimited).
- ``prob``: per-hit fire probability drawn from the rule's seeded RNG.
- ``delay_s``: sleep length for ``delay``; drain grace for ``preempt``.
"""

from __future__ import annotations

import dataclasses
import json
import os
import random
import threading
from typing import Any, Dict, List, Optional, Sequence, Union

from ..observability.flight_recorder import record as _flight_record

ENV_VAR = "RAY_TPU_CHAOS"
SEED_ENV = "RAY_TPU_CHAOS_SEED"

# The injection sites compiled into the runtime, with the actions each
# site actually implements. Kept as data so tests (and the README) can
# enumerate the fault surface; a typo'd point OR a point/action pair no
# site implements fails loudly at parse time — otherwise the rule would
# "fire" (counted, flight-recorded) while applying no fault, and a chaos
# campaign would validate nothing while its telemetry says it did.
POINT_ACTIONS = {
    "task.exec": ("kill", "raise", "delay"),  # worker_proc: before each task
    "raylet.heartbeat": ("kill",),            # raylet tick (kill = node crash)
    "chan.write": ("delay", "drop", "raise"),  # core/channel.py writer
    "chan.read": ("delay", "raise"),          # core/channel.py reader
    "coll.rendezvous": ("raise",),            # collective.py group setup
    "coll.op": ("raise", "delay"),            # collective.py each op
    "provider.poll": ("preempt",),            # node provider poll round
    # Control-plane network faults (core/rpc.py). `drop` on net.call
    # black-holes the message (one-way sends vanish; two-way calls fail
    # like a vanished peer); `drop` on net.connect makes the connect
    # loop burn its own retry deadline, exactly like packets on the
    # floor. Group-based partitions (chaos.partition) ride the same
    # sites via chaos/net.py.
    "net.call": ("drop", "delay", "raise"),   # RpcClient.call/notify, by addr|method
    "net.connect": ("drop", "raise"),         # RpcClient._new_sock, by addr
    # Worker-pool zygote spawn path (core/worker_pool.py). `kill`
    # SIGKILLs the zygote DAEMON at a spawn request (not the raylet) —
    # the daemon-death-strands-the-pool failure mode: the pool manager
    # must detect it, respawn the zygote, and rebuild the parked pool
    # while the in-flight spawn falls back to a cold Popen.
    "zygote.spawn": ("kill", "raise", "delay"),
    # LLM engine decode loop (serve/llm/engine.py), once per decode step,
    # detail = deployment name. `kill` SIGKILLs the replica mid-decode —
    # the drill for "replica death must not wedge the batch or leak KV
    # pages"; `raise` fails the step (engine fail-fasts the batch);
    # `delay` stretches TPOT to trip latency watchdogs.
    "serve.decode": ("kill", "raise", "delay"),
}
POINTS = tuple(POINT_ACTIONS)

_ACTIONS = ("kill", "raise", "delay", "drop", "preempt")
# Grace window defaults differ by meaning: a `delay` sleeps briefly; a
# `preempt` grace must outlive the supervisors' reaction latency (the
# node-event long-poll + control-loop ticks) or the graceful-drain path
# under test silently degenerates into blunt node death.
_DEFAULT_DELAY_S = 0.05
_DEFAULT_PREEMPT_GRACE_S = 5.0


@dataclasses.dataclass
class ChaosRule:
    point: str
    action: str = "raise"
    # One substring, or a list of substrings that must ALL appear in the
    # site's detail string (e.g. ["train_step", "@0"] = that function's
    # first attempt only — rule counters are per-process, but an
    # attempt-qualified match is deterministic across any worker churn).
    match: Union[str, tuple] = ""
    after: int = 0
    times: int = 1
    prob: float = 1.0
    # None = per-action default (0.05 s for `delay`, 5 s grace for
    # `preempt`); resolved in validate().
    delay_s: Optional[float] = None
    # Mutable per-process state (not part of the spec).
    hits: int = 0
    injected: int = 0
    rng: Optional[random.Random] = None

    def validate(self) -> "ChaosRule":
        if self.point not in POINTS:
            raise ValueError(
                f"unknown chaos point {self.point!r}; valid: {sorted(POINTS)}"
            )
        if self.action not in _ACTIONS:
            raise ValueError(
                f"unknown chaos action {self.action!r}; valid: {sorted(_ACTIONS)}"
            )
        if self.action not in POINT_ACTIONS[self.point]:
            raise ValueError(
                f"chaos point {self.point!r} does not implement action "
                f"{self.action!r}; it supports: "
                f"{sorted(POINT_ACTIONS[self.point])}"
            )
        if self.delay_s is None:
            self.delay_s = (
                _DEFAULT_PREEMPT_GRACE_S
                if self.action == "preempt"
                else _DEFAULT_DELAY_S
            )
        if isinstance(self.match, list):
            self.match = tuple(self.match)
        return self

    def matches(self, detail: str) -> bool:
        if not self.match:
            return True
        needles = (
            self.match if isinstance(self.match, tuple) else (self.match,)
        )
        return all(n in detail for n in needles)


def _parse_rules(spec: Union[str, dict, Sequence]) -> List[ChaosRule]:
    if isinstance(spec, str):
        spec = json.loads(spec)
    if isinstance(spec, dict):
        spec = [spec]
    rules = []
    for r in spec:
        if isinstance(r, ChaosRule):
            # Copy: the controller owns its rules' mutable state (hits/
            # injected/rng); appending the caller's instance by reference
            # would make two controllers built from one rule list clobber
            # each other's counters and seeds.
            rules.append(dataclasses.replace(r).validate())
            continue
        known = {f.name for f in dataclasses.fields(ChaosRule)}
        extra = set(r) - known
        if extra:
            raise ValueError(f"unknown chaos rule field(s) {sorted(extra)}")
        rules.append(ChaosRule(**r).validate())
    return rules


class ChaosController:
    """One process's armed rule set. Decisions are serialized under a
    lock — injection points are never so hot that contention matters
    (the disabled path doesn't reach here at all)."""

    def __init__(self, rules: Union[str, dict, Sequence], seed: int = 0):
        self.seed = int(seed)
        self.rules: List[ChaosRule] = _parse_rules(rules)
        self._by_point: Dict[str, List[ChaosRule]] = {}
        import zlib

        for i, rule in enumerate(self.rules):
            # Independent deterministic stream per rule: adding a rule
            # never perturbs another rule's decisions. crc32 (not hash():
            # str hashing is salted per process) keeps the stream
            # identical across every worker/daemon process.
            rule.rng = random.Random(
                (self.seed << 32) ^ (i << 16) ^ zlib.crc32(rule.point.encode())
            )
            rule.hits = 0
            rule.injected = 0
            self._by_point.setdefault(rule.point, []).append(rule)
        self._lock = threading.Lock()

    @classmethod
    def from_env(cls) -> Optional["ChaosController"]:
        spec = os.environ.get(ENV_VAR)
        if not spec:
            return None
        seed = int(os.environ.get(SEED_ENV, "0") or 0)
        return cls(_parse_rules(spec), seed=seed)

    def maybe_inject(self, point: str, detail: str = "") -> Optional[ChaosRule]:
        rules = self._by_point.get(point)
        if not rules:
            return None
        with self._lock:
            for rule in rules:
                if not rule.matches(detail):
                    continue
                rule.hits += 1
                if rule.hits <= rule.after:
                    continue
                if rule.times >= 0 and rule.injected >= rule.times:
                    continue
                if rule.prob < 1.0 and rule.rng.random() >= rule.prob:
                    continue
                rule.injected += 1
                self._stamp(point, rule, detail)
                return rule
        return None

    @staticmethod
    def _stamp(point: str, rule: ChaosRule, detail: str) -> None:
        # Cause before symptom: the flight record lands before the fault
        # is applied, so a post-mortem trace orders them correctly.
        _flight_record("chaos.inject", (point, rule.action, detail))
        from ..observability.postmortem import publish_trigger

        publish_trigger(
            "chaos.inject",
            {"point": point, "action": rule.action, "detail": detail},
            source="chaos",
        )
        try:
            from ..utils import internal_metrics as imet

            imet.CHAOS_INJECTIONS.inc(point=point, action=rule.action)
        except Exception:  # lint: swallow-ok(metrics must never break the injection itself)
            pass
        try:
            # The structured log stream gets the injection too: `ray-tpu
            # logs --component chaos` shows a campaign's faults inline
            # with the symptoms they caused.
            from ..observability.logs import get_logger

            get_logger("chaos").warning(
                "injecting %s at %s (%s)", rule.action, point, detail
            )
        except Exception:  # lint: swallow-ok(logging must never break the injection itself)
            pass

    def stats(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [
                {
                    "point": r.point,
                    "action": r.action,
                    "match": r.match,
                    "hits": r.hits,
                    "injected": r.injected,
                }
                for r in self.rules
            ]


# ------------------------------------------------------------- module API
# The controller is parsed from the environment once, at import — import
# cost is one getenv when unarmed, and worker/daemon processes inherit
# the driver's env so a single export arms the whole cluster.
_controller: Optional[ChaosController] = ChaosController.from_env()


def enabled() -> bool:
    return _controller is not None


def controller() -> Optional[ChaosController]:
    return _controller


def configure(
    rules: Union[str, dict, Sequence], seed: Optional[int] = None
) -> ChaosController:
    """Arms THIS process programmatically (tests, provider-side chaos)."""
    global _controller
    if seed is None:
        seed = int(os.environ.get(SEED_ENV, "0") or 0)
    _controller = ChaosController(rules, seed=seed)
    return _controller


def disable() -> None:
    global _controller
    _controller = None


def maybe_inject(point: str, detail: str = "") -> Optional[ChaosRule]:
    """The hot-path entry every injection site calls. Disabled cost: one
    global load + None check. Returns the fired rule (the site applies
    its action) or None."""
    c = _controller
    if c is None:
        return None
    return c.maybe_inject(point, detail)


def kill_now(point: str, detail: str = "") -> None:
    """Applies a `kill` action: SIGKILL this process — no atexit, no
    graceful teardown, exactly like an OOM-kill or a preempted VM
    vanishing. Unlike the real failure, the CAUSE is ours: the flight
    ring (which holds the just-stamped ``chaos.inject``) is dumped and
    the metrics buffer flushed synchronously first, so a post-mortem
    `ray-tpu trace` shows the injection strictly before the crash's
    symptoms. To the rest of the cluster the death is indistinguishable
    from the real thing — the process state after SIGKILL is the same."""
    import signal

    try:
        from ..observability import flight_recorder as _frec

        _frec.dump(reason=f"chaos kill at {point}: {detail}")
    except Exception:  # lint: swallow-ok(pre-SIGKILL dump is best-effort by design)
        pass
    try:
        from ..utils import internal_metrics as imet

        imet._flush_once()
    except Exception:  # lint: swallow-ok(pre-SIGKILL metric flush is best-effort by design)
        pass
    os.kill(os.getpid(), signal.SIGKILL)
