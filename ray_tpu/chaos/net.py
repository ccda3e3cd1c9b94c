"""Network-partition chaos: black-hole control-plane traffic between node groups.

The kill/preempt points (controller.py) model machines *dying*. A real
TPU fleet's nastier failure is the machine that *keeps running* while
the network between it and the control plane is gone: its raylet misses
heartbeats, the GCS declares it dead and reschedules, and when the
partition heals the zombie is still there, holding leases and serving
actors. This module makes that failure injectable:

- ``chaos.partition(groups, one_way=…, heal_after=…)`` (driver side)
  computes, for every affected process (the GCS daemon, each raylet,
  the driver itself), the set of peer *addresses* it must stop talking
  to, and installs that spec into each process over RPC
  (``chaos_partition``). Addresses are the RPC endpoints the cluster
  already dials (``raylet_<node_id>.sock`` UDS paths, the GCS socket),
  so a spec is session-unique with no extra identity plumbing.
- The per-process half (``install``/``blocked_addr``/``heal``) is
  consulted by the injection points threaded into
  :meth:`ray_tpu.core.rpc.RpcClient.call` / ``_new_sock``: a blocked
  two-way ``call`` raises :class:`RpcUnavailableError` (the session is
  gone, not the data), a blocked one-way ``notify`` silently vanishes
  (a true black hole), and a blocked ``connect`` behaves like packets
  dropped on the floor — the client's own retry/backoff loop burns its
  deadline.
- Symmetric, one-way, and GCS-only partitions are all expressible as
  group edges; ``heal_after`` stamps a monotonic self-heal deadline in
  every process, so a partition can never outlive its spec even when
  the healing RPC itself is partitioned away.

Like every other chaos capability: installs and blocked sends are
flight-recorded (``chaos.partition`` / ``net.drop`` / ``net.heal``) and
counted (``raytpu_net_partitions_total`` / ``raytpu_net_blocked_total``)
so a campaign's telemetry proves the faults actually happened.

Disarmed cost at the rpc sites: one module-global load + ``is None``
check (same budget class as ``maybe_inject``).
"""

from __future__ import annotations

import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Set, Tuple

GCS = "gcs"
DRIVER = "driver"


class _PartitionState:
    """One installed partition spec. A process can hold SEVERAL at once
    (a chaos campaign routinely overlaps two partitions of different
    victims through the same GCS process) — each spec blocks its own
    addresses and heals on its own deadline; replacing a single global
    spec would silently lift the earlier partition's blocks."""

    __slots__ = ("blocked", "heal_at", "spec_id", "recorded")

    def __init__(
        self,
        blocked: Tuple[str, ...],
        heal_at: Optional[float],
        spec_id: str,
    ):
        self.blocked = blocked
        self.heal_at = heal_at
        self.spec_id = spec_id
        # Addresses whose first blocked send was already flight-recorded:
        # a partitioned heartbeat loop retries at 1 Hz and a reconnect
        # loop at 20 ms — recording every drop would wash the ring.
        self.recorded: Set[str] = set()


_lock = threading.Lock()
# spec_id -> _PartitionState. None (not {}) when empty so the rpc fast
# path's armed check stays one global load + truth test.
_specs: Optional[Dict[str, _PartitionState]] = None


def active() -> bool:
    """Cheap armed check for the rpc fast path."""
    return _specs is not None


def install(
    blocked: Sequence[str],
    heal_after: Optional[float] = None,
    spec_id: str = "",
) -> str:
    """Arms THIS process: sends/connects to any address containing one of
    `blocked` substrings are black-holed until heal()/the deadline.
    Specs stack — installing a second partition never lifts the first."""
    global _specs
    spec_id = spec_id or uuid.uuid4().hex[:8]
    heal_at = (
        time.monotonic() + max(0.0, heal_after) if heal_after is not None else None
    )
    with _lock:
        if _specs is None:
            _specs = {}
        _specs[spec_id] = _PartitionState(tuple(blocked), heal_at, spec_id)
    from ..observability.flight_recorder import record as _flight_record

    _flight_record("chaos.partition", (spec_id, tuple(b[-48:] for b in blocked)))
    try:
        from ..utils import internal_metrics as imet

        imet.NET_PARTITIONS.inc()
    except Exception:  # lint: swallow-ok(metrics must never break the injection itself)
        pass
    try:
        from ..observability.logs import get_logger

        get_logger("chaos").warning(
            "network partition %s installed: blocking %d peer address(es)%s",
            spec_id,
            len(blocked),
            f", self-heals in {heal_after:.1f}s" if heal_after else "",
        )
    except Exception:  # lint: swallow-ok(logging must never break the injection itself)
        pass
    return spec_id


def heal(spec_id: str = "") -> bool:
    """Disarms one spec (or, with no spec_id, every active spec) in this
    process. No-op when nothing matching is active."""
    global _specs
    healed: List[str] = []
    with _lock:
        if _specs is None:
            return False
        if spec_id:
            s = _specs.pop(spec_id, None)
            if s is not None:
                healed.append(s.spec_id)
        else:
            healed.extend(_specs)
            _specs.clear()
        if not _specs:
            _specs = None
    if not healed:
        return False
    from ..observability.flight_recorder import record as _flight_record

    for sid in healed:
        _flight_record("net.heal", (sid,))
    return True


def blocked_addr(addr: str) -> Optional[str]:
    """The matching blocked substring when `addr` is currently
    partitioned away from this process, else None. Each spec self-heals
    lazily at its own deadline (every process enforces its own clocks,
    so a partition can never outlive its spec even if the heal RPC
    itself is blocked)."""
    specs = _specs
    if specs is None:
        return None
    now = time.monotonic()
    for s in list(specs.values()):
        if s.heal_at is not None and now >= s.heal_at:
            heal(s.spec_id)
            continue
        for sub in s.blocked:
            if sub in addr:
                return sub
    return None


def note_drop(addr: str, what: str) -> None:
    """Accounting for one black-holed send/connect: counted always,
    flight-recorded once per (spec, address)."""
    try:
        from ..utils import internal_metrics as imet

        imet.NET_BLOCKED.inc()
    except Exception:  # lint: swallow-ok(metrics must never break the drop itself)
        pass
    specs = _specs
    if specs is None:
        return
    for s in list(specs.values()):
        if any(sub in addr for sub in s.blocked):
            if addr not in s.recorded:
                s.recorded.add(addr)
                from ..observability.flight_recorder import record as _flight_record

                _flight_record("net.drop", (what, addr[-48:]))
            return


class ChaosPartitionRpc:
    """The daemon-side RPC surface, mixed into GcsService and
    RayletService (one definition — the install contract must not
    diverge between the two): arms/heals partition specs in-process."""

    def chaos_partition(
        self,
        blocked: List[str],
        heal_after: Optional[float] = None,
        spec_id: str = "",
    ) -> bool:
        install(blocked, heal_after=heal_after, spec_id=spec_id)
        return True

    def chaos_heal(self, spec_id: str = "") -> bool:
        return heal(spec_id)


# ---------------------------------------------------------------- driver API
class Partition:
    """Handle to an installed partition: heal() tears it down everywhere
    the driver can still reach (the per-process heal_after deadline
    covers the rest)."""

    def __init__(self, spec_id: str, targets: List[Tuple[str, Any]], local: bool):
        self.spec_id = spec_id
        self._targets = targets  # (kind, RpcClient) for gcs/raylet installs
        self._local = local
        self.healed = False

    def heal(self) -> None:
        if self.healed:
            return
        if self._local:
            heal(self.spec_id)  # idempotent: safe across heal() retries
        failed = []
        for kind, cli in self._targets:
            try:
                cli.call("chaos_heal", self.spec_id, timeout=10.0)
            except Exception:  # lint: swallow-ok(peer may be partitioned away; its heal_after deadline covers it)
                failed.append((kind, cli))
        # Only a FULLY delivered heal closes the handle: with
        # heal_after=None there is no per-process deadline backstop, so a
        # target unreachable right now must stay retryable — otherwise a
        # swallowed failure black-holes that process until exit.
        self._targets = failed
        self.healed = not failed
        if failed:
            try:
                from ..observability.logs import get_logger

                get_logger("chaos").warning(
                    "partition %s: heal undelivered to %d target(s); "
                    "call heal() again (heal_after deadline covers them "
                    "if one was set)", self.spec_id[:8], len(failed),
                )
            except Exception:  # lint: swallow-ok(logging must never break the heal itself)
                pass

    def __enter__(self) -> "Partition":
        return self

    def __exit__(self, *exc) -> bool:
        self.heal()
        return False


def _resolve_members(
    groups: Sequence[Sequence[str]], runtime
) -> Tuple[Dict[str, int], Dict[str, str]]:
    """member -> group index; member -> RPC address string."""
    node_socks: Dict[str, str] = {}
    for n in runtime._gcs.call("list_nodes"):
        node_socks[n["NodeID"]] = n["sock"]
    member_group: Dict[str, int] = {}
    member_addr: Dict[str, str] = {}
    for gi, group in enumerate(groups):
        for m in group:
            if m in member_group:
                raise ValueError(f"partition member {m!r} appears in two groups")
            member_group[m] = gi
            if m == GCS:
                member_addr[m] = runtime._gcs.path
            elif m == DRIVER:
                member_addr[m] = ""  # nothing dials the driver via RpcClient
            else:
                sock = node_socks.get(m)
                if sock is None:
                    raise ValueError(
                        f"partition member {m!r} is not a known node id "
                        f"(known: {sorted(node_socks)}, or 'gcs'/'driver')"
                    )
                member_addr[m] = sock
    return member_group, member_addr


def partition(
    groups: Sequence[Sequence[str]],
    one_way: bool = False,
    heal_after: Optional[float] = None,
    runtime=None,
) -> Partition:
    """Partitions the cluster's control plane between `groups`.

    `groups` is a list of member lists; members are node ids (as shown
    by ``state.list_nodes()``/``Cluster.add_node``), ``"gcs"``, or
    ``"driver"``. Traffic between members of *different* groups is
    black-holed; members named in no group keep full connectivity.
    ``one_way=True`` blocks only the first group's *outbound* edges
    (its packets vanish; replies that never had a request don't exist).
    ``heal_after`` seconds stamps a self-heal deadline into every
    affected process; ``Partition.heal()`` heals early.

    GCS-only isolation of a node is ``partition([[node_id], ["gcs"]])``:
    the node's raylet and the GCS stop hearing each other while the
    driver (and the node's workers/data plane) stay connected — the
    zombie scenario the epoch fence exists for.
    """
    if runtime is None:
        from ..core.runtime_base import current_runtime

        runtime = current_runtime()
    if runtime is None:
        raise RuntimeError("chaos.partition needs an initialized cluster runtime")
    if len(groups) < 2:
        raise ValueError("a partition needs at least two groups")
    member_group, member_addr = _resolve_members(groups, runtime)

    def edge_blocked(src_gi: int, dst_gi: int) -> bool:
        if src_gi == dst_gi:
            return False
        return (src_gi == 0) if one_way else True

    spec_id = uuid.uuid4().hex[:8]
    installs: List[Tuple[str, List[str]]] = []  # (member, blocked substrings)
    for m, gi in member_group.items():
        blocked = sorted(
            {
                member_addr[peer]
                for peer, pgi in member_group.items()
                if member_addr[peer] and edge_blocked(gi, pgi)
            }
        )
        if blocked:
            installs.append((m, blocked))

    # Remote installs first (the driver must still reach every target at
    # install time), driver-local activation last.
    from ..core.rpc import RpcClient

    targets: List[Tuple[str, Any]] = []
    local = False
    local_blocked: List[str] = []
    try:
        for m, blocked in installs:
            if m == DRIVER:
                local = True
                local_blocked = blocked
                continue
            cli = (
                runtime._gcs
                if m == GCS
                else runtime._raylet_for(member_addr[m])
                if hasattr(runtime, "_raylet_for")
                else RpcClient(member_addr[m])
            )
            # Appended BEFORE the call: a chaos_partition whose reply is
            # lost may still have been DELIVERED (RpcClient resends after
            # a reconnect), so the rollback below must try to heal the
            # failing target too, not just the ones that acked. Healing a
            # spec that never installed is a no-op.
            targets.append((m, cli))
            cli.call("chaos_partition", blocked, heal_after, spec_id, timeout=10.0)
    except Exception:
        # Partial install: heal the targets that DID (or MAY have) armed
        # — without a handle (we raise before constructing one) and
        # possibly without a heal_after deadline, they would otherwise
        # stay black-holed until process exit.
        for _m, cli in targets:
            try:
                cli.call("chaos_heal", spec_id, timeout=10.0)
            except Exception:  # lint: swallow-ok(rollback heal; the heal_after deadline is the backstop)
                pass
        raise
    if local:
        install(local_blocked, heal_after=heal_after, spec_id=spec_id)
    return Partition(spec_id, targets, local)
