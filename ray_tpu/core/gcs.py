"""GCS: the cluster control plane (head-node daemon).

Re-design of the reference's GCS server (reference:
src/ray/gcs/gcs_server/gcs_server.h:80; node manager gcs_node_manager.h:45;
actor registry + restart FT gcs_actor_manager.h:308/:548; actor placement
gcs_actor_scheduler.h:111; placement groups gcs_placement_group_manager.h:230;
internal KV gcs_kv_manager.h; health checks gcs_health_check_manager.h;
object directory ownership_based_object_directory.h — centralized here
because the simulated cluster has no per-owner metadata service yet).

Runs as its own process serving RPC over a UDS. Like the reference, the
GCS is NOT on the task fast path: drivers talk to raylets for tasks and
objects; the GCS holds membership, actors, PGs, the object directory and
the resource view used for spillback decisions.
"""

from __future__ import annotations

import collections
import contextlib
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Set, Tuple

from . import gcs_shards as _gsh
from . import heartbeat as _hb
from ..chaos.net import ChaosPartitionRpc
from ..observability import postmortem as _postmortem
from ..exceptions import (
    ActorNameTakenError,
    PlacementGroupError,
    SchedulingError,
    StaleNodeEpochError,
)
from ..observability.flight_recorder import record as _frec_record
from ..utils import lock_order
from ..observability.logs import get_logger as _get_logger
from ..utils import internal_metrics as imet
from ..utils.config import CONFIG

_log = _get_logger("gcs")

HEARTBEAT_TIMEOUT_S = CONFIG.heartbeat_timeout_s


def _is_hard_affinity(strategy: str) -> bool:
    from .placement_group import decode_node_affinity

    aff = decode_node_affinity(strategy)
    return aff is not None and not aff[1]

# Finished/failed task records kept for the state API before FIFO eviction.
TASK_TABLE_CAP = 50_000


class GcsService(ChaosPartitionRpc):
    def __init__(
        self,
        snapshot_path: Optional[str] = None,
        session_dir: Optional[str] = None,
        shards: Optional[int] = None,
    ):
        self._lock = lock_order.tracked_rlock("gcs.state")
        self._snapshot_path = snapshot_path
        self._session_dir = session_dir or (
            os.path.dirname(snapshot_path) if snapshot_path else None
        )
        # Hot tables — nodes (+ their registration epochs), actors, and
        # the object directory (+ its borrow/free companions) — live in
        # N key-hashed shards, each with its own lock and WAL segment
        # (gcs_shards.py). Everything below stays on the control lock.
        # Monotonic per-node registration epochs (persisted): every
        # register_node stamps the next epoch for that node id, and every
        # raylet-originated RPC carries the epoch it was granted. A node
        # the health loop declared dead whose RPCs resume (a healed
        # partition's zombie) is FENCED: its calls are rejected with
        # StaleNodeEpochError until it re-registers as a fresh
        # incarnation — there is no silent resurrection path.
        self._nshards = _gsh.resolve_shard_count(shards)
        self._shards = _gsh.make_shards(self._nshards)
        self._named: Dict[Tuple[str, str], str] = {}
        self._kv: Dict[str, bytes] = {}
        # Freshness-window cache for full node-table dumps: at 1000
        # nodes, concurrent `status`/autoscaler/dashboard pollers would
        # each rebuild the full view; single-flighted behind this lock
        # (engaged only at scale — small clusters always read fresh).
        self._view_lock = lock_order.tracked_lock("gcs.nodeview")
        self._view_cache: Tuple[float, List[dict]] = (0.0, [])
        self._pgs: Dict[str, dict] = {}
        # Task table fed by batched raylet events (reference:
        # gcs_task_manager.h task events; used for owner-side failure
        # detection, lineage reconstruction decisions, and the state API).
        self._tasks: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
        # Cross-process borrow counts + free tombstones (the centralized
        # stand-in for the reference's owner<->borrower protocol,
        # reference_count.h WaitForRefRemoved): an owner's free is deferred
        # while borrowers hold the ref, and a freed object that seals late
        # (free raced the task) is deleted on arrival.
        self._removed_pgs: "collections.OrderedDict[str, bool]" = collections.OrderedDict()
        self._pg_creating: Set[str] = set()  # pending-PG retry in flight
        # Actor restarts currently in flight (node-death path). Kept OFF
        # the actor records: they are persisted (WAL/snapshot) and a
        # transient CAS flag restored after a GCS restart would block
        # that actor's restart path forever.
        self._actor_restarting: Set[str] = set()
        self._stranded_sweep_inflight = False  # one sweep thread at a time
        # Demand forecasts, keyed by source: autoscaler_v2's pending-actor
        # estimate ("autoscaler") and the data plane's starved-operator
        # pool growth ("data") both land here, summed into each heartbeat
        # reply's pool_hint so raylets pre-size their warm worker pools
        # BEFORE the launch storm arrives. The dict is REPLACED wholesale
        # on every write (never mutated in place) so the heartbeat path
        # can read it lock-free. {source: (value, expires_at_monotonic)}.
        self._demand_forecast: Dict[str, Tuple[int, float]] = {}
        # Borrow counts / free tombstones / deferred frees live on the
        # OBJECT's shard (same partition as its location set); only the
        # time-ordered free queue stays on the control lock.
        self._free_queue: List[Tuple[float, List[str]]] = []
        self._raylet_clients: Dict[str, Any] = {}
        self._user_metrics: Dict[Tuple, dict] = {}
        # Runtime-internal metrics table (reference: metric_defs.cc
        # runtime metrics aggregated by the head's metrics agent) — same
        # merge semantics as the user table, separate namespace.
        self._internal_metrics: Dict[Tuple, dict] = {}
        # Per-series time-series retention: every internal-metrics merge
        # also lands a (bounded, rolled-up) history sample, so rates and
        # regressions stay answerable after the moment passes
        # (observability/history.py; queried via `metrics_history`).
        from ..observability import history as _history_mod

        self._history = (
            _history_mod.MetricsHistory()
            if _history_mod.history_enabled()
            else None
        )
        # Cluster error reports (uncaught worker exceptions, crashes):
        # bounded ring fed by `report_error`, mirrored on the
        # `error_reports` pubsub channel.
        self._errors: List[dict] = []
        # General pubsub channels: name -> [(seq, message)] (bounded).
        self._pubsub: Dict[str, List[Tuple[int, Any]]] = {}
        self._pubsub_total = 0  # running entry count across channels
        self._pubsub_cv = threading.Condition()
        self._stop = threading.Event()
        # Write-ahead delta log between snapshots (reference: the Redis
        # store client persists control-table mutations as they happen,
        # redis_store_client.h:106; here an append-only file of
        # (table, key, record) deltas replayed over the last snapshot).
        # High-rate data-plane state (object locations, task events) stays
        # snapshot-only — as in the reference, where the object directory
        # is owner-based and rebuilt, not persisted.
        self._wal_path = snapshot_path + ".wal" if snapshot_path else None
        self._wal_f = None
        if snapshot_path:
            self._load_snapshot()
            self._replay_wal()
            self._wal_f = open(self._wal_path, "ab")
            for sh in self._shards:
                sh.wal_open(_gsh.wal_segment_path(snapshot_path, sh.index))
                sh.recount_alive()
            # Snapshot right after replay: every replayed segment (legacy
            # single-file WALs, segments written under a different shard
            # count) is folded into one durable snapshot and truncated, so
            # all live segments were written under THIS shard count.
            self._save_snapshot()
        self._health = threading.Thread(target=self._health_loop, daemon=True)
        self._health.start()
        # SLO watchdog: rules over the history stream, alerts onto the
        # node_events channel (observability/watchdog.py). Needs history.
        self._watchdog = None
        if self._history is not None:
            from ..observability import watchdog as _watchdog_mod

            if _watchdog_mod.watchdog_enabled():
                self._watchdog = _watchdog_mod.Watchdog(
                    history=self._history,
                    publish=lambda msg: self.pubsub_publish("node_events", msg),
                    metrics_fn=self.internal_metrics,
                )
                self._watchdog.start()
        # Anomaly trigger bus (observability/postmortem.py): incoming
        # triggers — remote via the report_trigger RPC, in-process via
        # the armed publisher — coalesce into incidents; each fresh
        # incident runs ONE harvest fan-out off-thread. Bounded ring of
        # incident records; bundles live under <session>/incidents/.
        self._incident_lock = lock_order.tracked_lock("gcs.incidents")
        self._incidents: "collections.OrderedDict[str, dict]" = collections.OrderedDict()
        self._open_incident: Optional[str] = None
        # In-process anomaly sources (the watchdog thread, chaos faults
        # injected inside THIS process) publish straight to _trigger.
        _postmortem.arm(self._trigger)

    # ------------------------------------------------------- persistence
    # Durable control-plane state (reference: gcs/store_client/
    # redis_store_client.h:106 — file-backed here; a GCS restart reloads
    # actors/PGs/KV and raylets re-register via heartbeat NACK, the
    # RayletNotifyGCSRestart analogue, core_worker.proto:441).
    _PERSISTED = (
        "_nodes",
        "_node_epochs",
        "_actors",
        "_named",
        "_pgs",
        "_kv",
        "_objects",
        "_freed",
        "_borrows",
        "_deferred_free",
    )

    # Tables split across the key-hashed shards; the snapshot stores them
    # MERGED under these names (format-compatible with pre-sharding
    # snapshots), and _load_snapshot scatters them back by key.
    _NODE_SHARDED = ("_nodes", "_node_epochs")
    _ACTOR_SHARDED = ("_actors",)
    _OBJECT_SHARDED = ("_objects", "_freed", "_borrows", "_deferred_free")
    _SHARD_ATTRS = {
        "_nodes": "nodes",
        "_node_epochs": "node_epochs",
        "_actors": "actors",
        "_objects": "objects",
        "_freed": "freed",
        "_borrows": "borrows",
        "_deferred_free": "deferred_free",
    }

    # ---------------------------------------------------- shard routing
    def _node_shard(self, node_id: str) -> _gsh.GcsShard:
        return self._shards[_gsh.shard_index(node_id, self._nshards)]

    def _actor_shard(self, actor_id: str) -> _gsh.GcsShard:
        return self._shards[_gsh.shard_index(actor_id, self._nshards)]

    def _object_shard(self, oid_hex: str) -> _gsh.GcsShard:
        return self._shards[_gsh.shard_index(oid_hex, self._nshards)]

    @contextlib.contextmanager
    def _locked(self, sh: _gsh.GcsShard):
        """Shard lock acquisition with the wait measured — the direct
        residual-contention signal (raytpu_gcs_shard_lock_wait_ms).
        Lock order: gcs.state may be held on entry; shard locks nest in
        ascending index only; NEVER take gcs.state while holding one."""
        t0 = time.perf_counter()
        with sh.lock:
            imet.GCS_SHARD_LOCK_WAIT.observe(
                (time.perf_counter() - t0) * 1e3, shard=str(sh.index)
            )
            yield sh

    def _alive_nodes(self) -> int:
        """O(shards) alive count off the per-shard counters — lock-free
        (a torn read across counters is at worst one heartbeat stale)."""
        return sum(sh.alive_count for sh in self._shards)

    def _node_count(self) -> int:
        return sum(len(sh.nodes) for sh in self._shards)

    def _nodes_view_for(self, nids) -> Dict[str, dict]:
        """Resolves node ids to {sock, store, alive} in one pass, grouped
        by shard (ascending, one lock each) — the cross-shard join used
        by object-location reads and the free path."""
        by_shard: Dict[int, List[str]] = {}
        for nid in set(nids):
            by_shard.setdefault(
                _gsh.shard_index(nid, self._nshards), []
            ).append(nid)
        out: Dict[str, dict] = {}
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            with self._locked(sh):
                for nid in by_shard[idx]:
                    n = sh.nodes.get(nid)
                    if n is not None:
                        out[nid] = {
                            "sock": n["sock"],
                            "store": n["store"],
                            "alive": n["alive"],
                        }
        return out

    def _node_sock(self, node_id: str, alive_only: bool = True) -> Optional[str]:
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n is None or (alive_only and not n["alive"]):
                return None
            return n["sock"]

    def _load_snapshot(self) -> None:
        import pickle

        try:
            with open(self._snapshot_path, "rb") as f:
                data = pickle.load(f)
        except (OSError, EOFError, pickle.UnpicklingError):
            return
        with self._lock:
            for name in ("_named", "_pgs", "_kv"):
                if name in data:
                    setattr(self, name, data[name])
            for pg in self._pgs.values():
                # A snapshot taken mid-reschedule must resume as
                # RESCHEDULING: only that state is retried.
                if pg.get("state") == "REPLANNING":
                    pg["state"] = "RESCHEDULING"
        now = time.monotonic()
        for name, attr in self._SHARD_ATTRS.items():
            merged = data.get(name)
            if merged is None:
                continue
            if isinstance(merged, (set, frozenset)):
                for key in merged:
                    sh = self._shards[_gsh.shard_index(key, self._nshards)]
                    with sh.lock:
                        getattr(sh, attr).add(key)
                continue
            for key, value in merged.items():
                if name == "_nodes":
                    # Grace: loaded nodes get a fresh heartbeat window;
                    # truly dead ones expire through the health check.
                    value["last_hb"] = now
                sh = self._shards[_gsh.shard_index(key, self._nshards)]
                with sh.lock:
                    getattr(sh, attr)[key] = value

    def _persist_delta(self, table: str, key, value) -> None:
        """Appends one CONTROL-table delta (_named/_pgs/_kv) to the meta
        WAL (value=None deletes). Called with self._lock held by the
        mutating handler, so snapshot truncation (also under the lock)
        can never lose a record. Sharded-table deltas go through the
        owning shard's wal_append under that shard's lock instead."""
        if self._wal_f is None:
            return
        try:
            self._wal_f.write(_gsh.encode_wal_record(table, key, value))
            self._wal_f.flush()
        except Exception as e:
            # Durability is best-effort between snapshots, but a WAL that
            # stopped persisting (disk full, unpicklable value) must be
            # visible once — silently running without it turns the next
            # GCS restart into state loss.
            if not getattr(self, "_wal_warned", False):
                self._wal_warned = True
                _log.warning("WAL append failed; durability degraded to snapshots: %r", e)

    _WAL_TABLES = (
        "_nodes", "_node_epochs", "_actors", "_named", "_pgs", "_kv",
    )

    def _replay_wal(self) -> None:
        """Replays every WAL file over the loaded snapshot: the meta
        segment (control tables; also sharded-table records from a
        legacy pre-sharding boot) and all shard segments. Records route
        by table+key under the CURRENT shard count, so a shard-count
        change between boots cannot misfile state."""
        for path in _gsh.discover_wal_paths(self._snapshot_path):
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                continue
            for table, key, value in _gsh.iter_wal_records(data):
                if table not in self._WAL_TABLES:
                    continue
                attr = self._SHARD_ATTRS.get(table)
                if attr is not None:
                    sh = self._shards[_gsh.shard_index(key, self._nshards)]
                    with sh.lock:
                        d = getattr(sh, attr)
                        if value is None:
                            d.pop(key, None)
                        else:
                            d[key] = value
                            if table == "_nodes":
                                value["last_hb"] = time.monotonic()
                else:
                    with self._lock:
                        d = getattr(self, table)
                        if value is None:
                            d.pop(key, None)
                        else:
                            d[key] = value

    def _save_snapshot(self) -> None:
        if not self._snapshot_path:
            return
        import copy
        import pickle

        data: Dict[str, Any] = {}
        with self._lock:
            # Shallow-ish copies under the lock (fast pointer copies);
            # the expensive pickle runs OUTSIDE so RPCs aren't stalled.
            for name in ("_named", "_pgs", "_kv"):
                data[name] = copy.copy(getattr(self, name))
            # Remember how much of each WAL this snapshot covers;
            # rotation happens only AFTER the snapshot is durably on
            # disk (wiping first would lose every delta if the pickle/
            # write fails or the process dies in between).
            wal_covered = 0
            if self._wal_f is not None:
                try:
                    self._wal_f.flush()
                    wal_covered = self._wal_f.tell()
                except Exception:
                    wal_covered = 0
        for name in self._SHARD_ATTRS:
            data[name] = set() if name == "_deferred_free" else {}
        shard_covered: List[int] = []
        for sh in self._shards:
            with self._locked(sh):
                for name, attr in self._SHARD_ATTRS.items():
                    part = getattr(sh, attr)
                    if isinstance(part, set):
                        data[name] |= part
                    else:
                        data[name].update(part)
                shard_covered.append(sh.wal_covered())
        try:
            blob = pickle.dumps(data)
        except Exception:
            return  # WAL still intact: nothing lost
        tmp = self._snapshot_path + ".tmp"
        try:
            with open(tmp, "wb") as f:
                f.write(blob)
            os.replace(tmp, self._snapshot_path)
        except OSError:
            return  # retried next interval; WAL still intact
        if wal_covered:
            with self._lock:
                if self._wal_f is None:
                    return
                try:
                    # Rotate: keep only deltas appended AFTER the copy
                    # (they are not in the snapshot).
                    self._wal_f.flush()
                    with open(self._wal_path, "rb") as rf:
                        rf.seek(wal_covered)
                        suffix = rf.read()
                    self._wal_f.close()
                    with open(self._wal_path, "wb") as wf:
                        wf.write(suffix)
                    self._wal_f = open(self._wal_path, "ab")
                except Exception:
                    try:  # never leave the WAL handle closed
                        self._wal_f = open(self._wal_path, "ab")
                    except Exception:
                        self._wal_f = None
        for sh, covered in zip(self._shards, shard_covered):
            if covered:
                with self._locked(sh):
                    sh.wal_rotate(covered)

    # ------------------------------------------------------------- nodes
    def _register_node_locked(
        self,
        sh: _gsh.GcsShard,
        node_id: str,
        sock_path: str,
        store_path: str,
        resources: dict,
        labels: Optional[dict],
        wal_out: List[Tuple[str, Any, Any]],
    ) -> int:
        """Inserts one node record (owning shard's lock held), collecting
        its WAL deltas into `wal_out` so batched registration can group-
        commit them. Returns the granted epoch."""
        # A fresh epoch per registration: a fenced/partitioned
        # incarnation rejoining gets a new number, and everything
        # still stamped with the old one stays rejected.
        epoch = sh.node_epochs.get(node_id, 0) + 1
        sh.node_epochs[node_id] = epoch
        prev = sh.nodes.get(node_id)
        if prev is None or not prev["alive"]:
            sh.alive_count += 1
        sh.nodes[node_id] = {
            "sock": sock_path,
            "store": store_path,
            "resources": dict(resources),
            "available": dict(resources),
            "labels": dict(labels or {}),
            "alive": True,
            "epoch": epoch,
            "last_hb": time.monotonic(),
        }
        wal_out.append(("_node_epochs", node_id, epoch))
        wal_out.append(("_nodes", node_id, sh.nodes[node_id]))
        return epoch

    def _post_register(self, registered: List[Tuple[str, int]]) -> None:
        """Shared fan-out after node registration(s): stranded-gang and
        stranded-actor retries, lifecycle events, node-table deltas."""
        with self._lock:
            retry_gangs = [
                pg_id
                for pg_id, pg in self._pgs.items()
                if pg.get("state") == "RESCHEDULING"
            ]
        for node_id, epoch in registered:
            _frec_record("node.added", (node_id[:12], epoch))
        if retry_gangs:
            # A new host may complete a slice: retry stranded gangs.
            threading.Thread(
                target=lambda: [self._reschedule_gang(p) for p in retry_gangs],
                daemon=True,
            ).start()
        # Node-death-stranded actors get the same treatment: new capacity
        # is the retry trigger for their restart placement.
        self._kick_stranded_restarts()
        # Capacity-wait subscribers (JaxTrainer's elastic renegotiation)
        # block on node_events instead of polling the node table: a join
        # is as much a lifecycle event as a drain.
        for node_id, epoch in registered:
            self.pubsub_publish(
                "node_events",
                {"event": "node_added", "node_id": node_id, "epoch": epoch,
                 "ts": time.time()},
            )
            self._publish_node_delta(node_id)

    def register_node(
        self,
        node_id: str,
        sock_path: str,
        store_path: str,
        resources: dict,
        labels: Optional[dict] = None,
    ) -> dict:
        sh = self._node_shard(node_id)
        wal: List[Tuple[str, Any, Any]] = []
        with self._locked(sh):
            epoch = self._register_node_locked(
                sh, node_id, sock_path, store_path, resources, labels, wal
            )
            sh.wal_append_many(wal)
        n_alive = self._alive_nodes()
        self._post_register([(node_id, epoch)])
        return {"ok": True, "nodes": n_alive, "epoch": epoch}

    def register_nodes(self, specs: List[dict]) -> List[dict]:
        """Batched registration: ONE RPC admits a storm of nodes. The
        batch is partitioned per shard and applied under per-shard locks
        — never a global one — with each shard's WAL deltas landing as a
        single group commit (one write+flush per shard touched, not two
        per node). Spec keys: node_id, sock, store, resources, labels."""
        by_shard: Dict[int, List[dict]] = {}
        for s in specs:
            by_shard.setdefault(
                _gsh.shard_index(s["node_id"], self._nshards), []
            ).append(s)
        epochs: Dict[str, int] = {}
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            wal: List[Tuple[str, Any, Any]] = []
            with self._locked(sh):
                for s in by_shard[idx]:
                    epochs[s["node_id"]] = self._register_node_locked(
                        sh,
                        s["node_id"],
                        s["sock"],
                        s["store"],
                        s.get("resources") or {},
                        s.get("labels"),
                        wal,
                    )
                sh.wal_append_many(wal)
        n_alive = self._alive_nodes()
        self._post_register([(s["node_id"], epochs[s["node_id"]]) for s in specs])
        return [
            {"ok": True, "nodes": n_alive, "epoch": epochs[s["node_id"]]}
            for s in specs
        ]

    # ------------------------------------------------------------ fencing
    def _mark_fenced_locked(
        self, sh: _gsh.GcsShard, node_id: str, n: dict
    ) -> bool:
        """Stamps the FENCED state on a dead/stale node record (owning
        shard's lock held). Returns True on the first fencing of this
        incarnation — the caller publishes/counts outside the lock."""
        if n.get("fenced"):
            return False
        if n["alive"]:
            sh.alive_count -= 1
        n["alive"] = False  # fencing implies dead; never resurrect in place
        n["fenced"] = True
        n["fenced_ts"] = time.time()
        sh.wal_append("_nodes", node_id, n)
        return True

    def _reject_stale_node(
        self, node_id: str, epoch: Optional[int], context: str
    ) -> None:
        """The fence itself: raises StaleNodeEpochError when `node_id` is
        dead-marked or `epoch` does not match the current registration.
        Every raylet-originated mutation path calls this first — a
        partitioned node that was declared dead keeps *executing*, but
        nothing it says moves cluster state until it re-registers as a
        fresh incarnation (no silent resurrection). The verdict is judged
        under the NODE's shard lock — a cross-shard mutation (say an
        actor write whose fencing record lives elsewhere) takes the node
        shard here, releases it, then takes the mutation's own shard:
        sequential, never nested."""
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n is None:
                return  # unknown node: the caller's NACK path handles it
            verdict = self._fence_verdict_locked(sh, node_id, n, epoch)
        if verdict is not None:
            self._raise_fenced(node_id, epoch, verdict, context)

    def _fence_verdict_locked(
        self, sh: _gsh.GcsShard, node_id: str, n: dict, epoch: Optional[int]
    ) -> Optional[Tuple[Optional[int], bool]]:
        """Judges one raylet-originated call against the membership record
        (owning shard's lock held — callers that also mutate the record do
        both under ONE acquisition, so the verdict and the mutation cannot
        interleave with a concurrent re-registration). Returns None when
        the caller is current, else (current_epoch, newly_fenced) with a
        dead-marked record stamped FENCED."""
        cur = n.get("epoch")
        stale = epoch is not None and cur is not None and epoch != cur
        if n["alive"] and not stale:
            return None
        newly_fenced = False
        if not n["alive"]:
            # Only a dead-marked record is stamped FENCED. A
            # stale-epoch call against an ALIVE record is an OLD
            # incarnation talking after its successor re-registered:
            # the caller is rejected, but the CURRENT incarnation's
            # record must not be touched.
            newly_fenced = self._mark_fenced_locked(sh, node_id, n)
        return (cur, newly_fenced)

    def _raise_fenced(
        self,
        node_id: str,
        epoch: Optional[int],
        verdict: Tuple[Optional[int], bool],
        context: str,
    ) -> None:
        """Finalizes a fence rejection outside the lock: counts/records/
        publishes on the FIRST fencing of an incarnation, then raises the
        typed error every time."""
        cur, newly_fenced = verdict
        if newly_fenced:
            imet.NODES_FENCED.inc()
            _frec_record("node.fence", (node_id[:12], epoch, cur, context))
            _log.warning(
                "fencing node %s (%s; claimed epoch %s, current %s): "
                "rejecting its RPCs until it re-registers",
                node_id[:12], context, epoch, cur,
            )
            # Supervisors treat fencing exactly like death: same channel,
            # its own event so post-mortems can tell the two apart.
            self.pubsub_publish(
                "node_events",
                {
                    "event": "node_fenced",
                    "node_id": node_id,
                    "epoch": epoch,
                    "current_epoch": cur,
                    "ts": time.time(),
                },
            )
            self._trigger(
                "node.fenced",
                {"node_id": node_id[:12], "epoch": epoch, "current": cur},
                source="gcs",
            )
            self._publish_node_delta(node_id)
        raise StaleNodeEpochError(
            node_id,
            claimed_epoch=epoch,
            current_epoch=cur,
            reason=f"{context}: node is dead-marked or its epoch is stale",
        )

    def heartbeat(
        self,
        node_id: str,
        available: Optional[dict] = None,
        stats: Optional[dict] = None,
        epoch: Optional[int] = None,
    ) -> dict:
        """The 1 Hz fan-in. Payloads are DELTAS (core/heartbeat.py):
        `available` is None when unchanged, `stats` carries only changed
        keys (a full resend sets stats["full"]). The whole beat touches
        only the node's own shard — never the control lock, never an
        O(cluster) scan."""
        raylet_drained = False
        alive = self._alive_nodes()
        # Warm-pool demand hint: this node's share of the summed demand
        # forecasts — launches expected but NOT yet registered
        # (registration consumes the forecast). The autoscaler's
        # pending-actor storms and the data plane's starved-operator pool
        # growth are independent sources, so they add. Deliberately
        # excludes already-registered PENDING actors: those are consuming
        # the pool right now, the raylet's local launch-rate EWMA already
        # sees them, and counting them here double-inflated the target
        # right as the storm peaked. Read lock-free BEFORE the shard lock
        # (the dict is swapped atomically; gcs.state must never be taken
        # while a shard lock is held).
        now_mono = time.monotonic()
        fc_n = sum(
            n for n, exp in self._demand_forecast.values() if n > 0 and now_mono < exp
        )
        pool_hint = 0
        if fc_n > 0 and alive > 0:
            pool_hint = -(-fc_n // alive)  # ceil division
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n is None:
                return {"ok": False, "nodes": alive}
            # Verdict and update under ONE lock acquisition: judging here
            # and re-deriving inside _reject_stale_node left a window
            # where a concurrent re-registration flipped the record
            # between the two and a fenced-judged heartbeat returned ok
            # without having applied its update.
            verdict = self._fence_verdict_locked(sh, node_id, n, epoch)
            if verdict is None:
                if stats:
                    _hb.apply_heartbeat(n, available, dict(stats))
                    merged = n.get("stats") or {}
                    if merged.get("draining") and not n.get("draining"):
                        raylet_drained = True
                    # Clock-offset sampling on the heartbeat path: the
                    # raylet stamps its wall-clock send time; offset =
                    # gcs_now - send_time (network latency folds in, a
                    # one-way UDS/TCP hop — microseconds against the
                    # inter-host skews this corrects). The incident
                    # merger shifts that node's flight/span timestamps
                    # by this to restore cross-node causal order.
                    wall = merged.get("wall_ts")
                    if isinstance(wall, (int, float)):
                        n["clock_offset_us"] = int((time.time() - wall) * 1e6)
                elif available is not None:
                    n["available"] = dict(available)
                n["last_hb"] = time.monotonic()
        if verdict is not None:
            # A heartbeat from a dead-marked node used to flip it back
            # alive in place — the silent-resurrection bug: the zombie
            # kept its workers, leases, and (GCS-side) a duplicate of
            # every named actor already rescheduled elsewhere. Now it is
            # NACKed with the typed fence error; the raylet reacts by
            # killing its workers and re-registering as a fresh node.
            self._raise_fenced(node_id, epoch, verdict, "heartbeat")
        if raylet_drained:
            # Raylet-initiated drain (chaos/local admin): adopt it through
            # the same path as a GCS-initiated one so scheduling exclusion,
            # subscriber notification, persistence, and the drained
            # counter all fire identically.
            self.report_preemption(node_id, 0.0, "raylet-initiated drain")
        return {"ok": True, "nodes": alive, "pool_hint": pool_hint}

    def report_demand_forecast(
        self, n: int, ttl_s: float = 15.0, source: str = "autoscaler"
    ) -> bool:
        """Pending-work forecast from `source` (actors expected to launch
        cluster-wide soon): autoscaler_v2 relays pending-actor estimates,
        data/op_pool.py declares starved-operator pool growth. Each
        source's forecast is independent — a new report REPLACES that
        source's prior value and TTL only. TTL-bounded: a crashed
        reporter's stale forecast must decay instead of pinning every
        pool high forever. Each heartbeat reply hands every raylet
        ceil(sum / alive_nodes) as its pool_hint share."""
        with self._lock:
            fc = dict(self._demand_forecast)
            fc[str(source)] = (
                max(0, int(n)),
                time.monotonic() + max(0.0, float(ttl_s)),
            )
            self._demand_forecast = fc  # atomic whole-dict swap
        return True

    # ---------------------------------------------------- preemption/drain
    def report_preemption(
        self, node_id: str, deadline_s: float = 30.0, reason: str = "preempted"
    ) -> bool:
        """A preemption notice for `node_id` (synthesized by chaos / the
        local provider, or relayed from the cloud's metadata server by a
        real one). The node enters the DRAINING state: it stays alive and
        keeps executing in-flight work, but new placement avoids it, its
        raylet stops granting leases, and `node_draining` is published on
        the `node_events` pubsub channel so gang supervisors (train,
        serve, cgraph drivers) can checkpoint/replace before the machine
        actually dies at the deadline."""
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n is None:
                return False
            already = bool(n.get("draining"))
            n["draining"] = True
            n["drain_reason"] = reason
            n["drain_deadline"] = time.time() + max(0.0, deadline_s)
            sh.wal_append("_nodes", node_id, n)
            sock = n["sock"] if n["alive"] else None
        if already:
            return True
        imet.NODES_DRAINED.inc()
        _frec_record("node.drain_notice", (node_id[:12], deadline_s, reason))
        self._announce_draining(node_id, deadline_s, reason)
        self._publish_node_delta(node_id)
        # Flip the raylet into drain mode (best-effort: on a real
        # preemption the machine may already be unreachable — the pubsub
        # notice above is the part subscribers can rely on).
        if sock:
            try:
                self._raylet_call(sock, "drain", deadline_s)
            except Exception as e:
                _log.debug("drain RPC to %s failed (node may already be gone): %r",
                           sock, e)
        return True

    def _announce_draining(self, node_id: str, deadline_s: float, reason: str) -> None:
        self.pubsub_publish(
            "node_events",
            {
                "event": "node_draining",
                "node_id": node_id,
                "deadline_s": deadline_s,
                "reason": reason,
                "ts": time.time(),
            },
        )

    def drain_node(self, node_id: str) -> bool:
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n:
                if n["alive"]:
                    sh.alive_count -= 1
                n["alive"] = False
                sh.wal_append("_nodes", node_id, n)
        self._on_node_death(node_id)
        return True

    @staticmethod
    def _node_state(n: dict) -> str:
        """The membership state machine's label for one node record:
        ALIVE -> DRAINING (preemption notice) -> DEAD (heartbeat expiry /
        drain deadline) -> FENCED (a dead-marked incarnation's RPCs came
        back and were rejected) -> rejoin via register_node (node_added,
        fresh epoch)."""
        if n["alive"]:
            return "DRAINING" if n.get("draining") else "ALIVE"
        return "FENCED" if n.get("fenced") else "DEAD"

    @classmethod
    def _node_entry(cls, nid: str, n: dict) -> dict:
        return {
            "NodeID": nid, "Alive": n["alive"], "Resources": dict(n["resources"]),
            "Available": dict(n["available"]), "Labels": dict(n.get("labels") or {}),
            "Stats": dict(n.get("stats") or {}),
            "Draining": bool(n.get("draining")),
            "DrainReason": n.get("drain_reason"),
            "DrainDeadline": n.get("drain_deadline"),
            "Epoch": n.get("epoch"),
            "Fenced": bool(n.get("fenced")),
            "State": cls._node_state(n),
            "sock": n["sock"], "store": n["store"],
        }

    # Full-dump cache freshness window and the cluster size at which it
    # engages. Below the threshold every call reads fresh (tests and
    # small clusters see exact state); above it, concurrent dump callers
    # share one build per window instead of each walking 1000 records.
    _VIEW_TTL_S = 0.25
    _VIEW_MIN_NODES = 256

    def _build_node_view(self, limit: Optional[int]) -> List[dict]:
        out: List[dict] = []
        for sh in self._shards:
            with self._locked(sh):
                for nid, n in sh.nodes.items():
                    out.append(self._node_entry(nid, n))
                    if limit is not None and len(out) >= limit:
                        return out
        return out

    def list_nodes(self, limit: Optional[int] = None) -> List[dict]:
        if limit is not None:
            return self._build_node_view(max(0, int(limit)))
        if self._node_count() < self._VIEW_MIN_NODES:
            return self._build_node_view(None)
        # Single-flight at scale: one builder per freshness window; the
        # other dump callers (status, autoscaler, dashboard) wait on the
        # view lock and reuse its result.
        with self._view_lock:
            ts, cached = self._view_cache
            if time.monotonic() - ts < self._VIEW_TTL_S:
                return cached
            fresh = self._build_node_view(None)
            self._view_cache = (time.monotonic(), fresh)
            return fresh

    def node_summary(self) -> dict:
        """O(nodes) single-pass rollup for `ray-tpu status --summary`:
        counts by membership state plus cluster resource totals — the
        1000-node answer that doesn't ship 1000 full records."""
        by_state: Dict[str, int] = {}
        resources: Dict[str, float] = {}
        available: Dict[str, float] = {}
        draining = 0
        total = 0
        for sh in self._shards:
            with self._locked(sh):
                for n in sh.nodes.values():
                    total += 1
                    st = self._node_state(n)
                    by_state[st] = by_state.get(st, 0) + 1
                    if n.get("draining"):
                        draining += 1
                    if n["alive"]:
                        for k, v in n["resources"].items():
                            resources[k] = resources.get(k, 0.0) + v
                        for k, v in n["available"].items():
                            available[k] = available.get(k, 0.0) + v
        return {
            "total": total,
            "alive": self._alive_nodes(),
            "draining": draining,
            "by_state": by_state,
            "resources": resources,
            "available": available,
        }

    def list_actors(self, limit: int = 1000) -> List[dict]:
        """Actor table summary for the state API (reference:
        python/ray/util/state/api.py list_actors)."""
        out: List[dict] = []
        for sh in self._shards:
            with self._locked(sh):
                out.extend(
                    {
                        "actor_id": aid,
                        "state": a["state"],
                        "node_id": a.get("node_id"),
                        "name": a.get("name"),
                        "namespace": a.get("namespace"),
                        "num_restarts": a.get("num_restarts", 0),
                        "max_restarts": a.get("max_restarts", 0),
                        "pg_id": a.get("pg_id"),
                        "death_reason": a.get("death_reason", ""),
                    }
                    for aid, a in sh.actors.items()
                )
        return out[-limit:]

    def list_objects(self, limit: int = 1000) -> List[dict]:
        """Object directory summary (reference: list_objects in the state
        API; ours reports locations + borrow/pending-free status)."""
        out = []
        for sh in self._shards:
            with self._locked(sh):
                for h, locs in list(sh.objects.items())[-limit:]:
                    out.append(
                        {
                            "object_id": h,
                            "locations": sorted(locs),
                            "borrows": sh.borrows.get(h, 0),
                            "pending_free": h in sh.deferred_free,
                        }
                    )
        return out[-limit:]

    def _merge_metric_records(
        self,
        table: Dict[Tuple, dict],
        worker_id: str,
        records: List[dict],
        history=None,
    ) -> bool:
        """Shared aggregation for the user and internal metrics tables
        (reference: src/ray/stats/metric.h registry + exporter). Counters
        accumulate deltas; gauges keep the last value per (worker, tags);
        histograms merge bucket counts. With `history`, every merged
        series also lands a cumulative sample in the history rings."""
        with self._lock:
            for rec in records:
                key = (rec["name"], tuple(sorted(rec.get("tags", {}).items())))
                entry = table.setdefault(
                    key,
                    {
                        "name": rec["name"],
                        "kind": rec["kind"],
                        "tags": dict(rec.get("tags", {})),
                        "value": 0.0,
                        "gauges": {},
                    },
                )
                if rec["kind"] == "counter":
                    entry["value"] += float(rec["value"])
                elif rec["kind"] == "gauge":
                    entry["gauges"][worker_id] = (float(rec["value"]), time.monotonic())
                elif rec["kind"] == "histogram":
                    entry["value"] += float(rec["value"])
                    counts = rec.get("counts") or []
                    have = entry.setdefault("counts", [0] * len(counts))
                    if len(have) == len(counts):
                        entry["counts"] = [a + b for a, b in zip(have, counts)]
                    entry.setdefault("boundaries", rec.get("boundaries"))
                if history is not None:
                    if rec["kind"] == "counter":
                        history.observe(
                            entry["name"], "counter", entry["tags"], entry["value"]
                        )
                    elif rec["kind"] == "gauge":
                        # Cluster aggregate with the SAME 30 s staleness
                        # rule as _metrics_view: a dead worker's last
                        # value (same tags, different worker_id) must
                        # not inflate history samples until something
                        # happens to render the table view.
                        now_m = time.monotonic()
                        total = sum(
                            v
                            for v, ts in entry["gauges"].values()
                            if now_m - ts < 30.0
                        )
                        history.observe(entry["name"], "gauge", entry["tags"], total)
                    elif rec["kind"] == "histogram":
                        history.observe(
                            entry["name"],
                            "histogram",
                            entry["tags"],
                            float(sum(entry.get("counts") or [])),
                            hist_sum=entry["value"],
                        )
        return True

    def _metrics_view(self, table: Dict[Tuple, dict]) -> List[dict]:
        now = time.monotonic()
        out: List[dict] = []
        with self._lock:
            for v in table.values():
                if v["kind"] == "gauge":
                    # A dead worker's last gauge value must not inflate the
                    # cluster sum forever: reporters stale for 30 s are
                    # PRUNED IN PLACE (worker churn would otherwise grow
                    # the stored dict without bound), and only fresh ones
                    # count (gauges re-report every flush interval).
                    stale = [
                        w for w, (_, ts) in v["gauges"].items() if now - ts >= 30.0
                    ]
                    for w in stale:
                        del v["gauges"][w]
                    v["value"] = sum(val for val, _ in v["gauges"].values())
                entry = dict(v)
                if entry["kind"] == "gauge":
                    entry["gauges"] = {w: val for w, (val, _) in v["gauges"].items()}
                out.append(entry)
        return out

    def report_metrics(self, worker_id: str, records: List[dict]) -> bool:
        """User-defined application metrics (ray_tpu.utils.metrics)."""
        return self._merge_metric_records(self._user_metrics, worker_id, records)

    def user_metrics(self) -> List[dict]:
        return self._metrics_view(self._user_metrics)

    def report_internal_metrics(self, worker_id: str, records: List[dict]) -> bool:
        """Runtime-internal metrics (ray_tpu.utils.internal_metrics) —
        flushed by raylets, the GCS itself, workers, and drivers."""
        return self._merge_metric_records(
            self._internal_metrics, worker_id, records, history=self._history
        )

    def internal_metrics(self) -> List[dict]:
        return self._metrics_view(self._internal_metrics)

    def metrics_history(
        self,
        name: Optional[str] = None,
        tags: Optional[dict] = None,
        window_s: Optional[float] = None,
        as_rate: bool = False,
    ) -> List[dict]:
        """Time-series view of the internal-metrics table: matching
        series with [ts, value] ([ts, count, sum] for histograms)
        samples — fine-resolution recent, rolled-up old. Empty when
        retention is disabled (RAY_TPU_METRICS_HISTORY=0)."""
        if self._history is None:
            return []
        return self._history.query(
            name=name, tags=tags, window_s=window_s, as_rate=as_rate
        )

    def active_alerts(self) -> List[dict]:
        """Currently-firing SLO watchdog alerts (empty when disarmed)."""
        if self._watchdog is None:
            return []
        return self._watchdog.active_alerts()

    def _observe_rpc(self, method: str, latency_ms: float) -> None:
        """Per-method RPC accounting hook invoked by RpcServer (only the
        GCS opts in — the raylet's task fast path stays uninstrumented at
        the RPC layer)."""
        imet.GCS_RPC_TOTAL.inc(method=method)
        if method not in ("pubsub_poll", "pubsub_poll2"):
            # Long-poll duration is the subscriber's wait, not GCS work —
            # it would drown the latency histogram.
            imet.GCS_RPC_LATENCY.observe(latency_ms, method=method)

    def stats(self) -> dict:
        """Cluster-wide counters (reference: src/ray/stats/metric.h — the
        aggregate half; per-node gauges ride heartbeats)."""
        with self._lock:
            by_state: Dict[str, int] = {}
            for rec in self._tasks.values():
                by_state[rec["state"]] = by_state.get(rec["state"], 0) + 1
            n_pgs = len(self._pgs)
        actor_states: Dict[str, int] = {}
        store = {"bytes_in_use": 0, "num_objects": 0, "num_spilled": 0}
        objects_indexed = 0
        for sh in self._shards:
            with self._locked(sh):
                for a in sh.actors.values():
                    actor_states[a["state"]] = actor_states.get(a["state"], 0) + 1
                objects_indexed += len(sh.objects)
                for n in sh.nodes.values():
                    if not n["alive"]:
                        continue
                    s = n.get("stats") or {}
                    for k in store:
                        store[k] += int(s.get(k, 0))
        return {
            "tasks": by_state,
            "actors": actor_states,
            "objects_indexed": objects_indexed,
            "store": store,
            "nodes_alive": self._alive_nodes(),
            "placement_groups": n_pgs,
        }

    def node_info(self, node_id: str) -> Optional[dict]:
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            return dict(n) if n else None

    def cluster_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sh in self._shards:
            with self._locked(sh):
                for n in sh.nodes.values():
                    if not n["alive"]:
                        continue
                    for k, v in n["resources"].items():
                        out[k] = out.get(k, 0.0) + v
        return out

    def available_resources(self) -> Dict[str, float]:
        out: Dict[str, float] = {}
        for sh in self._shards:
            with self._locked(sh):
                for n in sh.nodes.values():
                    if not n["alive"]:
                        continue
                    for k, v in n["available"].items():
                        out[k] = out.get(k, 0.0) + v
        return out

    # ------------------------------------------------- scheduling assist
    def pick_node(
        self,
        resources: dict,
        exclude: Optional[List[str]] = None,
        mode: str = "pack",
    ) -> Optional[dict]:
        """Best-fit node for a resource request (the cluster-level half of
        the two-level scheduler; reference: cluster_resource_scheduler.h:44
        + hybrid_scheduling_policy.h:50 / spread policy). mode="pack" picks
        the most-utilized feasible node; mode="spread" round-robins over
        feasible nodes (reference: SPREAD policy — the resource view lags
        by a heartbeat, so a burst of submissions must not all land on the
        momentarily-least-utilized node)."""
        exclude = set(exclude or [])
        candidates: List[Tuple[str, dict]] = []
        for sh in self._shards:
            with self._locked(sh):
                for nid, n in sh.nodes.items():
                    if nid in exclude or not n["alive"] or n.get("draining"):
                        # A draining node is leaving: placing new work
                        # there would lose it at the preemption deadline.
                        continue
                    avail = n["available"]
                    if all(
                        avail.get(k, 0.0) >= v for k, v in resources.items()
                    ):
                        candidates.append(
                            (
                                nid,
                                {
                                    "node_id": nid,
                                    "sock": n["sock"],
                                    "store": n["store"],
                                    "_used": 1.0
                                    - sum(avail.values())
                                    / (sum(n["resources"].values()) or 1.0),
                                },
                            )
                        )
        if not candidates:
            return None
        candidates.sort(key=lambda c: c[0])  # stable order across shard layouts
        feasible = [e for _, e in candidates]
        best = max(feasible, key=lambda e: e["_used"])
        if mode == "spread":
            with self._lock:
                self._spread_rr = getattr(self, "_spread_rr", -1) + 1
                chosen = feasible[self._spread_rr % len(feasible)]
        else:
            chosen = best
        return {k: v for k, v in chosen.items() if k != "_used"}

    def _forgive_own_stall(self, stalled_s: float) -> None:
        """The failure detector must not charge nodes for ITS OWN pause:
        while this process did not run, heartbeats could be neither
        received nor recorded. Seen on a sandboxed one-host TPU machine,
        where a worker's TPU runtime start-up freezes every process of
        the host for ~8 s — longer than the heartbeat timeout — so the
        GCS woke up, found every heartbeat stale and fenced the node,
        killing the very worker that was opening the chip."""
        _log.warning(
            "health loop did not run for %.1fs; crediting it to node heartbeats",
            stalled_s,
        )
        for sh in self._shards:
            with self._locked(sh):
                for n in sh.nodes.values():
                    if n["alive"]:
                        n["last_hb"] += stalled_s

    def _health_loop(self):
        tick = 0
        snap_every = max(1, int(CONFIG.gcs_snapshot_interval_s / 0.1))
        last_check = time.monotonic()
        while not self._stop.wait(0.1):
            self._process_frees()
            tick += 1
            if tick % snap_every == 0:
                self._save_snapshot()
            if tick % 20 == 0:
                # Stranded gangs retry when capacity frees up, not only on
                # node registration.
                with self._lock:
                    stranded = [
                        pg_id
                        for pg_id, pg in self._pgs.items()
                        if pg.get("state") == "RESCHEDULING"
                    ]
                for pg_id in stranded:
                    self._reschedule_gang(pg_id)
                # Node-death-stranded actors get the same cadence: their
                # restart placement can fail transiently (the chosen
                # raylet partitioned/dying at create time), and waiting
                # for the NEXT node registration would strand a named
                # actor forever on a cluster that already has capacity.
                # Off-thread: a create to a dying raylet can block on
                # connect, and the health loop must keep beating (the
                # in-memory _actor_restarting set dedupes overlapping
                # sweeps per actor).
                self._kick_stranded_restarts()
            # Consecutive liveness checks are ~0.1 s apart; a gap of
            # seconds means this thread (or the whole host) stood still.
            now = time.monotonic()
            if now - last_check > 1.0:
                self._forgive_own_stall(now - last_check)
            last_check = now
            dead = []
            lag_records: List[dict] = []
            sample_lag = tick % 10 == 0 and self._history is not None
            for sh in self._shards:
                with self._locked(sh):
                    for nid, n in sh.nodes.items():
                        if not n["alive"]:
                            continue
                        if time.monotonic() - n["last_hb"] > HEARTBEAT_TIMEOUT_S:
                            n["alive"] = False
                            sh.alive_count -= 1
                            dead.append(nid)
                        elif sample_lag:
                            # Heartbeat lag gauge, once per second per alive
                            # node: the signal the heartbeat_lag watchdog
                            # rule (and `ray-tpu top`) watches. Fed through
                            # the normal report path so the table, /metrics,
                            # and history all agree.
                            # Record shape tied to the declared instrument
                            # (name/component/tag come from the catalog so a
                            # rename cannot desynchronize them); hand-built
                            # rather than set on the Gauge because this must
                            # land SYNCHRONOUSLY — an in-process GcsService
                            # has no flusher wired to itself.
                            lag = imet.NODE_HEARTBEAT_LAG
                            lag_records.append(
                                {
                                    "name": lag.name,
                                    "kind": lag.kind,
                                    "value": time.monotonic() - n["last_hb"],
                                    "tags": {
                                        "component": lag.component,
                                        "node_id": "gcs",
                                        lag.tag_keys[0]: nid[:12],
                                    },
                                }
                            )
            if lag_records:
                self.report_internal_metrics("gcs", lag_records)
            for nid in dead:
                self._on_node_death(nid)

    def _on_node_death(self, node_id: str) -> None:
        """Node failure: objects there are lost from the directory; actors
        become restart candidates (reference: gcs_node_manager death
        handling -> gcs_actor_manager restart :548); SLICE_GANG groups with
        a member on the dead node co-fail and reschedule atomically."""
        # Death is also a node_event: supervisors subscribed for drain
        # notices learn about un-noticed failures from the same stream.
        _frec_record("node.dead", (node_id[:12],))
        self.pubsub_publish(
            "node_events",
            {"event": "node_dead", "node_id": node_id, "ts": time.time()},
        )
        self._trigger("node.dead", {"node_id": node_id[:12]}, source="gcs")
        self._publish_node_delta(node_id)
        gangs: List[str] = []
        with self._lock:
            for pg_id, pg in self._pgs.items():
                if (
                    pg["strategy"] == "SLICE_GANG"
                    and node_id in pg["placements"]
                    and pg.get("state") == "CREATED"
                ):
                    pg["state"] = "RESCHEDULING"
                    gangs.append(pg_id)
        if gangs:
            threading.Thread(
                target=lambda: [self._reschedule_gang(p) for p in gangs],
                daemon=True,
            ).start()
        dead_sock = self._node_sock(node_id, alive_only=False)
        with self._lock:
            if dead_sock is not None:
                cli = self._raylet_clients.pop(dead_sock, None)
                if cli is not None:
                    try:
                        cli.close()
                    except Exception:  # lint: swallow-ok(closing a client to a dead node)
                        pass
            # Tasks queued/running on the dead node can never complete there:
            # mark them failed so owners retry or reconstruct (reference:
            # task_manager node-death failure propagation).
            for rec in self._tasks.values():
                if rec.get("node") == node_id and rec["state"] in ("QUEUED", "RUNNING"):
                    rec["state"] = "FAILED"
                    rec["reason"] = "node_died"
                    rec["ts"] = time.time()
        restart_candidates: List[str] = []
        name_drops: List[Tuple[str, dict]] = []
        for sh in self._shards:
            with self._locked(sh):
                for locs in sh.objects.values():
                    locs.discard(node_id)
                for aid, a in sh.actors.items():
                    # RESTARTING is included: a restart whose target node died
                    # between placement and actor_started would otherwise keep
                    # node_id pinned to the corpse — invisible to both the
                    # death sweep (old condition) and the stranded-actor retry
                    # (which only takes node-less records) — a permanent wedge.
                    if a.get("node_id") == node_id and a["state"] in (
                        "ALIVE", "PENDING", "RESTARTING",
                    ):
                        a["state"] = "RESTARTING" if self._can_restart(a) else "DEAD"
                        a["node_id"] = None
                        if a["state"] == "DEAD":
                            a["death_reason"] = f"node {node_id[:8]} died"
                            # Name release touches _named (control lock):
                            # collected here, applied AFTER the shard lock
                            # is released — gcs.state must never be taken
                            # while a shard lock is held.
                            name_drops.append((aid, a))
                        else:
                            restart_candidates.append(aid)
        if name_drops:
            with self._lock:
                for aid, a in name_drops:
                    self._drop_name(aid, a)
        if restart_candidates:
            # Node death must DRIVE restarts: with the node gone there is
            # no raylet left to report actor_died, so without this the
            # actors sit RESTARTING forever and every named-actor lookup
            # wedges (the exact liveness hole a partitioned node's
            # rescheduled actors fall into).
            threading.Thread(
                target=lambda: [
                    self._restart_actor(aid) for aid in restart_candidates
                ],
                daemon=True,
            ).start()

    def _restart_actor(self, actor_id: str) -> None:
        """Re-places and re-creates one RESTARTING actor — the single
        restart implementation behind both node death and raylet-reported
        actor_died. No capacity now -> stays RESTARTING and is retried
        when the next node registers (and on the health loop cadence)."""
        sh = self._actor_shard(actor_id)
        with self._lock:
            if actor_id in self._actor_restarting:
                return
            with self._locked(sh):
                a = sh.actors.get(actor_id)
                if a is None or a["state"] != "RESTARTING" or a.get("node_id"):
                    return
                resources = dict(a["resources"])
                pg_id = a.get("pg_id")
                bundle_index = a.get("bundle_index", -1)
                strategy = a.get("strategy", "DEFAULT")
            self._actor_restarting.add(actor_id)  # CAS: one restarter at a time
        try:
            if pg_id:
                node = self.pick_bundle(pg_id, bundle_index)
            else:
                node = self._place_with_strategy(resources, strategy)
            if node is None:
                # PERMANENTLY unplaceable restarts must FAIL VISIBLY, not
                # wait in RESTARTING forever: the name would stay claimed
                # and get_actor() would wedge with no failure signal. Two
                # terminal cases: a hard-pinned actor (never migrates —
                # only its own node id returning could satisfy it, which
                # a caller cannot count on) and a bundle-pinned actor
                # whose placement group was REMOVED (tombstoned; a PG
                # mid-reschedule stays transient and keeps waiting).
                with self._lock:
                    pg_gone = bool(pg_id) and pg_id not in self._pgs
                terminal_reason = None
                if pg_gone:
                    terminal_reason = (
                        f"placement group {pg_id[:8]} removed; "
                        "bundle-pinned restart impossible"
                    )
                elif not pg_id and _is_hard_affinity(strategy):
                    terminal_reason = (
                        "hard NodeAffinity target unavailable for restart"
                    )
                if terminal_reason is not None:
                    with self._lock:
                        with self._locked(sh):
                            a = sh.actors.get(actor_id)
                            if (
                                a is not None
                                and a["state"] == "RESTARTING"
                                and not a.get("node_id")
                            ):
                                a["state"] = "DEAD"
                                a["death_reason"] = terminal_reason
                                self._drop_name(actor_id, a)
                                sh.wal_append("_actors", actor_id, a)
                    return
                return  # no capacity yet: retried on the next node_added
            with self._locked(sh):
                a = sh.actors.get(actor_id)
                if a is None or a["state"] != "RESTARTING" or a.get("node_id"):
                    return  # raced a raylet-reported restart
                a["node_id"] = node["node_id"]
                spec_blob = a["spec_blob"]
                sh.wal_append("_actors", actor_id, a)
            try:
                self._raylet_call(
                    node["sock"], "create_actor", spec_blob, True,
                    node.get("bundle_index", -1),
                )
            except Exception as e:
                _log.warning("restart of actor %s on %s failed (%r); will retry",
                             actor_id[:8], node["node_id"][:8], e)
                with self._locked(sh):
                    a = sh.actors.get(actor_id)
                    if a is not None and a["state"] == "RESTARTING":
                        # Back to stranded; retried later. Persisted: a
                        # GCS restart restoring the record still pinned
                        # to the failed target would hide it from the
                        # stranded sweep forever.
                        a["node_id"] = None
                        sh.wal_append("_actors", actor_id, a)
                return
            with self._locked(sh):
                a = sh.actors.get(actor_id)
                if a is not None:
                    # Budget accounting AFTER the create landed: one
                    # logical restart = one increment. Charging each
                    # placement ATTEMPT (transient create failures are
                    # retried on a 2 s cadence) would silently exhaust a
                    # finite max_restarts without ever restarting.
                    a["num_restarts"] += 1
                    sh.wal_append("_actors", actor_id, a)
            imet.ACTOR_RESTARTS.inc()
        finally:
            with self._lock:
                self._actor_restarting.discard(actor_id)

    def _kick_stranded_restarts(self) -> None:
        """Spawns one off-thread stranded-actor sweep, only when something
        is actually stranded (a fleet re-registering after a GCS restart
        must not fan out N no-op scan threads; off-thread because a create
        to a dying raylet can block on connect and the caller — the health
        loop or a register_node handler — must not stall)."""
        with self._lock:
            if self._stranded_sweep_inflight:
                # A sweep snapshots the stranded set AFTER this flag is
                # set, so any actor stranded before this kick is either
                # in the running sweep or picked up within one health
                # tick — no need for a second concurrent thread (a mass
                # worker crash would otherwise fan out one per death).
                return
            has_stranded = False
            for sh in self._shards:
                with self._locked(sh):
                    if any(
                        a["state"] == "RESTARTING" and not a.get("node_id")
                        for a in sh.actors.values()
                    ):
                        has_stranded = True
                        break
            if not has_stranded:
                return
            self._stranded_sweep_inflight = True
        threading.Thread(
            target=self._restart_stranded_actors, daemon=True
        ).start()

    def _restart_stranded_actors(self) -> None:
        """Retries node-death-stranded RESTARTING actors (no node yet) —
        invoked when new capacity registers, mirroring the stranded-gang
        retry."""
        try:
            stranded: List[str] = []
            for sh in self._shards:
                with self._locked(sh):
                    stranded.extend(
                        aid
                        for aid, a in sh.actors.items()
                        if a["state"] == "RESTARTING" and not a.get("node_id")
                    )
            for aid in stranded:
                self._restart_actor(aid)
        finally:
            with self._lock:
                self._stranded_sweep_inflight = False

    # ------------------------------------------------------------- actors
    @staticmethod
    def _can_restart(a: dict) -> bool:
        mr = a.get("max_restarts", 0)
        return mr == -1 or a.get("num_restarts", 0) < mr

    def _drop_name(self, actor_id: str, a: dict) -> None:
        """Releases a dead actor's name claim. Caller holds self._lock
        (the name table's lock) and passes the actor record it already
        read — this method must not reach into a shard."""
        key = (a.get("namespace") or "default", a.get("name") or "")
        if a.get("name") and self._named.get(key) == actor_id:
            del self._named[key]
            self._persist_delta("_named", key, None)

    def _place_with_strategy(self, resources: dict, strategy: str) -> Optional[dict]:
        """Strategy-aware node choice shared by first placement AND restart
        (a hard-pinned actor must not silently restart elsewhere). NodeAffinity
        picks by TOTAL capacity — the raylet queues until resources free."""
        from .placement_group import decode_node_affinity

        aff = decode_node_affinity(strategy)
        if aff is not None:
            target_id, soft = aff
            sh = self._node_shard(target_id)
            with self._locked(sh):
                n = sh.nodes.get(target_id)
                if (
                    n is not None
                    and n["alive"]
                    and all(
                        n["resources"].get(k, 0.0) >= v for k, v in resources.items()
                    )
                ):
                    return {"node_id": target_id, "sock": n["sock"], "store": n["store"]}
            if not soft:
                return None
            return self.pick_node(resources)
        return self.pick_node(resources, mode="spread" if strategy == "SPREAD" else "pack")

    def _claim_name(
        self, actor_id: str, name: Optional[str], namespace: Optional[str]
    ) -> Optional[Tuple[str, str]]:
        """Claims the actor name up front so two concurrent registrations
        cannot both pass the uniqueness check while placement runs
        (TOCTOU). Returns the claimed key (None for unnamed actors)."""
        key = (namespace or "default", name) if name else None
        if key is not None:
            with self._lock:
                if key in self._named:
                    raise ActorNameTakenError(f"actor name {name!r} already taken")
                self._named[key] = actor_id
        return key

    def _release_name_claim(
        self, key: Optional[Tuple[str, str]], actor_id: str
    ) -> None:
        if key is None:
            return
        with self._lock:
            if self._named.get(key) == actor_id:
                del self._named[key]

    def _consume_forecast(self, n: int) -> None:
        # Each registration CONSUMES one unit of the pending-work
        # forecast: the forecast predicts launches that haven't arrived
        # yet, so once they do, the pools must stop holding capacity for
        # them (an unconsumed forecast kept refilling — and CPU-starving
        # — the node straight through the launch storm it predicted).
        # Sources are drawn down in sorted order — an arbitrary but
        # deterministic attribution; the pool_hint only ever sees the sum.
        with self._lock:
            fc = dict(self._demand_forecast)
            remaining = int(n)
            for src in sorted(fc):
                if remaining <= 0:
                    break
                fc_n, fc_exp = fc[src]
                if fc_n > 0:
                    take = min(fc_n, remaining)
                    fc[src] = (fc_n - take, fc_exp)
                    remaining -= take
            self._demand_forecast = fc  # atomic whole-dict swap

    def _place_actor(
        self,
        resources: dict,
        pg_id: Optional[str],
        bundle_index: int,
        strategy: str,
    ) -> dict:
        """Pure placement for one actor (no table mutation): bundle pin,
        strategy placement, or the total-capacity overflow fallback.
        Raises typed errors on permanently-unplaceable requests."""
        if pg_id:
            node = self.pick_bundle(pg_id, bundle_index)
            if node is None:
                raise PlacementGroupError(
                    f"placement group {pg_id[:8]} bundle {bundle_index} not available"
                )
            return node
        node = self._place_with_strategy(resources, strategy)
        if node is None and not _is_hard_affinity(strategy):
            # Busy cluster: fall back to a node whose TOTAL capacity
            # fits — the raylet queues the creation until resources
            # free, matching the reference's PENDING_CREATION state
            # (gcs_actor_scheduler queues actors; it never fails
            # them for transient load). Round-robin over the
            # feasible nodes so a burst of overflow actors spreads
            # its queues instead of piling onto one node.
            feasible: List[Tuple[str, dict]] = []
            for sh in self._shards:
                with self._locked(sh):
                    feasible.extend(
                        (nid, {"node_id": nid, "sock": n["sock"], "store": n["store"]})
                        for nid, n in sh.nodes.items()
                        if n["alive"]
                        and not n.get("draining")
                        and all(
                            n["resources"].get(k, 0.0) >= v
                            for k, v in resources.items()
                        )
                    )
            if feasible:
                feasible.sort(key=lambda f: f[0])
                with self._lock:
                    self._overflow_rr = getattr(self, "_overflow_rr", -1) + 1
                    node = feasible[self._overflow_rr % len(feasible)][1]
        if node is None:
            if _is_hard_affinity(strategy):
                raise SchedulingError(
                    f"hard NodeAffinity to {strategy.split(':')[1][:12]} "
                    f"cannot be satisfied for actor requiring {resources}"
                )
            raise SchedulingError(
                f"no node can EVER host actor requiring {resources}"
            )
        return node

    @staticmethod
    def _actor_record(
        spec_blob: bytes,
        node: dict,
        resources: dict,
        max_restarts: int,
        pg_id: Optional[str],
        bundle_index: int,
        strategy: str,
        name: Optional[str],
        namespace: Optional[str],
    ) -> dict:
        return {
            "state": "PENDING",
            "node_id": node["node_id"],
            "spec_blob": spec_blob,
            "resources": dict(resources),
            "max_restarts": max_restarts,
            "num_restarts": 0,
            "pg_id": pg_id,
            "bundle_index": node.get("bundle_index", bundle_index) if pg_id else -1,
            "strategy": strategy,
            "name": name,
            "namespace": namespace or "default",
            "death_reason": "",
        }

    def register_actor(
        self,
        actor_id: str,
        spec_blob: bytes,
        resources: dict,
        max_restarts: int,
        name: Optional[str],
        namespace: Optional[str],
        pg_id: Optional[str] = None,
        bundle_index: int = -1,
        strategy: str = "DEFAULT",
    ) -> dict:
        """Registers + places an actor; returns the chosen node (the caller
        raylet/driver forwards the creation there). Reference:
        gcs_actor_manager.h RegisterActor + gcs_actor_scheduler placement.
        Bundle-pinned actors go to their reserved bundle\'s node."""
        key = self._claim_name(actor_id, name, namespace)
        try:
            node = self._place_actor(resources, pg_id, bundle_index, strategy)
        except BaseException:
            self._release_name_claim(key, actor_id)
            raise
        self._consume_forecast(1)
        record = self._actor_record(
            spec_blob, node, resources, max_restarts, pg_id, bundle_index,
            strategy, name, namespace,
        )
        sh = self._actor_shard(actor_id)
        with self._locked(sh):
            sh.actors[actor_id] = record
            sh.wal_append("_actors", actor_id, record)
        if key is not None:
            with self._lock:
                self._persist_delta("_named", key, actor_id)
        return node

    def create_actors(self, specs: List[dict]) -> List[dict]:
        """Batched register+place+forward: ONE driver RPC registers a
        storm of actors and the GCS itself forwards the creations,
        grouped per target raylet into `create_actor_batch` calls — the
        control plane serializes on O(batches), not O(actors), and the
        driver's old two-round-trip create (register_actor + raylet
        create_actor) collapses to one. The batch is the unit of
        cross-shard routing: after per-spec name claims and placement,
        the records are PARTITIONED BY ACTOR SHARD and committed under
        per-shard locks — one lock acquisition and ONE group-committed
        WAL flush per shard touched, never a global lock. Per-spec
        failures return as the exception OBJECT in that spec's slot
        (re-raised driver-side); one bad spec cannot fail its
        batch-mates. Forward replays are safe: the raylet's create path
        is idempotent (PR 14)."""
        results: List[Optional[dict]] = [None] * len(specs)
        placed: List[Tuple[int, dict, dict, Optional[Tuple[str, str]]]] = []
        for i, s in enumerate(specs):
            key = None
            try:
                key = self._claim_name(s["actor_id"], s.get("name"), s.get("namespace"))
                node = self._place_actor(
                    s.get("resources") or {},
                    s.get("pg_id"),
                    s.get("bundle_index", -1),
                    s.get("strategy", "DEFAULT"),
                )
            except Exception as e:  # noqa: BLE001
                self._release_name_claim(key, s["actor_id"])
                results[i] = {"error": e}
                continue
            placed.append((i, s, node, key))
        if placed:
            self._consume_forecast(len(placed))
        by_shard: Dict[int, List[Tuple[int, dict, dict, Optional[Tuple[str, str]]]]] = {}
        for entry in placed:
            by_shard.setdefault(
                _gsh.shard_index(entry[1]["actor_id"], self._nshards), []
            ).append(entry)
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            wal: List[Tuple[str, Any, Any]] = []
            with self._locked(sh):
                for _, s, node, _ in by_shard[idx]:
                    rec = self._actor_record(
                        s["spec_blob"], node, s.get("resources") or {},
                        s.get("max_restarts", 0), s.get("pg_id"),
                        s.get("bundle_index", -1), s.get("strategy", "DEFAULT"),
                        s.get("name"), s.get("namespace"),
                    )
                    sh.actors[s["actor_id"]] = rec
                    wal.append(("_actors", s["actor_id"], rec))
                sh.wal_append_many(wal)
        named = [(key, s["actor_id"]) for _, s, _, key in placed if key is not None]
        if named:
            with self._lock:
                for key, aid in named:
                    self._persist_delta("_named", key, aid)
        by_sock: Dict[str, List[Tuple[int, bytes, int]]] = {}
        for i, s, node, _ in placed:
            bi = node.get("bundle_index", -1)
            results[i] = {
                "node_id": node["node_id"], "sock": node["sock"], "bundle_index": bi
            }
            by_sock.setdefault(node["sock"], []).append((i, s["spec_blob"], bi))
        for sock, items in by_sock.items():
            try:
                self._raylet_call(
                    sock, "create_actor_batch", [(blob, bi) for _, blob, bi in items]
                )
            except Exception as e:  # noqa: BLE001
                # The chosen raylet is unreachable: surface the failure
                # to the driver (matching the old direct-forward path's
                # raise) and free the registration — a PENDING record
                # pinned to a node that never hosted it would wedge
                # name lookups forever.
                _log.warning(
                    "create_actor_batch forward to %s failed: %r", sock, e
                )
                for i, _, _ in items:
                    aid = specs[i]["actor_id"]
                    sh = self._actor_shard(aid)
                    with self._lock:
                        with self._locked(sh):
                            a = sh.actors.get(aid)
                            if a is not None and a["state"] == "PENDING":
                                a["state"] = "DEAD"
                                a["death_reason"] = f"creation forward failed: {e!r}"
                                a["node_id"] = None
                                self._drop_name(aid, a)
                                sh.wal_append("_actors", aid, a)
                    results[i] = {"error": e}
        return results

    def actor_started(
        self, actor_id: str, node_id: str, epoch: Optional[int] = None
    ) -> bool:
        # Fenced: a zombie reporting "started" for an actor the GCS has
        # already rescheduled elsewhere would repoint the record at the
        # duplicate instance.
        self._reject_stale_node(node_id, epoch, "actor_started")
        sh = self._actor_shard(actor_id)
        with self._locked(sh):
            a = sh.actors.get(actor_id)
            if a:
                if a["state"] == "DEAD" or a.get("node_id") not in (None, node_id):
                    # The record is terminally dead, or pinned to another
                    # node (an ambiguously-delivered create was retried
                    # elsewhere while this instance was still launching):
                    # this instance is a DUPLICATE. False tells the
                    # reporting raylet to kill it locally — the singleton
                    # invariant the fence protects, minus the partition.
                    return False
                a["state"] = "ALIVE"
                a["node_id"] = node_id
                sh.wal_append("_actors", actor_id, a)
        return True

    def actor_started_batch(
        self, node_id: str, actor_ids: List[str], epoch: Optional[int] = None
    ) -> Dict[str, bool]:
        """Coalesced actor_started reports from one raylet's launch
        storm: the fence is judged ONCE per batch (all entries carry the
        same incarnation's epoch) and the per-actor verdicts follow the
        single-report semantics — False tells the raylet that instance
        is a duplicate to kill locally."""
        self._reject_stale_node(node_id, epoch, "actor_started_batch")
        out: Dict[str, bool] = {}
        by_shard: Dict[int, List[str]] = {}
        for actor_id in actor_ids:
            by_shard.setdefault(
                _gsh.shard_index(actor_id, self._nshards), []
            ).append(actor_id)
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            wal: List[Tuple[str, Any, Any]] = []
            with self._locked(sh):
                for actor_id in by_shard[idx]:
                    a = sh.actors.get(actor_id)
                    if a and (
                        a["state"] == "DEAD" or a.get("node_id") not in (None, node_id)
                    ):
                        out[actor_id] = False
                        continue
                    if a:
                        a["state"] = "ALIVE"
                        a["node_id"] = node_id
                        wal.append(("_actors", actor_id, a))
                    out[actor_id] = True
                if wal:
                    sh.wal_append_many(wal)
        return out

    def actor_died(
        self,
        actor_id: str,
        reason: str,
        no_restart: bool = False,
        node_id: Optional[str] = None,
        epoch: Optional[int] = None,
    ) -> dict:
        """Returns the restart decision: {restart: bool}; when True the
        GCS re-places and re-creates the actor itself, off-thread, via
        _restart_actor (reference: actor state machine,
        design_docs/actor_states.rst).
        Raylet reporters carry (node_id, epoch): a fenced incarnation's
        death report must not touch an actor record — the GCS already
        rescheduled it, and flipping the healthy successor to RESTARTING
        here would be exactly the split-brain hijack the fence blocks on
        every other mutation path."""
        if node_id is not None:
            self._reject_stale_node(node_id, epoch, "actor_died")
        sh = self._actor_shard(actor_id)
        # Control lock first (name drop needs it), THEN the actor's shard
        # — the one legal nesting order.
        with self._lock, self._locked(sh):
            a = sh.actors.get(actor_id)
            if a is None:
                return {"restart": False}
            if node_id is not None and a.get("node_id") not in (None, node_id):
                # The record moved (restarted elsewhere) since this
                # reporter hosted it: a stale report about a bygone
                # incarnation, not a death of the current one.
                return {"restart": False}
            if no_restart or not self._can_restart(a):
                a["state"] = "DEAD"
                a["death_reason"] = reason
                a["node_id"] = None
                self._drop_name(actor_id, a)
                sh.wal_append("_actors", actor_id, a)
                return {"restart": False}
            # Flip to RESTARTING (unpinned) and hand off to the single
            # place-pin-create-charge implementation (_restart_actor) —
            # the same path node death uses. It charges num_restarts only
            # once the create lands (placement/create retries of one
            # death cost one budget unit, not one per attempt); a plain
            # no-capacity outcome WAITS in RESTARTING (retried on every
            # node_added + the health loop's cadence), while PERMANENTLY
            # unplaceable restarts — hard NodeAffinity target gone, or
            # the pinning placement group removed — go DEAD with the
            # name dropped so callers get a failure signal, not a wedge.
            a["state"] = "RESTARTING"
            a["node_id"] = None
            sh.wal_append("_actors", actor_id, a)
        self._kick_stranded_restarts()
        return {"restart": True}

    def get_actor(self, actor_id: str) -> Optional[dict]:
        sh = self._actor_shard(actor_id)
        with self._locked(sh):
            a = sh.actors.get(actor_id)
            if a is None:
                return None
            out = {k: v for k, v in a.items() if k != "spec_blob"}
            node_id = a["node_id"]
        # Sock resolve on the NODE's shard happens after the actor shard
        # is released — cross-shard reads are sequential, never nested.
        out["sock"] = self._node_sock(node_id, alive_only=False) if node_id else None
        return out

    def lookup_named_actor(self, name: str, namespace: Optional[str]) -> Optional[str]:
        with self._lock:
            return self._named.get((namespace or "default", name))

    # ------------------------------------------------------------ objects
    def add_object_location(self, oid_hex: str, node_id: str) -> bool:
        sh = self._object_shard(oid_hex)
        with self._locked(sh):
            sh.objects.setdefault(oid_hex, set()).add(node_id)
        return True

    def remove_object_location(
        self, oid_hex: str, node_id: str, epoch: Optional[int] = None
    ) -> bool:
        self._reject_stale_node(node_id, epoch, "remove_object_location")
        sh = self._object_shard(oid_hex)
        with self._locked(sh):
            locs = sh.objects.get(oid_hex)
            if locs is not None:
                locs.discard(node_id)
                if not locs:
                    del sh.objects[oid_hex]
        return True

    def get_object_locations(self, oid_hex: str) -> List[dict]:
        sh = self._object_shard(oid_hex)
        with self._locked(sh):
            locs = list(sh.objects.get(oid_hex, ()))
        view = self._nodes_view_for(locs)
        return [
            {"node_id": nid, "sock": view[nid]["sock"], "store": view[nid]["store"]}
            for nid in locs
            if nid in view and view[nid]["alive"]
        ]

    def get_object_locations_batch(self, oid_hexes: List[str]) -> Dict[str, List[dict]]:
        """One round trip for a raylet's whole wait set."""
        found: Dict[str, List[str]] = {}
        by_shard: Dict[int, List[str]] = {}
        for h in oid_hexes:
            by_shard.setdefault(_gsh.shard_index(h, self._nshards), []).append(h)
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            with self._locked(sh):
                for h in by_shard[idx]:
                    locs = sh.objects.get(h)
                    if locs:
                        found[h] = list(locs)
        view = self._nodes_view_for(
            sorted({nid for locs in found.values() for nid in locs})
        )
        return {
            h: [
                {"node_id": nid, "sock": view[nid]["sock"]}
                for nid in locs
                if nid in view and view[nid]["alive"]
            ]
            for h, locs in found.items()
        }

    def free_objects(self, oid_hexes: List[str]) -> bool:
        """The owner dropped its last reference. The free is executed after
        a short grace period (by the health loop) so in-flight borrow
        registrations land first, and is deferred further while any borrower
        still holds the ref (reference: reference_count.h:64 owner release +
        WaitForRefRemoved borrower protocol)."""
        with self._lock:
            self._free_queue.append((time.monotonic(), list(oid_hexes)))
        return True

    def flush_frees(self) -> bool:
        """Prompt free processing for a raylet under pool pressure. A small
        grace remains: other processes' borrow registrations flush on a
        ~20 ms cadence and must land before their objects' frees execute."""
        self._process_frees(grace=0.05)
        return True

    def _process_frees(self, grace: float = 0.1) -> None:
        now = time.monotonic()
        with self._lock:
            ready = [b for ts, b in self._free_queue if now - ts >= grace]
            self._free_queue = [e for e in self._free_queue if now - e[0] < grace]
        if not ready:
            return
        by_shard: Dict[int, List[str]] = {}
        for batch in ready:
            for h in batch:
                by_shard.setdefault(_gsh.shard_index(h, self._nshards), []).append(h)
        freed: List[Tuple[str, List[str]]] = []
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            with self._locked(sh):
                for h in by_shard[idx]:
                    if sh.borrows.get(h, 0) > 0:
                        sh.deferred_free.add(h)
                    else:
                        self._release_locked(sh, h, freed)
        self._delete_on_nodes(self._socks_for_frees(freed))

    def _release_locked(
        self, sh: _gsh.GcsShard, h: str, freed: List[Tuple[str, List[str]]]
    ) -> None:
        """Tombstones h and collects (h, locations) for deletion — the
        owning shard's lock is held; sock resolution (a NODE-shard read)
        happens after it is released, never nested under it."""
        sh.freed[h] = True
        cap = max(1024, 200_000 // self._nshards)
        while len(sh.freed) > cap:
            sh.freed.popitem(last=False)
        locs = sh.objects.pop(h, None)
        if locs:
            freed.append((h, list(locs)))

    def _socks_for_frees(
        self, freed: List[Tuple[str, List[str]]]
    ) -> Dict[str, List[str]]:
        """(object, locations) pairs -> {sock: [objects]} for the delete
        fan-out, keeping only currently-alive copies."""
        if not freed:
            return {}
        view = self._nodes_view_for(
            sorted({nid for _, locs in freed for nid in locs})
        )
        by_node: Dict[str, List[str]] = {}
        for h, locs in freed:
            for nid in locs:
                v = view.get(nid)
                if v is not None and v["alive"]:
                    by_node.setdefault(v["sock"], []).append(h)
        return by_node

    def _delete_on_nodes(self, by_node: Dict[str, List[str]]) -> None:
        for sock, hs in by_node.items():
            try:
                self._raylet_call(sock, "delete_objects", hs)
            except Exception:  # lint: swallow-ok(node going away frees its pool anyway)
                pass

    def update_borrows(self, deltas: Dict[str, int]) -> bool:
        """Batched borrow-count adjustments from non-owner processes."""
        by_shard: Dict[int, List[Tuple[str, int]]] = {}
        for h, d in deltas.items():
            by_shard.setdefault(
                _gsh.shard_index(h, self._nshards), []
            ).append((h, d))
        freed: List[Tuple[str, List[str]]] = []
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            with self._locked(sh):
                for h, d in by_shard[idx]:
                    c = sh.borrows.get(h, 0) + d
                    if c > 0:
                        sh.borrows[h] = c
                        continue
                    sh.borrows.pop(h, None)
                    if h in sh.deferred_free:
                        sh.deferred_free.discard(h)
                        self._release_locked(sh, h, freed)
        self._delete_on_nodes(self._socks_for_frees(freed))
        return True

    # -------------------------------------------------------------- tasks
    def node_sync(
        self,
        node_id: str,
        sealed: List[str],
        events: List[dict],
        epoch: Optional[int] = None,
    ) -> bool:
        """Batched raylet -> GCS sync: object locations + task state events
        (reference: task_event_buffer.h batching + object directory adds).
        Epoch-fenced: a dead-marked/stale incarnation must not index
        objects or mutate task state (its copies are already gone from
        the directory; re-adding them would hand readers dangling
        locations)."""
        self._reject_stale_node(node_id, epoch, "node_sync")
        stale: List[str] = []
        by_shard: Dict[int, List[str]] = {}
        for h in sealed:
            by_shard.setdefault(_gsh.shard_index(h, self._nshards), []).append(h)
        for idx in sorted(by_shard):
            sh = self._shards[idx]
            with self._locked(sh):
                for h in by_shard[idx]:
                    if h in sh.freed:
                        # The owner freed this object before it sealed
                        # (fire-and-forget task): delete the late copy
                        # instead of indexing it.
                        stale.append(h)
                        continue
                    sh.objects.setdefault(h, set()).add(node_id)
        node_sock = self._node_sock(node_id) if stale else None
        with self._lock:
            for evt in events:
                tid = evt["task_id"]
                rec = self._tasks.get(tid)
                if rec is None:
                    rec = {"task_id": tid, "state": "QUEUED", "name": "", "ts": 0.0}
                    self._tasks[tid] = rec
                    # Evict oldest TERMINAL records only: evicting a live
                    # task would make its owner misread "unknown" as lost
                    # and double-execute it.
                    while len(self._tasks) > TASK_TABLE_CAP:
                        old_tid, old = self._tasks.popitem(last=False)
                        if old["state"] not in ("FINISHED", "FAILED"):
                            self._tasks[old_tid] = old
                            self._tasks.move_to_end(old_tid, last=False)
                            break
                # Batches can interleave across nodes; never let a stale
                # RUNNING overwrite a terminal state from the same attempt,
                # but a retry (QUEUED with higher attempt) resets it.
                if evt["state"] == "QUEUED" or rec["state"] not in ("FINISHED", "FAILED"):
                    rec["state"] = evt["state"]
                    rec["node"] = node_id
                    rec["ts"] = evt.get("ts", time.time())
                    if evt.get("name"):
                        rec["name"] = evt["name"]
                    if evt.get("reason"):
                        rec["reason"] = evt["reason"]
                    if evt.get("retry"):
                        rec["retries"] = evt["retry"]
                    # Bounded transition history: feeds the timeline export
                    # (reference: task events backing `ray timeline`).
                    hist = rec.setdefault("history", [])
                    hist.append((evt["state"], rec["ts"], node_id))
                    del hist[:-8]
        if stale and node_sock:
            try:
                self._raylet_call(node_sock, "delete_objects", stale)
            except Exception:  # lint: swallow-ok(stale-object GC retried by the monitor)
                pass
        return True

    @staticmethod
    def _task_copy(rec: dict) -> dict:
        # History is the one nested MUTABLE value: deep-copy it under the
        # lock or the RPC layer pickles it while node_sync appends.
        out = dict(rec)
        if "history" in out:
            out["history"] = list(out["history"])
        return out

    def get_task_states(self, task_ids: List[str]) -> Dict[str, dict]:
        with self._lock:
            return {
                tid: self._task_copy(self._tasks[tid])
                for tid in task_ids
                if tid in self._tasks
            }

    def list_tasks(self, limit: int = 1000) -> List[dict]:
        with self._lock:
            out = [self._task_copy(rec) for rec in self._tasks.values()]
        return out[-limit:]

    # --------------------------------------------------------------- kv
    def kv_put(self, key: str, value: bytes) -> bool:
        with self._lock:
            self._kv[key] = value
            self._persist_delta("_kv", key, value)
        return True

    def kv_get(self, key: str) -> Optional[bytes]:
        with self._lock:
            return self._kv.get(key)

    def kv_del(self, key: str) -> bool:
        with self._lock:
            hit = self._kv.pop(key, None) is not None
            if hit:
                self._persist_delta("_kv", key, None)
            return hit

    def kv_keys(self, prefix: str = "") -> List[str]:
        with self._lock:
            return [k for k in self._kv if k.startswith(prefix)]

    # ----------------------------------------------------------- pubsub
    # General-purpose channels (reference: src/ray/pubsub/publisher.h
    # long-poll publisher + subscriber.h): per-channel bounded sequence
    # log; subscribers long-poll for entries after their cursor and get
    # woken the moment something publishes. Lazy channel creation, no
    # registration handshake — a subscriber is just a cursor.
    _PUBSUB_RETAIN = 1024

    def pubsub_publish(self, channel: str, message: Any) -> int:
        with self._pubsub_cv:
            log = self._pubsub.setdefault(channel, [])
            seq = (log[-1][0] + 1) if log else 1
            log.append((seq, message))
            self._pubsub_total += 1
            if len(log) > self._PUBSUB_RETAIN:
                trimmed = len(log) - self._PUBSUB_RETAIN
                del log[:trimmed]
                self._pubsub_total -= trimmed
            backlog = self._pubsub_total  # O(1): gauge off the lock's path
            self._pubsub_cv.notify_all()
        imet.GCS_PUBSUB_BACKLOG.set(backlog)
        return seq

    def pubsub_poll(
        self, channel: str, after_seq: int = 0, timeout: float = 10.0
    ) -> List[Tuple[int, Any]]:
        """Entries with seq > after_seq; blocks up to `timeout` when there
        are none yet (the long-poll half of the reference's protocol)."""
        deadline = time.monotonic() + max(0.0, timeout)
        with self._pubsub_cv:
            while True:
                log = self._pubsub.get(channel, [])
                out = [(s, m) for s, m in log if s > after_seq]
                if out:
                    return out
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return []
                self._pubsub_cv.wait(timeout=min(remaining, 1.0))

    def pubsub_poll2(
        self, channel: str, after_seq: int = 0, timeout: float = 10.0
    ) -> dict:
        """Gap-aware delta poll: `{"entries": [(seq, msg), ...], "gap": bool}`.
        `gap=True` means the subscriber's cursor fell behind the retention
        ring — entries after its cursor were already trimmed, so an
        incremental apply would silently miss deltas; the subscriber must
        resync from a snapshot (`node_table_snapshot` for the node_table
        channel) and resume from the seq the snapshot reports. A gap
        returns IMMEDIATELY without long-polling: the caller is about to
        do a full resync, and making it wait for fresh deltas first is
        pure added lag. `pubsub_poll` keeps the old contract (silent
        trim) for existing subscribers."""
        deadline = time.monotonic() + max(0.0, timeout)
        gap = False
        out: List[Tuple[int, Any]] = []
        with self._pubsub_cv:
            while True:
                log = self._pubsub.get(channel, [])
                if after_seq > 0 and log and log[0][0] > after_seq + 1:
                    gap = True
                    break
                out = [(s, m) for s, m in log if s > after_seq]
                if out:
                    break
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    break
                self._pubsub_cv.wait(timeout=min(remaining, 1.0))
        if gap:
            imet.GCS_PUBSUB_RESYNCS.inc(channel=channel)
        elif out:
            imet.GCS_PUBSUB_DELTAS.inc(len(out), channel=channel)
        return {"entries": out, "gap": gap}

    # ------------------------------------------------- node-table deltas
    # The `node_table` channel replaces "poll list_nodes() every few
    # seconds" for membership-tracking subscribers: each membership or
    # lifecycle-state change publishes ONE slim per-node diff, and
    # subscribers mirror the table locally by applying diffs in seq
    # order. Deliberately EXCLUDED from the diff: `available` and
    # `stats`, which change on every heartbeat — publishing those would
    # turn the delta stream back into the full-snapshot firehose it
    # replaces. Subscribers that need resource freshness read it from
    # the snapshot they resync from, or query list_nodes directly.

    @staticmethod
    def _slim_node(nid: str, n: dict, epoch: int) -> dict:
        return {
            "op": "upsert",
            "NodeID": nid,
            "Alive": bool(n["alive"]),
            "Draining": bool(n.get("draining")),
            "Fenced": bool(n.get("fenced")),
            "Epoch": epoch,
            "State": "DEAD" if not n["alive"] else (
                "DRAINING" if n.get("draining") else "ALIVE"
            ),
            "Labels": dict(n.get("labels") or {}),
            "Resources": dict(n["resources"]),
            "sock": n["sock"],
            "store": n["store"],
        }

    def _publish_node_delta(self, node_id: str) -> None:
        """Publishes the node's current slim row to `node_table`. Called
        AFTER the mutation's shard lock is released (pubsub takes its own
        condition lock; holding a shard lock across it would nest shard ->
        pubsub under the fan-in's hottest locks)."""
        sh = self._node_shard(node_id)
        with self._locked(sh):
            n = sh.nodes.get(node_id)
            if n is None:
                return
            row = self._slim_node(node_id, n, sh.node_epochs.get(node_id, 0))
        try:
            self.pubsub_publish("node_table", row)
        except Exception as e:  # lint: swallow-ok(subscribers resync from snapshot on gap)
            _log.warning("node_table publish for %s failed: %r", node_id[:12], e)

    def node_table_snapshot(self) -> dict:
        """Resync point for node_table subscribers that fell behind the
        retention ring: the full slim table plus the channel seq to
        resume delta-polling from. The seq is captured BEFORE the table
        is read — a delta published mid-build is then re-delivered and
        re-applied (upserts are idempotent), never lost."""
        with self._pubsub_cv:
            log = self._pubsub.get("node_table", [])
            seq = log[-1][0] if log else 0
        nodes: List[dict] = []
        for sh in self._shards:
            with self._locked(sh):
                nodes.extend(
                    self._slim_node(nid, n, sh.node_epochs.get(nid, 0))
                    for nid, n in sh.nodes.items()
                )
        imet.GCS_PUBSUB_RESYNCS.inc(channel="node_table.snapshot")
        return {"seq": seq, "nodes": nodes}

    # ------------------------------------------------------ error reports
    # Cluster error table (reference: the error pubsub surfacing uncaught
    # worker exceptions at the driver, _private/utils.py publish_error_to
    # _driver + util/state list_cluster_events): workers report uncaught
    # task exceptions, raylets report worker crashes (with the dying
    # process's captured-output tail). Bounded ring + `error_reports`
    # pubsub channel; `state.cluster_errors()` / `ray-tpu status` read it.
    _ERRORS_RETAIN = 256

    def report_error(self, payload: dict) -> bool:
        if not isinstance(payload, dict):
            return False
        payload = dict(payload)
        payload.setdefault("ts", time.time())
        with self._lock:
            self._errors.append(payload)
            del self._errors[: -self._ERRORS_RETAIN]
        imet.ERROR_REPORTS.inc()
        try:
            self.pubsub_publish("error_reports", payload)
        except Exception as e:
            _log.warning("error-report publish failed (subscribers missed %r): %r",
                         payload.get("type"), e)
        return True

    def cluster_errors(self, limit: int = 100) -> List[dict]:
        with self._lock:
            return list(self._errors)[-limit:]

    # ------------------------------------------------------ placement grp
    def _plan_bundles(
        self, bundles: List[dict], strategy: str, banned: Set[str]
    ) -> List[str]:
        """Pure placement planning against the current resource view
        (reference: bundle_scheduling_policy.h PACK/SPREAD/STRICT_PACK/
        STRICT_SPREAD + the TPU-native SLICE_GANG)."""
        if strategy == "SLICE_GANG":
            return self._plan_slice_gang(bundles, banned)
        placements: List[str] = []
        avail: Dict[str, dict] = {}
        for sh in self._shards:
            with self._locked(sh):
                avail.update(
                    (nid, dict(n["available"]))
                    for nid, n in sh.nodes.items()
                    if n["alive"] and nid not in banned and not n.get("draining")
                )
        order = sorted(avail, key=lambda nid: -sum(avail[nid].values()))

        def fits(nid, b):
            return all(avail[nid].get(k, 0.0) >= v for k, v in b.items())

        def take(nid, b):
            for k, v in b.items():
                avail[nid][k] = avail[nid].get(k, 0.0) - v

        for i, bundle in enumerate(bundles):
            chosen = None
            if strategy in ("PACK", "STRICT_PACK"):
                pool = placements[:1] if (strategy == "STRICT_PACK" and placements) else order
                for nid in pool if placements else order:
                    if fits(nid, bundle):
                        chosen = nid
                        break
                if chosen is None and strategy == "PACK":
                    for nid in order:
                        if fits(nid, bundle):
                            chosen = nid
                            break
            elif strategy in ("SPREAD", "STRICT_SPREAD"):
                used = set(placements)
                candidates = [n for n in order if n not in used] or (
                    order if strategy == "SPREAD" else []
                )
                for nid in candidates:
                    if fits(nid, bundle):
                        chosen = nid
                        break
            if chosen is None:
                raise PlacementGroupError(
                    f"cannot place bundle {i} ({bundle}) with strategy {strategy}"
                )
            take(chosen, bundle)
            placements.append(chosen)
        return placements

    def _plan_slice_gang(self, bundles: List[dict], banned: Set[str]) -> List[str]:
        """SLICE_GANG: all bundles land on hosts of ONE named TPU slice, or
        the gang fails — an SPMD program must see its full mesh (reference:
        the TPU-{pod}-head idiom at _private/accelerators/tpu.py:334-397 and
        bundle_scheduling_policy.h:82-106, redesigned as a first-class
        atomic policy over registered TpuSliceSpecs)."""
        slices: Dict[str, List[Tuple[int, str, dict]]] = {}
        for sh in self._shards:
            with self._locked(sh):
                for nid, n in sh.nodes.items():
                    if not n["alive"] or nid in banned or n.get("draining"):
                        continue
                    sl = (n.get("labels") or {}).get("slice_name")
                    if not sl:
                        continue
                    widx = int((n.get("labels") or {}).get("worker_index", 0))
                    slices.setdefault(sl, []).append((widx, nid, dict(n["available"])))
        # Smallest slice that fits first: don't fragment big slices.
        for sl in sorted(slices, key=lambda s: (len(slices[s]), s)):
            hosts = sorted(slices[sl])
            avail = {nid: dict(av) for _, nid, av in hosts}
            order = [nid for _, nid, _ in hosts]
            placements: List[str] = []
            for bundle in bundles:
                chosen = None
                for j in range(len(order)):
                    nid = order[(len(placements) + j) % len(order)]
                    if all(avail[nid].get(k, 0.0) >= v for k, v in bundle.items()):
                        chosen = nid
                        break
                if chosen is None:
                    break
                for k, v in bundle.items():
                    avail[chosen][k] = avail[chosen].get(k, 0.0) - v
                placements.append(chosen)
            if len(placements) == len(bundles):
                return placements
        raise PlacementGroupError(
            f"no registered TPU slice can host all {len(bundles)} bundles atomically"
        )

    def _reschedule_gang(self, pg_id: str) -> None:
        """A gang member died: release every sibling lease (bundle-pinned
        work fails fast on its raylet) and re-place the WHOLE gang on
        another slice (no partial restarts)."""
        with self._lock:
            pg = self._pgs.get(pg_id)
            if pg is None or pg.get("state") != "RESCHEDULING":
                return
            pg["state"] = "REPLANNING"  # CAS: one rescheduler at a time
            placements = list(pg["placements"])
            bundles = pg["bundles"]
        for i, nid in enumerate(placements):
            sock = self._node_sock(nid)
            if sock:
                try:
                    self._raylet_call(sock, "release_bundle", pg_id, i)
                except Exception:  # lint: swallow-ok(bundle release on a dead/gone node)
                    pass
        try:
            self.create_placement_group(pg_id, bundles, "SLICE_GANG")
        except Exception:
            with self._lock:
                pg = self._pgs.get(pg_id)
                if pg is not None and pg.get("state") == "REPLANNING":
                    pg["state"] = "RESCHEDULING"  # retried on next register

    def create_placement_group(self, pg_id: str, bundles: List[dict], strategy: str) -> dict:
        """Plans placements, then leases each bundle on its raylet — the
        raylet debits its own free pool, so the reservation is durable
        across heartbeats (reference: gcs_placement_group_scheduler.h:283
        two-phase PREPARE/COMMIT; placement_group_resource_manager.h).
        All-or-nothing: any failed lease rolls the gang back."""
        with self._lock:
            if pg_id in self._removed_pgs:
                raise PlacementGroupError(f"placement group {pg_id[:8]} was removed")
        banned: Set[str] = set()
        last_err: Optional[str] = None
        for _ in range(4):  # replanning rounds for stale-view refusals
            placements = self._plan_bundles(bundles, strategy, banned)
            reserved: List[Tuple[str, int]] = []
            failed_node = None
            for i, (nid, bundle) in enumerate(zip(placements, bundles)):
                sock = self._node_sock(nid)
                ok = False
                if sock is not None:
                    try:
                        ok = self._raylet_call(sock, "reserve_bundle", pg_id, i, bundle)
                    except Exception:
                        ok = False
                if not ok:
                    failed_node = nid
                    break
                reserved.append((nid, i))
            if failed_node is None:
                # Refresh the view from each leasing raylet (authoritative,
                # post-reserve) rather than debiting locally — a concurrent
                # heartbeat that already reflects the lease would otherwise
                # be debited twice.
                for nid in set(placements):
                    sock = self._node_sock(nid, alive_only=False)
                    if sock:
                        try:
                            _, avail = self._raylet_call(sock, "node_resources")
                            nsh = self._node_shard(nid)
                            with self._locked(nsh):
                                node = nsh.nodes.get(nid)
                                if node:
                                    node["available"] = dict(avail)
                        except Exception:  # lint: swallow-ok(advisory resource-view refresh)
                            pass
                with self._lock:
                    removed = pg_id in self._removed_pgs
                    if not removed:
                        self._pgs[pg_id] = {
                            "bundles": bundles,
                            "strategy": strategy,
                            "placements": placements,
                            "state": "CREATED",
                            "rr": 0,
                        }
                        self._persist_delta("_pgs", pg_id, self._pgs[pg_id])
                if removed:
                    # remove_placement_group raced the (re)creation: undo
                    # the fresh leases instead of leaking them ownerlessly.
                    for nid, i in reserved:
                        sock = self._node_sock(nid, alive_only=False)
                        if sock:
                            try:
                                self._raylet_call(sock, "release_bundle", pg_id, i)
                            except Exception:  # lint: swallow-ok(bundle release on a dead/gone node)
                                pass
                    raise PlacementGroupError(f"placement group {pg_id[:8]} was removed")
                return {"placements": placements}
            # Roll back partial gang, ban the refusing node, replan.
            for nid, i in reserved:
                sock = self._node_sock(nid, alive_only=False)
                if sock:
                    try:
                        self._raylet_call(sock, "release_bundle", pg_id, i)
                    except Exception:  # lint: swallow-ok(bundle release on a dead/gone node)
                        pass
            banned.add(failed_node)
            last_err = f"node {failed_node[:8]} refused bundle lease"
        raise PlacementGroupError(f"placement group {pg_id[:8]} creation failed: {last_err}")

    def _raylet_call(self, sock: str, method: str, *args):
        """Cached per-raylet client for control-plane calls (bundle
        lease/release, view refresh) — never on the task fast path. Entries
        are evicted when their node dies (_on_node_death), so cache access
        holds _lock; only the blocking connect stays outside it."""
        from .rpc import RpcClient

        with self._lock:
            cli = self._raylet_clients.get(sock)
        if cli is None:
            fresh = RpcClient(sock)
            with self._lock:
                cli = self._raylet_clients.setdefault(sock, fresh)
            if cli is not fresh:
                fresh.close()  # lost the insert race
        return cli.call(method, *args)

    def remove_placement_group(self, pg_id: str) -> bool:
        with self._lock:
            pg = self._pgs.pop(pg_id, None)
            if pg is not None:
                self._persist_delta("_pgs", pg_id, None)
            # Tombstone: an in-flight gang reschedule must not resurrect a
            # removed PG (and re-lease its bundles ownerlessly).
            self._removed_pgs[pg_id] = True
            while len(self._removed_pgs) > 10_000:
                self._removed_pgs.popitem(last=False)
        if pg:
            for i, (nid, bundle) in enumerate(zip(pg["placements"], pg["bundles"])):
                nsh = self._node_shard(nid)
                with self._locked(nsh):
                    n = nsh.nodes.get(nid)
                    sock = n["sock"] if n and n["alive"] else None
                    if n:
                        for k, v in bundle.items():
                            n["available"][k] = min(
                                n["resources"].get(k, 0.0), n["available"].get(k, 0.0) + v
                            )
                if sock:
                    try:
                        self._raylet_call(sock, "release_bundle", pg_id, i)
                    except Exception:  # lint: swallow-ok(bundle release on a dead/gone node)
                        pass
        return True

    def pick_bundle(self, pg_id: str, bundle_index: int) -> Optional[dict]:
        """Resolves a (pg, bundle) to its host node for bundle-pinned
        submission; bundle_index -1 round-robins across the gang."""
        with self._lock:
            pg = self._pgs.get(pg_id)
            if pg is None:
                return None
            if pg.get("state") not in (None, "CREATED"):
                return None  # gang rescheduling: fail fast, no partial use
            if bundle_index < 0:
                bundle_index = pg["rr"] % len(pg["placements"])
                pg["rr"] += 1
            if bundle_index >= len(pg["placements"]):
                return None
            nid = pg["placements"][bundle_index]
            # Control -> node-shard nesting (the legal order): the rr
            # cursor above must stay consistent with the liveness check.
            nsh = self._node_shard(nid)
            with self._locked(nsh):
                n = nsh.nodes.get(nid)
                if n is None or not n["alive"]:
                    return None
                return {
                    "node_id": nid,
                    "sock": n["sock"],
                    "store": n["store"],
                    "bundle_index": bundle_index,
                }

    def register_pending_placement_group(
        self, pg_id: str, bundles: List[dict], strategy: str
    ) -> bool:
        """Records a PG the cluster cannot place YET (reference: the
        PENDING state of gcs_placement_group_manager.h:230 — creation is
        asynchronous; the autoscaler watches pending groups and provisions
        capacity for them)."""
        with self._lock:
            if pg_id in self._removed_pgs or pg_id in self._pgs:
                return False
            self._pgs[pg_id] = {
                "bundles": bundles,
                "strategy": strategy,
                "placements": [],
                "state": "PENDING",
                "rr": 0,
            }
            self._persist_delta("_pgs", pg_id, self._pgs[pg_id])
        return True

    def retry_pending_placement_group(self, pg_id: str) -> Optional[dict]:
        """Attempts to place a PENDING group (invoked by ready() pollers —
        new capacity may have arrived). One attempt in flight per group."""
        with self._lock:
            pg = self._pgs.get(pg_id)
            if pg is None:
                return None
            if pg.get("state") == "CREATED":
                return {"placements": pg["placements"]}
            if pg.get("state") != "PENDING" or pg_id in self._pg_creating:
                return None
            self._pg_creating.add(pg_id)
            bundles, strategy = pg["bundles"], pg["strategy"]
        try:
            with self._lock:
                del self._pgs[pg_id]  # create() re-registers on success
            try:
                return self.create_placement_group(pg_id, bundles, strategy)
            except RuntimeError:
                with self._lock:
                    if pg_id not in self._removed_pgs and pg_id not in self._pgs:
                        self._pgs[pg_id] = {
                            "bundles": bundles,
                            "strategy": strategy,
                            "placements": [],
                            "state": "PENDING",
                            "rr": 0,
                        }
                return None
        finally:
            with self._lock:
                self._pg_creating.discard(pg_id)

    def placement_group_table(self) -> Dict[str, dict]:
        with self._lock:
            return {k: dict(v) for k, v in self._pgs.items()}

    def get_placement_group(self, pg_id: str) -> Optional[dict]:
        with self._lock:
            pg = self._pgs.get(pg_id)
            return dict(pg) if pg else None

    # ----------------------------------------------------------- control
    def ping(self) -> str:
        return "pong"

    def flight_dump(self) -> Optional[str]:
        """Dumps the GCS process's flight ring (node.dead / node.fence /
        node.added and friends) so partition post-mortems can order the
        membership transitions exactly."""
        from ..observability import flight_recorder as _frec

        return _frec.dump(reason="gcs flight_dump rpc")

    # ------------------------------------------------------- trigger bus
    @staticmethod
    def _postmortem_enabled() -> bool:
        return os.environ.get("RAY_TPU_POSTMORTEM") != "0"

    @staticmethod
    def _coalesce_window_s() -> float:
        try:
            return float(os.environ.get("RAY_TPU_INCIDENT_WINDOW_S", "10.0"))
        except ValueError:
            return 10.0

    def report_trigger(
        self, kind: str, detail: Any = None, source: Optional[str] = None
    ) -> dict:
        """Remote half of the trigger bus (raylets/drivers/workers
        forward their anomaly triggers here via postmortem.arm_client)."""
        return self._trigger(kind, detail, source)

    def _trigger(
        self, kind: str, detail: Any = None, source: Optional[str] = None
    ) -> dict:
        """One anomaly trigger: coalesces into the open incident when its
        last trigger is within the (sliding) coalesce window — a chaos
        soak's 50 faults become one incident's trigger chain, not 50
        full-ring harvests — else opens a fresh incident and starts its
        harvest off-thread (the harvest fans RPCs through every raylet;
        it must never run on an RPC handler or under a state lock)."""
        if not self._postmortem_enabled():
            return {"ok": False, "disabled": True}
        ev = {
            "ts": time.time(),
            "ts_us": time.time_ns() // 1000,
            "kind": kind,
            "detail": _postmortem.safe_detail(detail),
            "source": source,
        }
        imet.POSTMORTEM_TRIGGERS.inc(kind=kind)
        fresh = False
        with self._incident_lock:
            inc = (
                self._incidents.get(self._open_incident)
                if self._open_incident
                else None
            )
            now_mono = time.monotonic()
            if (
                inc is not None
                and now_mono - inc["last_mono"] <= self._coalesce_window_s()
            ):
                inc["last_mono"] = now_mono
                inc["triggers"].append(ev)
                inc["coalesced"] += 1
                iid = inc["id"]
            else:
                iid = f"inc-{ev['ts_us']}-{kind.replace('.', '-')}"
                self._incidents[iid] = {
                    "id": iid,
                    "opened_ts": ev["ts"],
                    "opened_mono": now_mono,
                    "last_mono": now_mono,
                    "state": "open",
                    "triggers": [ev],
                    "coalesced": 0,
                    "bundle": None,
                }
                self._open_incident = iid
                fresh = True
                while len(self._incidents) > 64:
                    self._incidents.popitem(last=False)
        if fresh:
            _frec_record("incident.open", (iid, kind))
            imet.POSTMORTEM_INCIDENTS.inc()
            _log.warning(
                "incident %s opened by trigger %s (source=%s); harvesting",
                iid, kind, source,
            )
            self.pubsub_publish(
                "node_events",
                {"event": "incident", "incident_id": iid, "trigger": kind,
                 "ts": ev["ts"]},
            )
            threading.Thread(
                target=self._harvest, args=(iid,), daemon=True,
                name=f"harvest-{iid[:20]}",
            ).start()
        return {"ok": True, "incident": iid, "coalesced": not fresh}

    def _harvest(self, incident_id: str) -> None:
        """The incident harvest: after a short settle delay (lets the
        trigger chain accumulate and secondary failures land), fans
        `flight_dump` through every alive raylet (each SIGUSR2s its
        workers so their rings dump too), snapshots the GCS's own ring,
        tails structured logs, freezes the metrics-history window, and
        stages the bundle + clock-offset manifest, then builds the
        merged skew-corrected trace."""
        from ..observability import flight_recorder as _frec

        try:
            delay = float(os.environ.get("RAY_TPU_HARVEST_DELAY_S", "0.75"))
        except ValueError:
            delay = 0.75
        time.sleep(max(0.0, delay))
        with self._incident_lock:
            inc = self._incidents.get(incident_id)
            if inc is None:
                return
            inc["state"] = "harvesting"
        try:
            nodes = []
            for sh in self._shards:
                with self._locked(sh):
                    nodes.extend(
                        (nid, n["sock"], int(n.get("clock_offset_us") or 0))
                        for nid, n in sh.nodes.items()
                        if n["alive"]
                    )
            pids: Dict[str, dict] = {
                str(os.getpid()): {"node": "gcs", "offset_us": 0}
            }
            node_info: Dict[str, dict] = {}
            logs: List[dict] = []
            for nid, sock, offset_us in nodes:
                node_info[nid[:12]] = {"offset_us": offset_us}
                try:
                    res = self._raylet_call(sock, "flight_dump")
                except Exception as e:  # lint: swallow-ok(dead/partitioned raylet; harvest the reachable rings)
                    node_info[nid[:12]]["error"] = repr(e)[:200]
                    continue
                node_info[nid[:12]]["dump"] = (res or {}).get("path")
                for pid in (res or {}).get("pids") or ():
                    pids[str(pid)] = {"node": nid[:12], "offset_us": offset_us}
                try:
                    logs.extend(
                        self._raylet_call(sock, "tail_logs", {"tail": 300})
                        or []
                    )
                except Exception:  # lint: swallow-ok(log tails are enrichment; the rings are the contract)
                    pass
            _frec.dump(reason=f"incident harvest {incident_id}")
            # Give SIGUSR2'd workers a beat to land their rings before
            # the bundle copies the flight dir.
            time.sleep(0.5)
            with self._incident_lock:
                triggers = list(inc["triggers"])
            window_s = max(
                60.0, time.time() - (triggers[0]["ts"] - 30.0)
            )
            metrics = (
                self._history.query(window_s=window_s)
                if self._history is not None
                else []
            )
            goodput: Dict[str, Any] = {}
            for series in metrics:
                if series.get("name") == "raytpu_train_goodput" and series.get("samples"):
                    goodput["goodput"] = series["samples"][-1][1]
                if series.get("name") == "raytpu_train_mfu" and series.get("samples"):
                    goodput["mfu"] = series["samples"][-1][1]
            logs.sort(key=lambda r: r.get("ts") or 0.0)
            manifest = {
                "incident_id": incident_id,
                "opened_ts": triggers[0]["ts"],
                "triggers": triggers,
                "nodes": node_info,
                "pids": pids,
                "goodput": goodput,
                "impact_window_s": window_s,
            }
            bundle_dir = os.path.join(
                _postmortem.incidents_dir(self._session_dir), incident_id
            )
            _postmortem.stage_bundle(
                bundle_dir, manifest, log_records=logs[-1000:], metrics=metrics
            )
            _postmortem.merge_trace(bundle_dir)
            with self._incident_lock:
                inc["state"] = "staged"
                inc["bundle"] = bundle_dir
            _frec_record("incident.staged", (incident_id, bundle_dir))
            _log.warning(
                "incident %s staged: %s (render with `ray-tpu postmortem %s`)",
                incident_id, bundle_dir, incident_id,
            )
        except Exception:
            _log.exception("incident %s harvest failed", incident_id)
            with self._incident_lock:
                inc["state"] = "failed"

    def list_incidents(self) -> List[dict]:
        """Incident records, oldest first (state API / CLI)."""
        with self._incident_lock:
            return [
                {
                    "incident_id": i["id"],
                    "opened_ts": i["opened_ts"],
                    "state": i["state"],
                    "trigger": i["triggers"][0]["kind"] if i["triggers"] else None,
                    "triggers": len(i["triggers"]),
                    "bundle": i["bundle"],
                }
                for i in self._incidents.values()
            ]

    def get_incident(self, incident_id: str) -> Optional[dict]:
        with self._incident_lock:
            inc = self._incidents.get(incident_id)
            if inc is None:
                return None
            out = dict(inc)
            out["triggers"] = list(inc["triggers"])
            return out

    def debug_harvest(self, timeout_s: float = 20.0) -> dict:
        """`ray-tpu debug dump`: raises a manual trigger and waits for
        its incident's bundle to stage, so the CLI can print ONE bundle
        path + a ready-to-run postmortem hint instead of a loose
        per-process dump list. Coalesces like any other trigger — a dump
        requested mid-incident returns that incident's bundle."""
        res = _postmortem.publish_trigger(
            "debug.manual", None, source="ray-tpu debug dump"
        )
        if not isinstance(res, dict) or not res.get("ok"):
            # Client-side debounce (a second dump inside the window) or
            # the bus is disabled: fall back to whatever is open.
            with self._incident_lock:
                iid = self._open_incident
            if iid is None:
                return {"ok": False, "reason": "trigger bus disabled or debounced"}
        else:
            iid = res["incident"]
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            inc = self.get_incident(iid)
            if inc is None:
                break
            if inc["state"] in ("staged", "failed"):
                return {
                    "ok": inc["state"] == "staged",
                    "incident": iid,
                    "state": inc["state"],
                    "bundle": inc["bundle"],
                    "triggers": inc["triggers"],
                }
            time.sleep(0.1)
        return {"ok": False, "incident": iid, "reason": "harvest timed out"}

    # chaos_partition / chaos_heal: inherited from ChaosPartitionRpc
    # (chaos/net.py) — one definition shared with the raylet.

    def stop(self) -> bool:
        self._stop.set()
        if self._watchdog is not None:
            self._watchdog.stop()
        # Only disarm if this service is still the armed publisher — a
        # test that booted a newer in-process GCS keeps its bus.
        _postmortem.disarm(self._trigger)
        return True


def main(
    sock_path: str,
    snapshot_path: Optional[str] = None,
    tcp_address: Optional[str] = None,
) -> None:
    """GCS daemon. Serves the local UDS always; with `tcp_address`
    (tcp://host:port) ALSO serves the same tables over TCP so raylets on
    OTHER hosts can join (reference: the GCS listens on --gcs-server-port
    for the whole cluster)."""
    from .rpc import RpcServer

    import os

    from ..observability import logs as _logs

    _logs.configure(
        "gcs",
        node_id="gcs",
        directory=os.path.join(os.path.dirname(sock_path) or ".", "logs"),
    )
    _logs.get_logger("gcs").info("gcs daemon started (pid %d)", os.getpid())
    service = GcsService(
        snapshot_path=snapshot_path or sock_path + ".snapshot",
        session_dir=os.path.dirname(sock_path) or ".",
    )
    # The GCS's own internal metrics merge straight into its table — no
    # self-RPC loop (reference: the head metrics agent scraping itself).
    imet.configure(
        node_id="gcs",
        reporter="gcs",
        sink=lambda recs: service.report_internal_metrics("gcs", recs),
    )
    server = RpcServer(sock_path, service)
    tcp_server = RpcServer(tcp_address, service) if tcp_address else None
    if tcp_server is not None:
        # The bound address (ephemeral ports resolved) for the bootstrapper.
        print(f"GCS_TCP_ADDRESS={tcp_server.address}", flush=True)  # console-output: bootstrap protocol read by _read_announced
    try:
        while not service._stop.wait(0.5):
            pass
    finally:
        if tcp_server is not None:
            tcp_server.shutdown()
        server.shutdown()


if __name__ == "__main__":
    main(
        sys.argv[1],
        sys.argv[2] if len(sys.argv) > 2 else None,
        sys.argv[3] if len(sys.argv) > 3 else None,
    )
