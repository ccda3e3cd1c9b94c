"""Raylet: per-node daemon — worker pool, local scheduler, object plane.

Re-design of the reference's raylet (reference: src/ray/raylet/
node_manager.h:119 NodeManager; worker_pool.h:174 WorkerPool/PopWorker;
scheduling/cluster_task_manager.cc:44 QueueAndScheduleTask with spillback;
local_task_manager.cc:74 dispatch; dependency_manager.h). One raylet per
simulated node; each owns a shared-memory store segment and a pool of
worker processes that long-poll it for tasks.

Scheduling is two-level like the reference: the raylet first decides
local-vs-remote (consulting the GCS resource view; a remote choice
FORWARDS the task to that raylet — the analogue of lease spillback), then
the local half gates dispatch on resource availability and argument
locality (missing args are pulled from their location per the GCS object
directory before dispatch)."""

from __future__ import annotations

import collections
import json
import os
import pickle
import queue
import signal
import subprocess
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Set, Tuple

from .. import exceptions as exc
from .. import tracing as _tracing
from ..chaos.controller import kill_now as _chaos_kill
from ..chaos.controller import maybe_inject as _chaos_inject
from ..chaos.net import ChaosPartitionRpc
from ..utils import lock_order
from ..observability.flight_recorder import record as _flight_record
from ..observability.logs import get_logger as _get_logger
from ..utils import internal_metrics as imet
from ..utils.config import CONFIG
from . import proctree
from .heartbeat import HeartbeatCodec
from .ids import ObjectID
from .object_transport import StoredError
from .placement_group import decode_node_affinity
from .rpc import RpcClient, RpcServer
from .shm_store import SharedMemoryStore

POLL_TIMEOUT_S = CONFIG.worker_poll_timeout_s

_log = _get_logger("raylet")


# Sentinel returned by RayletService._gcs_call_fenced when the call was
# rejected with StaleNodeEpochError (the fence reaction has already run).
_FENCED = object()


class _Worker:
    def __init__(self, worker_id: str, proc: subprocess.Popen, env_key: str = ""):
        self.worker_id = worker_id
        self.proc = proc
        self.spawned_at = time.monotonic()  # flight_dump skips workers too
        # young to have bound their SIGUSR2 handler yet
        self.mailbox: "queue.Queue" = queue.Queue()
        self.busy_with: Optional[dict] = None  # task entry being executed
        self.actor_id: Optional[str] = None  # dedicated actor worker
        self.actor_rec: Optional[dict] = None  # the exact record dict this
        # worker serves: identity-compared on death so a re-created record
        # (fresh dict) is never charged for a bygone worker's exit
        self.env_key = env_key  # runtime-env pool key (reference:
        # worker_pool.h PopWorker matching runtime_env_hash)
        self.last_done: Optional[str] = None  # idempotency: a retried
        # worker_step must not double-apply its completion report
        self.ready = False  # first poll arrived: the process finished
        # booting (a forked-but-still-booting worker sits in the idle
        # pool — adoptable, its mailbox buffers the entry — but only a
        # ready worker counts as WARM for pool-health reporting)


class RayletService(ChaosPartitionRpc):
    def __init__(
        self,
        node_id: str,
        sock_path: str,
        store_path: str,
        gcs_sock: str,
        resources: Dict[str, float],
        store_capacity: int,
        labels: Optional[Dict[str, Any]] = None,
        advertise_address: Optional[str] = None,
        prestart_workers: int = 0,
    ):
        self._prestart_workers = int(prestart_workers)
        self.node_id = node_id
        self.sock_path = sock_path
        # The address other NODES reach this raylet at. Defaults to the
        # local UDS (single-host cluster); a multi-host raylet advertises
        # its tcp:// endpoint while local workers keep the UDS.
        self.advertised = advertise_address or sock_path
        self.store_path = store_path
        self.store = SharedMemoryStore.create(store_path, store_capacity)
        self.gcs = RpcClient(gcs_sock)
        self.gcs_sock = gcs_sock
        # Arm the anomaly trigger bus: raylet-side anomalies (chaos
        # injections, watchdog-adjacent events seen here) forward to the
        # GCS's report_trigger RPC for debounce + incident harvest.
        from ..observability import postmortem as _postmortem

        _postmortem.arm_client(self.gcs)
        self.total = dict(resources)
        self.available = dict(resources)
        self.labels = dict(labels or {})
        # Accelerator accounting goes through the manager registry
        # (ray_tpu.accelerators; reference: _private/accelerators/
        # accelerator.py — node startup consults the family manager, the
        # raylet no longer hardcodes TPU semantics). The manager supplies:
        # which physical chip indices this raylet may lease to bundles
        # (respecting an inherited TPU_VISIBLE_CHIPS restriction), the
        # spawn-time visibility env for workers, and — when the node
        # carries chips but no slice identity — the pod-slice labels
        # detected from env/metadata, so SLICE_GANG placement sees real
        # slices exactly like the test fixtures' fake ones.
        from ..accelerators import get_accelerator_manager

        self._tpu_manager = get_accelerator_manager("TPU")
        n_chips = int(resources.get("TPU", 0))
        if self._tpu_manager is not None:
            self._free_chips: Set[int] = set(
                self._tpu_manager.visible_chip_ids(n_chips)
            )
        else:
            self._free_chips = set(range(n_chips))
        self._all_chips = frozenset(self._free_chips)
        if n_chips and len(self._free_chips) < n_chips:
            # An inherited TPU_VISIBLE_CHIPS restriction leaves fewer
            # leasable chips than the declared count. Clamp the schedulable
            # total to match: otherwise a bundle could reserve more TPU
            # than this raylet has chips for, skip the chip lease, and its
            # workers would see every chip — including ones owned by
            # sibling raylets (the exact sharing the lease table prevents).
            self.total["TPU"] = self.available["TPU"] = float(
                len(self._free_chips)
            )
        if n_chips and "slice_name" not in self.labels and self._tpu_manager is not None:
            try:
                spec = self._tpu_manager.detect_slice_spec()
            except Exception:
                spec = None
            if spec is not None and spec.slice_name:
                self.labels.setdefault("slice_name", spec.slice_name)
                self.labels.setdefault("worker_index", spec.worker_index)
                self.labels.setdefault("tpu_version", spec.version)
                if spec.topology:
                    self.labels.setdefault("tpu_topology", spec.topology)
        self._res_lock = lock_order.tracked_lock("raylet.resources")
        # Placement-group bundle reservations hosted on this node:
        # (pg_id, bundle_index) -> {"reserved": {...}, "free": {...}}.
        # Reserved resources are deducted from `available`, so heartbeats
        # naturally reflect the lease (reference:
        # placement_group_resource_manager.h — the raylet owns bundle state).
        self._bundles: Dict[Tuple[str, int], dict] = {}

        self._workers: Dict[str, _Worker] = {}
        self._idle: Dict[str, List[str]] = {}  # env_key -> idle worker ids
        # Leased workers: owner pushes tasks to the worker's direct socket;
        # the raylet holds the lease's resources until it is returned
        # (reference: HandleRequestWorkerLease, node_manager.cc:1797).
        self._leases: Dict[str, Dict[str, Any]] = {}
        self._workers_lock = lock_order.tracked_lock("raylet.workers")
        self._max_task_workers = max(1, int(resources.get("CPU", 1)))
        # Task ids with cancel intent (reference: core_worker CancelTask ->
        # raylet queued-task removal). Bounded FIFO: broadcast cancels leave
        # ids on raylets that never see the task.
        self._cancelled: "collections.OrderedDict[str, bool]" = collections.OrderedDict()
        # Submission dedupe: one-way submits are resent after a reconnect
        # (rpc.py notify), and two-way submits are resent when the reply is
        # lost — either way the same (task_id, attempt) may arrive twice.
        # Keyed on attempt so owner-driven retries (a NEW attempt) pass.
        # Bounded LRU; only the RPC ingress checks it — internal re-entry
        # (soft-affinity fallback) legitimately re-ingests the same attempt.
        self._seen_submits: "collections.OrderedDict[Tuple[str, int], List[bytes]]" = (
            collections.OrderedDict()
        )
        self._seen_lock = lock_order.tracked_lock("raylet.seen_submits")

        self._pending: "queue.Queue" = queue.Queue()  # task entries
        # Wakes the dispatch loop on any schedulability change (new task,
        # worker freed, dep sealed, bundle released) instead of a 50 ms
        # poll cadence (reference: local_task_manager ScheduleAndDispatch
        # being invoked from every state-change site).
        self._sched_wake = threading.Event()
        self._waiting: List[dict] = []  # dep-blocked entries
        self._actors: Dict[str, dict] = {}  # actor_id -> {worker_id, queue, state}
        self._actor_lock = lock_order.tracked_lock("raylet.actors")

        self._remote_raylets: Dict[str, RpcClient] = {}
        self._stop = threading.Event()
        self._stopped = threading.Event()  # stop() has ended and reaped the children
        # Drain state (preemption notice received): new default-placement
        # work and lease grants are shed to other nodes while in-flight +
        # gang-pinned work finishes in the grace window.
        self._draining = False
        # Delta heartbeat encoder: steady-state beats carry only changed
        # state; forced full after (re)registration and fences, when the
        # GCS's view of this node is unknown (core/heartbeat.py).
        self._hb_codec = HeartbeatCodec()
        # Membership epoch granted at registration; carried on every
        # GCS-bound RPC. When the GCS answers StaleNodeEpochError this
        # incarnation has been fenced (declared dead during a partition):
        # _fence() kills the workers, drops leases/pins, and re-registers
        # as a fresh incarnation with a new epoch.
        self.epoch = 0
        # Local incarnation token stamped on every queued entry; _fence
        # regenerates it (at fence START) so a bygone life's queued work
        # is identity-distinguishable from the current one's regardless
        # of what the epoch NUMBER does across GCS resets.
        self._incarnation: object = object()
        self._fence_guard = threading.Lock()
        self._fencing = False
        # Highest epoch an actual fence has voided. self.epoch can ALSO
        # advance without a fence (heartbeat re-register after a GCS
        # snapshot loss) — callers whose batch was epoch-rejected consult
        # this to tell "my data belongs to a dead incarnation" (drop)
        # from "same healthy incarnation, new number" (resend).
        self._max_fenced_epoch = 0

        # Worker warm pool + zygote lifecycle (core/worker_pool.py): a
        # pre-warmed single-threaded forker (core/zygote.py) cuts the
        # ~2 s interpreter+jax startup of every fresh worker to a ~10 ms
        # fork, and the pool manager keeps BOTH warm tiers topped up — a
        # live idle-worker pool (popped at dispatch in microseconds) and
        # the zygote's parked pre-forks (a miss costs a ~1-2 ms pipe
        # assignment instead of the fork) — sized by launch-rate EWMA +
        # the GCS demand hint. Constructed after _log_dir below; started
        # at the END of __init__. Until the zygote is ready (or if
        # disabled/dead) spawns take the normal Popen path; a dead
        # zygote daemon is respawned by the manager, not abandoned.
        self._pool: Optional[Any] = None

        # Event-driven object plane: local seals notify this condition so
        # wait_objects() long-polls wake immediately instead of the old 5 ms
        # busy-poll (reference: pubsub WAIT_FOR_OBJECT_EVICTION/locality
        # channels, src/ray/pubsub/publisher.h — collapsed to a per-node
        # condition because all waiters of this node's store are local).
        self._seal_cv = threading.Condition()
        self._pulling: Set[str] = set()
        # Object-plane admission control (reference: pull_manager.h:52
        # prioritized bounded pulls; push_manager.h chunk scheduling):
        # bounds concurrent inbound pulls and outbound chunk serving so a
        # fan-in of requesters degrades to queueing, not thrash.
        self._pull_sem = threading.BoundedSemaphore(
            max(1, int(CONFIG.max_concurrent_pulls))
        )
        self._serve_sem = threading.BoundedSemaphore(
            max(1, int(CONFIG.max_concurrent_serves))
        )
        # Batched control-plane updates to the GCS (object locations + task
        # state events), off the task fast path (reference: task events are
        # batched in the reference too, src/ray/core_worker/task_event_buffer.h).
        self._loc_buf: List[str] = []
        self._evt_buf: List[dict] = []
        self._buf_lock = lock_order.tracked_lock("raylet.gcs_sync_buf")
        self._buf_wake = threading.Event()
        # Objects whose delete hit a reader pin; retried by the monitor loop
        # (guarded by _buf_lock: mutated from RPC handler threads).
        self._deferred_deletes: Set[str] = set()
        # Spill/eviction state (reference: plasma eviction_policy.h:160 LRU +
        # raylet/local_object_manager.h:41 spill-to-disk): seal-ordered index
        # of local objects (True = primary copy, False = pulled replica) and
        # the on-disk locations of spilled primaries.
        # Spill lands next to the raylet socket (session dir, disk-backed):
        # spilling INTO tmpfs would defeat the point of relieving the pool.
        self._spill_dir = os.path.join(
            os.path.dirname(sock_path) or ".", f"spill_{node_id}"
        )
        os.makedirs(self._spill_dir, exist_ok=True)
        self._log_dir = os.path.join(os.path.dirname(sock_path) or ".", "logs")
        os.makedirs(self._log_dir, exist_ok=True)
        from .worker_pool import WorkerPoolManager

        self._pool = WorkerPoolManager(self, prestart=self._prestart_workers)
        # Batched actor_started reports (flushed with the GCS sync
        # buffers): a launch storm costs the GCS O(batches), not
        # O(actors) — the epoch-fenced idempotent create path makes
        # replayed batches safe.
        self._started_buf: List[str] = []
        self._local_objects: "collections.OrderedDict[str, bool]" = collections.OrderedDict()
        self._spilled: Dict[str, str] = {}
        self._spill_lock = lock_order.tracked_lock("raylet.spill")
        # Serializes whole evict/spill/restore sequences: concurrent
        # ensure_space RPC threads must not unlink each other's fresh
        # spill files.
        self._evict_lock = lock_order.tracked_lock("raylet.evict")

        self._threads = [
            threading.Thread(target=self._scheduler_loop, daemon=True, name="sched"),
            threading.Thread(target=self._heartbeat_loop, daemon=True, name="hb"),
            threading.Thread(target=self._monitor_loop, daemon=True, name="monitor"),
            threading.Thread(target=self._flush_loop, daemon=True, name="flush"),
        ]
        if os.environ.get("RAY_TPU_LOG_MONITOR", "1") != "0":
            # Log monitor (reference: log_monitor.py): tails this node's
            # captured worker stdout/stderr, publishes new lines on the
            # `logs` pubsub channel (the driver re-prints them with
            # attribution prefixes), and mirrors them into structured
            # capture records so `ray-tpu logs --actor ...` finds raw
            # prints too.
            self._threads.append(
                threading.Thread(
                    target=self._log_monitor_loop, daemon=True, name="logmon"
                )
            )
        reg = self.gcs.call(
            # self.total, not the raw arg: the visible-chip clamp above must
            # be what the cluster schedules against (heartbeat re-register
            # already advertises self.total).
            "register_node", node_id, self.advertised, store_path, self.total, self.labels
        )
        self._cluster_size = reg.get("nodes", 1) if isinstance(reg, dict) else 1
        self.epoch = reg.get("epoch", 0) if isinstance(reg, dict) else 0
        # Internal metrics: this raylet's hot-path instruments flush
        # through its existing GCS client (batched, off the fast path),
        # and the per-node ReporterAgent collects cpu/mem/fd/device
        # gauges (reference: reporter_agent.py:336).
        imet.configure(
            node_id=node_id,
            reporter=f"raylet_{node_id}",
            sink=lambda recs: self.gcs.call(
                "report_internal_metrics", f"raylet_{node_id}", recs
            ),
        )
        self._reporter = imet.ReporterAgent()
        self._reporter.start()
        for t in self._threads:
            t.start()
        self._pool.start()

    # ----------------------------------------------- control-plane batching
    def _notify_sealed(self, oid_hexes: List[str], primary: bool = True) -> None:
        """A local seal: wake waiters now, tell the GCS directory soon."""
        if oid_hexes:
            with self._spill_lock:
                for h in oid_hexes:
                    self._local_objects[h] = primary
                    self._local_objects.move_to_end(h)
        with self._seal_cv:
            self._seal_cv.notify_all()
        with self._buf_lock:
            self._loc_buf.extend(oid_hexes)
        self._buf_wake.set()
        self._sched_wake.set()  # a sealed object may unblock queued tasks

    def _task_event(self, task_id: str, state: str, **extra) -> None:
        evt = {"task_id": task_id, "state": state, "ts": time.time()}
        evt.update(extra)
        with self._buf_lock:
            self._evt_buf.append(evt)
        self._buf_wake.set()

    def _enqueue(self, entry: dict) -> None:
        """Queues one entry for the local scheduler; stamps queue-entry
        time so dispatch can report queue-to-dispatch latency (and the
        local incarnation token, so work queued by a later-fenced
        incarnation is dropped at dispatch instead of double-executing —
        the token, not the epoch NUMBER, because the epoch also advances
        benignly on a GCS-snapshot-loss re-register, where queued work is
        still legitimate, and numbers can repeat across GCS resets)."""
        entry["_q_ts"] = time.monotonic()
        entry["_node_incarnation"] = self._incarnation
        _flight_record("sched.queue", (entry.get("task_id") or "")[:16])
        self._pending.put(entry)
        self._sched_wake.set()

    def _flush_loop(self) -> None:
        """Drains location + task-event buffers to the GCS (batched; the
        object fast path never blocks on a GCS round trip)."""
        while not self._stop.is_set():
            self._buf_wake.wait(timeout=0.2)
            self._buf_wake.clear()
            # Epoch captured BEFORE the buffer pop: these entries belong
            # to the incarnation that buffered them. A fence completing
            # between pop and send would advance self.epoch — stamping
            # the old life's sealed objects with the fresh epoch would
            # slip them past the GCS's fence check and re-index locations
            # it already dropped at node death. Captured-early, a raced
            # sync is rejected and dropped (fail-safe).
            ep = self.epoch
            with self._buf_lock:
                locs, self._loc_buf = self._loc_buf, []
                evts, self._evt_buf = self._evt_buf, []
                started, self._started_buf = self._started_buf, []
            if started:
                self._flush_actor_started(started, ep)
            if not locs and not evts:
                continue
            try:
                self.gcs.call("node_sync", self.node_id, locs, evts, ep)
                imet.GCS_SYNC_TOTAL.inc()
                imet.GCS_SYNC_BATCH.observe(len(locs) + len(evts))
            except exc.StaleNodeEpochError:
                # This incarnation is fenced: its sealed objects and task
                # events are void (the buffers die with the old life —
                # re-syncing them post-rejoin would advertise dangling
                # locations). _fence clears state and re-registers.
                self._fence("node_sync", ep)
                if ep > self._max_fenced_epoch:
                    # The rejection was an epoch advance WITHOUT a fence
                    # (heartbeat re-registered after a GCS snapshot loss):
                    # this is still the same healthy incarnation and its
                    # sealed objects are real — re-buffer so the next
                    # flush re-indexes them under the current epoch.
                    with self._buf_lock:
                        self._loc_buf = locs + self._loc_buf
                        self._evt_buf = evts + self._evt_buf
            except Exception:
                with self._buf_lock:  # GCS briefly unreachable: retry later
                    self._loc_buf = locs + self._loc_buf
                    self._evt_buf = evts + self._evt_buf
                # Stop-aware backoff: a plain sleep would hold shutdown
                # hostage for the full backoff (blocking-in-loop lint).
                self._stop.wait(0.5)

    def _flush_actor_started(self, started: List[str], ep: int) -> None:
        """One batched actor_started RPC for every constructor that
        completed since the last flush (launch storms coalesce; the old
        per-actor `actor_started` call serialized the GCS on O(actors)).
        Per-actor False verdicts mean the record moved while our create
        was in flight: that instance is a duplicate and dies locally —
        identical semantics to the old synchronous path."""
        try:
            verdicts = self.gcs.call(
                "actor_started_batch", self.node_id, started, ep
            )
        except exc.StaleNodeEpochError:
            # This incarnation was fenced mid-launch: the GCS already
            # moved these actors; our instances die with the fence.
            self._fence("actor_started", ep)
        except Exception:
            with self._buf_lock:  # GCS briefly unreachable: retry later
                self._started_buf = started + self._started_buf
        else:
            for aid, ok in (verdicts or {}).items():
                if ok is False:
                    self._kill_duplicate_instance(aid)

    def _kill_duplicate_instance(self, aid: str) -> None:
        """The GCS record for `aid` points elsewhere (an ambiguously
        delivered create was retried onto another node while this
        instance launched): kill the local duplicate WITHOUT an
        actor_died report — the record is not ours to touch; the monitor
        sees state DEAD and stays silent."""
        _log.warning(
            "actor %s started here but the GCS record points elsewhere: "
            "killing the duplicate instance", aid[:8],
        )
        with self._actor_lock:
            a = self._actors.get(aid)
            wid = a.get("worker_id") if a else None
            if a:
                a["state"] = "DEAD"
        if wid:
            with self._workers_lock:
                w = self._workers.get(wid)
            if w:
                w.proc.kill()

    # ------------------------------------------------------------ helpers
    def _remote(self, sock: str) -> RpcClient:
        cli = self._remote_raylets.get(sock)
        if cli is None:
            cli = RpcClient(sock)
            self._remote_raylets[sock] = cli
        return cli

    def _try_acquire(self, resources: Dict[str, float]) -> bool:
        with self._res_lock:
            if all(self.available.get(k, 0.0) >= v for k, v in resources.items()):
                for k, v in resources.items():
                    self.available[k] = self.available.get(k, 0.0) - v
                return True
            return False

    def _release(self, resources: Dict[str, float]) -> None:
        with self._res_lock:
            for k, v in resources.items():
                self.available[k] = min(self.total.get(k, 0.0), self.available.get(k, 0.0) + v)

    def _fits_total(self, resources: Dict[str, float]) -> bool:
        return all(self.total.get(k, 0.0) >= v for k, v in resources.items())

    # ------------------------------------------------- placement bundles
    def reserve_bundle(self, pg_id: str, bundle_index: int, resources: Dict[str, float]) -> bool:
        """Leases a PG bundle out of this node's free pool. The reservation
        survives heartbeats because it is debited from `available` here, at
        the source of truth."""
        key = (pg_id, bundle_index)
        with self._res_lock:
            if key in self._bundles:
                return True  # idempotent retry
            short = not all(
                self.available.get(k, 0.0) >= v for k, v in resources.items()
            )
        if short:
            # Leases may be sitting on the resources this bundle needs:
            # reclaim (release is immediate) and re-check once.
            self._maybe_reclaim_leases(resources)
        with self._res_lock:
            if key in self._bundles:
                return True
            if not all(self.available.get(k, 0.0) >= v for k, v in resources.items()):
                return False
            for k, v in resources.items():
                self.available[k] = self.available.get(k, 0.0) - v
            b = {"reserved": dict(resources), "free": dict(resources)}
            n_chips = int(resources.get("TPU", 0))
            if n_chips > 0 and len(self._free_chips) >= n_chips:
                # Lease physical chips to the bundle: its workers get
                # TPU_VISIBLE_CHIPS so co-located gangs never share a chip.
                chips = sorted(self._free_chips)[:n_chips]
                self._free_chips.difference_update(chips)
                b["chips"] = chips
            self._bundles[key] = b
        return True

    def release_bundle(self, pg_id: str, bundle_index: int) -> bool:
        with self._res_lock:
            b = self._bundles.pop((pg_id, bundle_index), None)
            if b is None:
                return False
            for k, v in b["reserved"].items():
                self.available[k] = min(self.total.get(k, 0.0), self.available.get(k, 0.0) + v)
            chips = set(b.get("chips") or ())
            self._free_chips.update(chips)
        if chips:
            # Workers bound to these chips must die with the lease: a new
            # gang may be handed the same chips immediately, and two live
            # processes must never share a chip.
            self._retire_chip_workers(chips)
        self._sched_wake.set()
        return True

    def _retire_chip_workers(self, chips: Set[int]) -> None:
        victims: List[_Worker] = []
        with self._workers_lock:
            for w in self._workers.values():
                if not w.env_key:
                    continue
                try:
                    tpu = json.loads(w.env_key).get("tpu")
                except Exception:  # lint: swallow-ok(malformed env_key means no chip lease)
                    continue
                if tpu and chips.intersection(tpu.get("chips", ())):
                    victims.append(w)
        for w in victims:
            # Kill only: the monitor loop observes the death, fails any
            # in-flight entries, releases resources, and purges idle lists.
            try:
                w.proc.kill()
            except OSError:
                pass

    def _fail_if_unschedulable(self, entry: dict) -> bool:
        """Bundle-pinned work whose bundle is gone (PG removed) or whose
        request exceeds the bundle's whole reservation can never dispatch:
        fail it now so get() raises instead of hanging (reference: Ray fails
        tasks of removed placement groups)."""
        key = self._entry_bundle_key(entry)
        if key is None:
            return False
        with self._res_lock:
            b = self._bundles.get(key)
            reserved = dict(b["reserved"]) if b else None
        if reserved is None:
            self._store_error_for(
                entry,
                RuntimeError(
                    f"placement group {key[0][:8]} bundle {key[1]} is not "
                    "reserved on this node (placement group removed?)"
                ),
            )
            return True
        if not all(reserved.get(k, 0.0) >= v for k, v in entry["resources"].items()):
            self._store_error_for(
                entry,
                RuntimeError(
                    f"task requires {entry['resources']} but bundle {key[1]} "
                    f"of placement group {key[0][:8]} only reserves {reserved}"
                ),
            )
            return True
        return False

    def _entry_bundle_key(self, entry: dict) -> Optional[Tuple[str, int]]:
        pg_id = entry.get("pg_id")
        if not pg_id:
            return None
        return (pg_id, entry.get("bundle_index", 0))

    def _try_acquire_entry(self, entry: dict) -> bool:
        """Acquires the entry's resources — from its PG bundle's reserved
        pool when it has one, else from the node's free pool."""
        key = self._entry_bundle_key(entry)
        if key is None:
            return self._try_acquire(entry["resources"])
        with self._res_lock:
            b = self._bundles.get(key)
            if b is None:
                # Bundle not (yet) reserved here — e.g. reservation RPC still
                # in flight. Keep the task queued.
                return False
            free = b["free"]
            if not all(free.get(k, 0.0) >= v for k, v in entry["resources"].items()):
                return False
            for k, v in entry["resources"].items():
                free[k] = free.get(k, 0.0) - v
        return True

    def _release_entry(self, entry: dict) -> None:
        key = self._entry_bundle_key(entry)
        if key is None:
            self._release(entry["resources"])
            return
        with self._res_lock:
            b = self._bundles.get(key)
            if b is None:
                return  # bundle was released while the task ran
            cap = b["reserved"]
            for k, v in entry["resources"].items():
                b["free"][k] = min(cap.get(k, 0.0), b["free"].get(k, 0.0) + v)

    # ----------------------------------------------------------- ingress
    def submit_task(self, spec_blob: bytes, forwarded: bool = False) -> List[bytes]:
        """Queues a normal task; returns return-object ids. May forward to
        another node (spillback, reference: cluster_task_manager.cc:136)."""
        entry = pickle.loads(spec_blob)
        dup = self._dedupe_submit(entry)
        if dup is not None:
            return dup
        return self._ingest_entry(entry, spec_blob, forwarded)

    def submit_task_batch(self, batch_blob: bytes) -> int:
        """Batched one-way submission: owners coalesce bursts into one
        message, collapsing per-task RPC overhead (reference: the
        submission-queue batching in NormalTaskSubmitter)."""
        entries = pickle.loads(batch_blob)
        for entry in entries:
            if self._dedupe_submit(entry) is None:
                self._ingest_entry(entry, None, False)
        return len(entries)

    def _dedupe_submit(self, entry: dict) -> Optional[List[bytes]]:
        """Returns the prior return_ids when this (task_id, attempt) already
        arrived at this node's RPC ingress — a reconnect-resend duplicate
        (rpc.py call/notify both resend after reconnect; the first send may
        have executed with its ack lost). None means first sighting."""
        key = (entry["task_id"], entry.get("attempt", 0))
        with self._seen_lock:
            if key in self._seen_submits:
                self._seen_submits.move_to_end(key)
                return self._seen_submits[key]
            self._seen_submits[key] = entry["return_ids"]
            while len(self._seen_submits) > 65536:
                self._seen_submits.popitem(last=False)
        return None

    def _ingest_entry(
        self, entry: dict, spec_blob: Optional[bytes], forwarded: bool
    ) -> List[bytes]:
        resources = entry["resources"]

        def blob() -> bytes:  # batched path: re-frame only when forwarding
            return spec_blob if spec_blob is not None else pickle.dumps(entry)
        if entry.get("pg_id"):
            # Bundle-pinned: the driver routed it to this node; never spill.
            entry["type"] = "task"
            self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
            self._enqueue(entry)
            return entry["return_ids"]
        if not forwarded:
            strategy = entry.get("strategy") or "DEFAULT"
            affinity = decode_node_affinity(strategy)
            if self._draining and affinity is None:
                # Draining (preemption notice): fresh default-placement
                # work must land on a node that will outlive the grace
                # window (explicitly node-pinned tasks keep their pin).
                # The placement thread excludes this node and fails the
                # task visibly if the cluster has no room.
                threading.Thread(
                    target=self._place_elsewhere, args=(entry, blob()), daemon=True
                ).start()
                return entry["return_ids"]
            if affinity is not None:
                # NodeAffinity (reference: scheduling_strategies.py
                # NodeAffinitySchedulingStrategy): route to the named node;
                # hard affinity fails when the node is gone, soft falls
                # back to default placement.
                target_id, soft = affinity
                if target_id != self.node_id:
                    # Off the handler thread: the GCS lookup retries on
                    # hiccups, and submit_task is a one-way notify whose
                    # handler must not stall the submission pipeline
                    # (same pattern as _place_elsewhere).
                    threading.Thread(
                        target=self._place_affinity,
                        args=(entry, blob(), target_id, soft),
                        daemon=True,
                    ).start()
                    return entry["return_ids"]
                if not self._fits_total(resources):
                    if not soft:
                        self._store_error_for(
                            entry,
                            RuntimeError(
                                f"hard NodeAffinity to {target_id[:12]}: node "
                                f"cannot ever satisfy {resources}"
                            ),
                        )
                        return entry["return_ids"]
                    # soft + infeasible here: fall through to default
                    # placement (spillback finds a capable node).
                else:
                    # Affinity to this node: queue here, skip spillback.
                    entry["type"] = "task"
                    self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
                    self._enqueue(entry)
                    return entry["return_ids"]
            elif strategy == "SPREAD":
                # Round-robin over feasible nodes (reference: spread policy,
                # scheduling_strategy="SPREAD"). Not gated on the cached
                # cluster size: it lags a heartbeat behind node additions,
                # and an explicit SPREAD request justifies the GCS hop.
                # Off the handler thread: a dead target would stall every
                # subsequent submission pipelined on this connection.
                threading.Thread(
                    target=self._place_spread, args=(entry, blob()), daemon=True
                ).start()
                return entry["return_ids"]
            # Cluster-level decision: if it can't run here (ever, or not
            # soon) and another node has room now, forward it.
            if not self._fits_total(resources):
                # Infeasible here. Hand placement to a background thread:
                # the GCS view lags by a heartbeat (a capable node may
                # appear), and the submit RPC is one-way so a failure must
                # surface as a stored error object, not a raise.
                threading.Thread(
                    target=self._place_elsewhere, args=(entry, blob()), daemon=True
                ).start()
                return entry["return_ids"]
            if self._cluster_size > 1 and not self._can_run_soon(resources):
                # On a single-node cluster there is nowhere to spill, so the
                # GCS round trip is skipped (hot under submission storms).
                # Submission is one-way, so spillback failures must not
                # raise: fall back to queuing locally (feasible here).
                try:
                    target = self.gcs.call("pick_node", resources, [self.node_id])
                    if target is not None:
                        return self._remote(target["sock"]).call(
                            "submit_task", blob(), True
                        )
                except Exception as e:
                    _log.debug("spillback failed, queuing locally: %r", e)
        entry["type"] = "task"
        self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
        self._enqueue(entry)
        return entry["return_ids"]

    def _place_affinity(
        self, entry: dict, spec_blob: bytes, target_id: str, soft: bool
    ) -> None:
        """Resolves + forwards a NodeAffinity task to its target node
        (background thread; a transient GCS hiccup must neither fail hard
        affinity permanently nor stall the submit handler)."""
        info = None
        looked_up = False
        for _ in range(3):
            try:
                info = self.gcs.call("node_info", target_id)
                looked_up = True
                break
            except Exception:
                time.sleep(0.3)
        if info is not None and info.get("alive"):
            total = info.get("resources") or {}
            if all(total.get(k, 0.0) >= v for k, v in entry["resources"].items()):
                try:
                    self._remote(info["sock"]).call("submit_task", spec_blob, True)
                    return
                except Exception:
                    info = None  # died mid-forward
            else:
                # Target can never run it: fail hard affinity here — the
                # forwarded path skips feasibility.
                if not soft:
                    self._store_error_for(
                        entry,
                        RuntimeError(
                            f"hard NodeAffinity to {target_id[:12]}: node "
                            f"cannot ever satisfy {entry['resources']}"
                        ),
                    )
                    return
                info = None
        if not soft:
            self._store_error_for(
                entry,
                RuntimeError(
                    f"hard NodeAffinity to {target_id[:12]} cannot be satisfied: "
                    + ("node is gone" if looked_up else "GCS unreachable")
                ),
            )
            return
        # Soft fallback: re-enter the default placement path.
        entry = dict(entry)
        entry["strategy"] = "DEFAULT"
        self._ingest_entry(entry, None, False)

    def _place_spread(self, entry: dict, spec_blob: bytes) -> None:
        """Resolves + forwards a SPREAD task (background thread); any
        failure falls back to local default placement."""
        try:
            target = self.gcs.call("pick_node", entry["resources"], [], "spread")
            if target is not None and target["node_id"] != self.node_id:
                self._remote(target["sock"]).call("submit_task", spec_blob, True)
                return
        except Exception as e:
            _log.debug("spread placement failed, queuing locally: %r", e)
        entry["type"] = "task"
        self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
        self._enqueue(entry)

    def _place_elsewhere(self, entry: dict, spec_blob: bytes) -> None:
        """Finds a node for a task this node can never run; retries while
        the GCS view catches up, then fails the task visibly."""
        resources = entry["resources"]
        deadline = time.monotonic() + CONFIG.placement_retry_timeout_s
        while time.monotonic() < deadline:
            try:
                target = self.gcs.call("pick_node", resources, [self.node_id])
            except Exception:
                target = None
            if target is not None:
                try:
                    self._remote(target["sock"]).call("submit_task", spec_blob, True)
                    return
                except Exception:  # lint: swallow-ok(target died mid-forward; retried until deadline)
                    pass
            time.sleep(0.1)
        self._store_error_for(
            entry, RuntimeError(f"no node can satisfy {resources}")
        )

    def _mark_cancelled(self, task_id: str) -> None:
        self._cancelled[task_id] = True
        while len(self._cancelled) > 10_000:
            self._cancelled.popitem(last=False)

    def is_cancelled(self, task_id: str) -> bool:
        return task_id in self._cancelled

    def cancel_task(self, task_id: str, force: bool = False) -> bool:
        """Cancels a queued or running normal task (reference: core_worker
        CancelTask; queued removal + SIGINT/kill of the executor). Returns
        True if the task was found here."""
        # Queued: remove from the waiting list via the scheduler's next scan.
        with self._workers_lock:
            running = next(
                (
                    w
                    for w in self._workers.values()
                    if w.busy_with is not None
                    and w.busy_with.get("task_id") == task_id
                ),
                None,
            )
        if running is None:
            self._mark_cancelled(task_id)
            self._sched_wake.set()
            return True
        entry = running.busy_with
        # Sticky intent: if the signalled worker dies instead of catching
        # the interrupt (e.g. SIGINT during startup imports), the monitor
        # must cancel, not retry.
        self._mark_cancelled(task_id)
        if force:
            running.proc.kill()
            self._store_error_for(
                entry,
                exc.TaskCancelledError(f"{entry.get('desc','task')} was cancelled"),
            )
        else:
            try:
                running.proc.send_signal(signal.SIGINT)
            except OSError:
                pass
        return True

    def _can_run_soon(self, resources) -> bool:
        with self._res_lock:
            return all(self.available.get(k, 0.0) >= v for k, v in resources.items())

    def create_actor(
        self, spec_blob: bytes, forwarded: bool = False, bundle_index: Optional[int] = None
    ) -> bool:
        """Hosts an actor (the GCS already picked this node). `bundle_index`
        carries the GCS-resolved bundle when the caller's spec said -1."""
        if self._pool is not None:
            self._pool.note_demand()  # launch-rate signal sizes the pool
        entry = pickle.loads(spec_blob)
        entry["type"] = "actor_creation"
        if bundle_index is not None and bundle_index >= 0:
            entry["bundle_index"] = bundle_index
        with self._actor_lock:
            existing = self._actors.get(entry["actor_id"])
            if existing is not None and existing["state"] != "DEAD":
                # Duplicate delivery: RpcClient.call resends its payload
                # after a reconnect, so the GCS's create can arrive twice.
                # Hosting it twice would launch a second live instance.
                return True
            self._actors[entry["actor_id"]] = {
                "worker_id": None,
                "state": "PENDING",
                "inflight": [],  # dispatched actor tasks, FIFO (serial exec)
                "spec_blob": spec_blob,
                "creation_entry": entry,  # resource/bundle accounting handle
                "resources": entry["resources"],
                "resources_held": False,
            }
        self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
        self._enqueue(entry)
        return True

    def create_actor_batch(self, items: List[Tuple[bytes, Optional[int]]]) -> int:
        """Batched actor hosting: the GCS forwards a registration storm's
        creations for this node in ONE RPC (each item is (spec_blob,
        resolved_bundle_index)). Individually idempotent — create_actor
        dedupes on the live actor table — so a replayed batch (RPC
        reconnect resend) is safe."""
        for blob, bundle_index in items:
            self.create_actor(blob, True, bundle_index)
        return len(items)

    def submit_actor_task(self, spec_blob: bytes) -> List[bytes]:
        entry = pickle.loads(spec_blob)
        entry["type"] = "actor_task"
        aid = entry["actor_id"]
        with self._actor_lock:
            a = self._actors.get(aid)
            if a is None or a["state"] == "DEAD":
                self._store_error_for(
                    entry,
                    RuntimeError(
                        f"actor {aid[:8]} is not on this node or is dead"
                    ),
                )
                return entry["return_ids"]
        self._task_event(entry["task_id"], "QUEUED", name=entry.get("desc", ""))
        self._enqueue(entry)
        return entry["return_ids"]

    def kill_actor(self, actor_id: str, no_restart: bool = True) -> bool:
        with self._actor_lock:
            a = self._actors.get(actor_id)
            wid = a.get("worker_id") if a else None
            if a:
                a["state"] = "DEAD"
        # Worker dies BEFORE the GCS hears about it: with restart allowed
        # the GCS re-creates immediately (possibly on this very node,
        # overwriting the local DEAD record) — killing the old worker
        # after that would misattribute its death to the fresh record and
        # trigger a second restart.
        if wid:
            with self._workers_lock:
                w = self._workers.get(wid)
            if w:
                w.proc.kill()
                # kill() returns once the process is GONE (bounded): an
                # accelerator belongs to one process, so the next actor to
                # open the chip must not race the dying owner's teardown.
                deadline = time.monotonic() + 10.0
                while w.proc.poll() is None and time.monotonic() < deadline:
                    time.sleep(0.005)
        self._gcs_call_fenced(
            "kill_actor", "actor_died", actor_id, "killed via kill()",
            no_restart, self.node_id,
        )
        return True

    # ------------------------------------------------------- object plane
    # -------------------------------------------------- remote-client proxy
    def client_put(self, oid_hex: str, blob: bytes) -> bool:
        """Stores a pre-framed object on behalf of a remote client driver
        (reference: ray client's server-side proxy owning client objects,
        util/client/server/). This raylet's node becomes the primary."""
        oid = ObjectID.from_hex(oid_hex)
        try:
            self.store.put_raw(oid, blob)
        except exc.ObjectStoreFullError:
            self.ensure_space(len(blob))
            self.store.put_raw(oid, blob)
        self._notify_sealed([oid_hex])
        return True

    def client_get(self, oid_hex: str, timeout: float = 30.0) -> Optional[bytes]:
        """Returns the framed payload for a remote client driver, pulling
        or restoring the object first when needed. None on timeout. Rides
        wait_objects (seal-notification waits + bounded location checks +
        async pulls) rather than a pull_object retry loop — a client
        blocked on a still-running task must not hammer the GCS."""
        oid = ObjectID.from_hex(oid_hex)
        deadline = time.monotonic() + timeout
        while True:
            if self.store.contains(oid) or oid_hex in self._spilled:
                if not self.store.contains(oid):
                    self._restore(oid_hex)
                raw = self.store.get_raw(oid)
                if raw is not None:
                    return raw
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                return None
            self.wait_objects([oid_hex], 1, min(remaining, 5.0), pull=True)

    def pull_object(self, oid_hex: str, timeout: float = 30.0) -> bool:
        """Ensures the object is in the local store, fetching from a remote
        node if needed (reference: pull_manager.h:52)."""
        oid = ObjectID.from_hex(oid_hex)
        if self.store.contains(oid):
            return True
        if self._restore(oid_hex):
            return True
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            if self._restore(oid_hex):
                # A transiently full pool can fail the first restore; the
                # spilled file is still the authoritative local copy.
                return True
            locations = self.gcs.call("get_object_locations", oid_hex)
            for loc in locations:
                if loc["node_id"] == self.node_id:
                    continue
                try:
                    if self._pull_from(loc["sock"], oid):
                        self._notify_sealed([oid_hex], primary=False)
                        return True
                except exc.ObjectStoreFullError:
                    break  # pins may drop; retry within the deadline
                except Exception:  # lint: swallow-ok(one dead location; try the next replica)
                    continue
            if self.store.contains(oid):
                return True
            time.sleep(0.01)
        return False

    def _pull_async(self, oid_hex: str) -> None:
        """One in-flight pull per object, shared by all waiters."""
        with self._seal_cv:
            if oid_hex in self._pulling:
                return
            self._pulling.add(oid_hex)

        def run():
            try:
                self.pull_object(oid_hex, timeout=CONFIG.object_wait_poll_s)
            finally:
                with self._seal_cv:
                    self._pulling.discard(oid_hex)
                    self._seal_cv.notify_all()

        threading.Thread(target=run, daemon=True).start()

    def wait_objects(
        self,
        oid_hexes: List[str],
        num_returns: Optional[int] = None,
        timeout: float = 10.0,
        pull: bool = False,
    ) -> List[str]:
        """Long-poll until >= num_returns of the objects are available.

        `pull=True` (the get() path) counts only locally-present objects and
        fetches remote ones in; `pull=False` (the wait() path) counts an
        object that exists anywhere in the cluster. Wakes on local seal
        notifications — the event-driven replacement for the driver's old
        5 ms polling loops (reference: core_worker Wait/Get long-poll on the
        plasma store + object directory subscriptions)."""
        if num_returns is None:
            num_returns = len(oid_hexes)
        deadline = time.monotonic() + max(0.0, timeout)
        exists_remote: Set[str] = set()
        last_loc_check = 0.0
        while True:
            ready = [
                h
                for h in oid_hexes
                if self.store.contains(ObjectID.from_hex(h))
                or (h in exists_remote)
                or (not pull and h in self._spilled)  # spilled == exists
            ]
            if len(ready) >= num_returns:
                return ready
            now = time.monotonic()
            if now >= deadline:
                return ready
            missing = [
                h
                for h in oid_hexes
                if h not in exists_remote
                and not self.store.contains(ObjectID.from_hex(h))
            ]
            if pull and missing:
                for h in missing:
                    if h in self._spilled:
                        self._restore(h)
            if missing and now - last_loc_check >= 0.05:
                last_loc_check = now
                try:
                    locs = self.gcs.call("get_object_locations_batch", missing)
                except Exception:
                    locs = {}
                for h, ls in locs.items():
                    if any(loc["node_id"] != self.node_id for loc in ls):
                        if pull:
                            self._pull_async(h)
                        else:
                            exists_remote.add(h)
            with self._seal_cv:
                self._seal_cv.wait(timeout=min(0.05, max(0.001, deadline - now)))

    def _pull_from(self, sock: str, oid: ObjectID) -> bool:
        """Fetches one object from a remote raylet. Small objects come in
        one RPC; large ones stream in transfer_chunk_bytes pieces written
        straight into the preallocated pool region (reference:
        push_manager.h:30 / object_buffer_pool.h chunked transfer — a 1 GiB
        object never needs a contiguous 1 GiB RPC buffer on either side).
        Bounded by the pull semaphore: excess pulls queue here instead of
        saturating memory/NIC (reference: pull_manager admission)."""
        with self._pull_sem:
            return self._pull_from_unbounded(sock, oid)

    def _pull_from_unbounded(self, sock: str, oid: ObjectID) -> bool:
        remote = self._remote(sock)
        oid_hex = oid.hex()
        chunk = int(CONFIG.transfer_chunk_bytes)
        size = remote.call("object_size", oid_hex)
        if size is None:
            return False
        if size <= chunk:
            raw = remote.call("fetch_object", oid_hex)
            if raw is None:
                return False
            try:
                self.store.put_raw(oid, raw)
            except exc.ObjectStoreFullError:
                self.ensure_space(len(raw))
                self.store.put_raw(oid, raw)
            imet.OBJECT_BYTES_IN.inc(len(raw))
            return True
        try:
            pool_off = self.store.begin_put_raw(oid, size)
        except exc.ObjectStoreFullError:
            self.ensure_space(size)
            pool_off = self.store.begin_put_raw(oid, size)
        if pool_off is None:
            return True  # concurrent pull won
        sealed = False
        try:
            pos = 0
            while pos < size:
                piece = remote.call("fetch_object_chunk", oid_hex, pos, chunk)
                if not piece:  # source evicted/died mid-transfer: abandon
                    return False
                self.store.write_raw_at(pool_off, pos, piece)
                pos += len(piece)
            self.store.finish_put_raw(oid)
            sealed = True
            imet.OBJECT_BYTES_IN.inc(size)
            return True
        finally:
            if not sealed:
                # Delete the UNSEALED slot: sealing a truncated payload
                # would hand readers corrupt data, and an orphaned CREATED
                # slot would poison every later pull with EEXIST.
                self.store.delete(oid)

    # ---------------------------------------------------- tree broadcast
    def push_object(self, oid_hex: str, src_sock: str, targets: List[str]) -> bool:
        """Receives a broadcast relay: fetch the object from `src_sock`,
        then fan the remaining targets out as TWO subtrees rooted at their
        first nodes — N-node broadcast completes in O(log N) rounds with
        every node uploading at most twice, instead of the O(N) serial
        pulls the owner would otherwise serve (reference:
        push_manager.h:30 push-based transfer; the tree shape is the
        standard broadcast inversion of it)."""
        threading.Thread(
            target=self._do_push, args=(oid_hex, src_sock, list(targets)), daemon=True
        ).start()
        return True

    def _do_push(self, oid_hex: str, src_sock: str, targets: List[str]) -> None:
        oid = ObjectID.from_hex(oid_hex)
        try:
            if not self.store.contains(oid):
                if not self._pull_from(src_sock, oid) and not self.store.contains(oid):
                    # Source lost the object mid-broadcast: the normal pull
                    # path (GCS directory) is the fallback for our subtree.
                    if not self.pull_object(oid_hex, timeout=30.0):
                        return
                self._notify_sealed([oid_hex], primary=False)
        except Exception:
            return
        self._relay_push(oid_hex, targets)

    def _relay_push(self, oid_hex: str, targets: List[str]) -> None:
        """Splits targets into two subtrees and notifies their roots."""
        targets = [t for t in targets if t != self.advertised and t != self.sock_path]
        if not targets:
            return
        mid = (len(targets) + 1) // 2
        for half in (targets[:mid], targets[mid:]):
            if not half:
                continue
            head, rest = half[0], half[1:]
            try:
                self._remote(head).notify(
                    "push_object", oid_hex, self.advertised, rest
                )
            except Exception:  # lint: swallow-ok(subtree self-heals via the pull path)
                pass

    def start_broadcast(self, oid_hex: str) -> int:
        """Driver-facing: pushes a LOCAL object to every other alive node;
        returns the number of targets."""
        try:
            nodes = self.gcs.call("list_nodes")
        except Exception:
            return 0
        targets = [
            n["sock"]
            for n in nodes
            if n.get("Alive") and n["NodeID"] != self.node_id
        ]
        self._relay_push(oid_hex, targets)
        return len(targets)

    def object_size(self, oid_hex: str) -> Optional[int]:
        oid = ObjectID.from_hex(oid_hex)
        size = self.store.raw_size(oid)
        if size is not None:
            return size
        with self._spill_lock:
            path = self._spilled.get(oid_hex)
        if path is not None:
            try:
                return os.path.getsize(path)
            except OSError:
                return None
        return None

    def fetch_object_chunk(self, oid_hex: str, offset: int, length: int) -> Optional[bytes]:
        """Serves one chunk of the framed payload (spilled objects read
        from disk without restoring). Chunk-granular admission: with many
        simultaneous requesters, streams interleave fairly instead of
        thrashing (reference: push_manager.h chunk scheduling)."""
        oid = ObjectID.from_hex(oid_hex)
        with self._serve_sem:
            piece = self.store.read_raw_chunk(oid, offset, length)
        if piece is not None:
            imet.OBJECT_BYTES_OUT.inc(len(piece))
            return piece
        with self._spill_lock:
            path = self._spilled.get(oid_hex)
        if path is not None:
            try:
                with open(path, "rb") as f:
                    f.seek(offset)
                    piece = f.read(length)
                imet.OBJECT_BYTES_OUT.inc(len(piece))
                return piece
            except OSError:
                return None
        return None

    def fetch_object(self, oid_hex: str) -> Optional[bytes]:
        """Serves the framed payload to a pulling raylet (the push half of
        the reference's object-manager transfer, push_manager.h:30); spilled
        primaries are served straight from disk."""
        raw = self.store.get_raw(ObjectID.from_hex(oid_hex))
        if raw is not None:
            imet.OBJECT_BYTES_OUT.inc(len(raw))
            return raw
        with self._spill_lock:
            path = self._spilled.get(oid_hex)
        if path is not None:
            try:
                with open(path, "rb") as f:
                    raw = f.read()
                imet.OBJECT_BYTES_OUT.inc(len(raw))
                return raw
            except OSError:
                return None
        return None

    # ---------------------------------------------------- eviction / spill
    def _spill_to(self, target_bytes: int) -> bool:
        with self._evict_lock:
            return self._spill_to_locked(target_bytes)

    def _spill_to_locked(self, target_bytes: int) -> bool:
        """Evicts replicas / spills primaries (seal order ≈ LRU) until pool
        usage is at or below target (reference: eviction_policy.h:160 +
        local_object_manager.h:41). One snapshot, one forward scan — a
        rescan per freed object would be O(n*k). Returns True when the
        target is met."""
        if self.store.bytes_in_use() <= target_bytes:
            return True
        with self._spill_lock:
            candidates = list(self._local_objects.items())
        for h, primary in candidates:
            if self.store.bytes_in_use() <= target_bytes:
                return True
            if not self._try_evict_one_locked(h, primary):
                continue
        return self.store.bytes_in_use() <= target_bytes

    def _try_evict_one_locked(self, h: str, primary: bool) -> bool:
        oid = ObjectID.from_hex(h)
        if not self.store.contains(oid):
            with self._spill_lock:
                self._local_objects.pop(h, None)
            return False
        if not primary:
            # A pulled replica: another node holds the primary, so a
            # plain delete is safe once the directory forgets us.
            if self.store.delete(oid):
                with self._spill_lock:
                    self._local_objects.pop(h, None)
                try:
                    self.gcs.call(
                        "remove_object_location", h, self.node_id, self.epoch
                    )
                except Exception:  # lint: swallow-ok(directory heals via node_sync batches)
                    pass
                return True
            return False  # pinned by a reader
        raw = self.store.get_raw(oid)
        if raw is None:
            with self._spill_lock:
                self._local_objects.pop(h, None)
            return False
        path = os.path.join(self._spill_dir, h)
        try:
            with open(path + ".tmp", "wb") as f:
                f.write(raw)
            os.replace(path + ".tmp", path)
        except OSError:
            return False  # disk full/unwritable
        if self.store.delete(oid):
            with self._spill_lock:
                self._spilled[h] = path
                self._local_objects.pop(h, None)
            imet.OBJECT_SPILL_TOTAL.inc()
            imet.OBJECT_SPILL_BYTES.inc(len(raw))
            return True
        try:
            os.unlink(path)  # pinned after all; keep the pool copy
        except OSError:
            pass
        return False

    def ensure_space(self, nbytes: int) -> bool:
        """Client-side ObjectStoreFullError escape hatch: make room for an
        allocation of `nbytes` — flush pending owner frees first (cheap),
        evict/spill only for what remains."""
        target = max(0, int(self.store.capacity() * 0.95) - int(nbytes))
        try:
            self.gcs.call("flush_frees")
        except Exception:  # lint: swallow-ok(advisory pre-pressure; eviction below is the guarantee)
            pass
        if self.store.bytes_in_use() <= target:
            return True
        return self._spill_to(target)

    def _restore(self, oid_hex: str) -> bool:
        """Brings a spilled object back into the pool (serialized with
        eviction so a concurrent spill cannot unlink the file mid-read)."""
        with self._evict_lock:
            with self._spill_lock:
                path = self._spilled.get(oid_hex)
            if path is None:
                return False
            try:
                with open(path, "rb") as f:
                    raw = f.read()
            except OSError:
                return False
            oid = ObjectID.from_hex(oid_hex)
            try:
                self.store.put_raw(oid, raw)
            except exc.ObjectStoreFullError:
                self._spill_to_locked(
                    max(0, int(self.store.capacity() * 0.95) - len(raw))
                )
                try:
                    self.store.put_raw(oid, raw)
                except exc.ObjectStoreFullError:
                    return False
            with self._spill_lock:
                self._spilled.pop(oid_hex, None)
            try:
                os.unlink(path)
            except OSError:
                pass
        imet.OBJECT_RESTORE_TOTAL.inc()
        self._notify_sealed([oid_hex])
        return True

    def notify_object(self, oid_hex: str) -> bool:
        self._notify_sealed([oid_hex])
        return True

    def delete_objects(self, oid_hexes: List[str]) -> int:
        """Frees objects from the local pool (the owner dropped its last
        reference; reference: plasma Delete + local_object_manager). Pinned
        objects (zero-copy readers in flight) are retried by the monitor."""
        freed = 0
        for h in oid_hexes:
            oid = ObjectID.from_hex(h)
            # _evict_lock: an in-flight spill of h must fully record its
            # file before we decide what to clean up.
            with self._evict_lock, self._spill_lock:
                self._local_objects.pop(h, None)
                spill_path = self._spilled.pop(h, None)
            if spill_path is not None:
                try:
                    os.unlink(spill_path)
                except OSError:
                    pass
            if self.store.delete(oid):
                freed += 1
            elif self.store.contains(oid):
                with self._buf_lock:
                    self._deferred_deletes.add(h)
        return freed

    # --------------------------------------------------- leased fast path
    def _direct_sock(self, worker_id: str) -> str:
        """The worker's direct-push UDS (created by the worker at boot,
        path derived identically on both sides)."""
        return os.path.join(
            os.path.dirname(self.sock_path) or ".", f"wkr_{worker_id}.sock"
        )

    def request_worker_lease(
        self, resources: Dict[str, float], env_key: str = ""
    ) -> dict:
        """Grants a worker lease for direct owner->worker task pushes: the
        resources are held for the lease lifetime and the raylet steps out
        of the per-task loop entirely (reference:
        normal_task_submitter.cc:354 RequestWorkerLease + the cached lease
        reuse at :555)."""
        resources = dict(resources or {"CPU": 1.0})
        if self._draining:
            # Draining node: shed fastpath owners toward a surviving node
            # (they fall back to raylet-mediated submission if the
            # cluster has nowhere else to lease).
            try:
                target = self.gcs.call("pick_node", resources, [self.node_id])
            except Exception:
                target = None
            if target is not None and target["node_id"] != self.node_id:
                return {"spill": target["sock"]}
            return {"retry": True}
        if not self._fits_total(resources):
            try:
                target = self.gcs.call("pick_node", resources, [self.node_id])
            except Exception:
                target = None
            if target is not None and target["node_id"] != self.node_id:
                return {"spill": target["sock"]}
            return {"retry": True}
        if (self._waiting or self._pending.qsize()) and not self._can_run_soon(
            {k: 2 * v for k, v in resources.items()}
        ):
            # Queued work exists and granting would take the last capacity:
            # let the queue drain first — a lease stealing it would be
            # revoked milliseconds later anyway (grant/revoke churn).
            return {"retry": True}
        if not self._try_acquire(resources):
            if self._cluster_size > 1:
                try:
                    target = self.gcs.call("pick_node", resources, [self.node_id])
                except Exception:
                    target = None
                if target is not None and target["node_id"] != self.node_id:
                    return {"spill": target["sock"]}
            return {"retry": True}
        w = self._checkout_worker(env_key)
        if w is None:
            self._release(resources)
            return {"retry": True}
        token = uuid.uuid4().hex
        self._leases[w.worker_id] = {
            "resources": resources,
            "granted_at": time.monotonic(),
            "token": token,
        }
        # The worker echoes the token on ITS return too, so a return from
        # a previous lease epoch can never pop a fresh re-grant.
        w.mailbox.put({"type": "direct", "token": token})
        return {
            "granted": {
                "worker_id": w.worker_id,
                "sock": self._direct_sock(w.worker_id),
                "token": token,
            }
        }

    def return_worker_lease(self, worker_id: str, token: Optional[str] = None) -> bool:
        """Lease handed back: release the held resources (token-matched)
        and pool the worker. Both sides of a lease return carry the grant
        token — the owner (fastpath janitor close) and the worker (direct
        mode exit) — and both may fire for the same lease, so the pop is
        token-guarded: a return from a previous lease epoch pools the
        worker but cannot clobber a lease the raylet already re-granted
        to a different owner. A tokenless return (the worker's lost-
        control-message belt re-entry, which never saw a grant) releases
        nothing; a lease whose every return was lost is reclaimed by the
        worker_poll sweep instead."""
        lease = self._leases.get(worker_id)
        if lease is not None and token is not None and lease.get("token") == token:
            self._leases.pop(worker_id, None)
            self._release(lease["resources"])
        if os.environ.get("RAY_TPU_DEBUG_DIRECT") == "1":
            _log.info("lease returned by %s", worker_id[:6])
        with self._workers_lock:
            w = self._workers.get(worker_id)
            if (
                w is not None
                and w.proc.poll() is None
                and w.actor_id is None
                and w.busy_with is None
            ):
                idle = self._idle.setdefault(w.env_key, [])
                if worker_id not in idle:
                    idle.append(worker_id)
        self._sched_wake.set()
        return True

    def _maybe_reclaim_leases(self, needed: Dict[str, float]) -> None:
        """Queued work cannot acquire resources while leases hold them:
        revoke leases (resources released NOW — bookkeeping oversubscribes
        briefly while the lease drains) and tell each worker to wind down.
        The worker relays a revoke frame to its owner, which drains
        outstanding pushes and closes; the worker then rejoins the pool
        (reference: the raylet-requested lease return in
        normal_task_submitter ReturnWorker/lease cancellation)."""
        now = time.monotonic()
        if now - getattr(self, "_last_reclaim", 0.0) < 0.1:
            return
        self._last_reclaim = now
        if os.environ.get("RAY_TPU_DEBUG_DIRECT") == "1":
            _log.info("reclaim check: leases=%s", list(self._leases))
        victims: List[str] = []
        for wid, lease in list(self._leases.items()):
            if now - lease.get("granted_at", 0.0) < 0.25:
                continue  # just granted; let it do some work first
            if any(lease["resources"].get(k, 0.0) > 0 for k in needed) or not needed:
                victims.append(wid)
                lease2 = self._leases.pop(wid, None)
                if lease2 is not None:
                    self._release(lease2["resources"])
        for wid in victims:
            threading.Thread(
                target=self._send_revoke, args=(wid,), daemon=True
            ).start()
        if victims:
            self._sched_wake.set()

    def _send_revoke(self, worker_id: str) -> None:
        """Tells a worker (over its direct socket) that its lease is
        revoked. Retries while the worker boots — a freshly-spawned leased
        worker takes ~1-2s to bind its direct socket, and a revoke racing
        that bind must not be lost (the lease resources are already
        released; an unrevoked worker would idle in direct mode forever)."""
        import socket as socketlib

        from .rpc import _send_msg

        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            with self._workers_lock:
                w = self._workers.get(worker_id)
            if w is None or w.proc.poll() is not None:
                return  # dead: the monitor reaps it
            try:
                s = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
                s.settimeout(2.0)
                s.connect(self._direct_sock(worker_id))
                _send_msg(s, pickle.dumps(("rv",)))
                s.close()
                return
            except OSError:
                time.sleep(0.1)

    def lease_active(self, worker_id: str) -> bool:
        return worker_id in self._leases

    def cancel_lease_task(self, worker_id: str, task_id: str, force: bool = False) -> bool:
        """Cancels a task the owner pushed directly to a leased worker.
        The raylet does not know the worker's queue, so it marks intent
        (the worker checks is_cancelled) and interrupts the process — the
        same signal protocol as the mailbox path."""
        self._mark_cancelled(task_id)
        with self._workers_lock:
            w = self._workers.get(worker_id)
        if w is None:
            return False
        if force:
            w.proc.kill()
        else:
            try:
                w.proc.send_signal(signal.SIGINT)
            except OSError:
                pass
        return True

    def fastpath_done(self, worker_id: str, sealed: List[str], events) -> bool:
        """Batched completion notifications from a leased/direct worker:
        seal locations for the GCS directory + waiters, task events for
        the state API. One-way and coalesced — never on the latency path."""
        if sealed:
            self._notify_sealed(sealed)
        for tid, state in events or ():
            self._task_event(tid, state)
        return True

    def actor_direct_sock(self, actor_id: str) -> Optional[str]:
        """The direct-push socket of the worker hosting this actor (None
        until the actor is ALIVE here)."""
        with self._actor_lock:
            a = self._actors.get(actor_id)
            if not a or a.get("state") != "ALIVE" or not a.get("worker_id"):
                return None
            wid = a["worker_id"]
        return self._direct_sock(wid)

    def debug_state(self) -> dict:
        """Scheduler/worker-pool introspection (ray-tpu status --verbose;
        reference: the raylet's DebugString dumped to raylet.out)."""
        with self._workers_lock:
            workers = {
                wid: {
                    "actor": w.actor_id,
                    "busy": (w.busy_with or {}).get("task_id"),
                    "env_key": w.env_key,
                    "alive": w.proc.poll() is None,
                }
                for wid, w in self._workers.items()
            }
            idle = {k: list(v) for k, v in self._idle.items()}
        with self._res_lock:
            avail = dict(self.available)
        return {
            "workers": workers,
            "idle": idle,
            "leases": {k: v["resources"] for k, v in self._leases.items()},
            "available": avail,
            "waiting": [e.get("task_id") for e in self._waiting],
            "pending_qsize": self._pending.qsize(),
            "pool": self._pool.stats() if self._pool is not None else {},
        }

    def flight_dump(self) -> dict:
        """`ray-tpu debug dump`: writes this raylet's flight-recorder ring
        to the flight dir and fans SIGUSR2 out to its worker processes
        (each worker's handler dumps its own ring). Returns the raylet's
        dump path + how many workers were signaled."""
        from ..observability import flight_recorder as _fr

        path = _fr.dump(reason=f"debug dump (raylet {self.node_id[:12]})")
        signaled = 0
        pids = [os.getpid()]
        with self._workers_lock:
            workers = list(self._workers.values())
        now = time.monotonic()
        for w in workers:
            # A worker binds its SIGUSR2 handler first thing in main(),
            # but a just-spawned interpreter still inside imports would be
            # KILLED by the signal's default disposition — skip the young.
            if now - w.spawned_at < 5.0:
                continue
            try:
                if w.proc.poll() is None:
                    # send_signal, not raw os.kill: PidHandle re-verifies
                    # /proc starttime so a recycled pid is never signaled.
                    w.proc.send_signal(signal.SIGUSR2)
                    signaled += 1
                    pids.append(w.proc.pid)
            except OSError:
                pass
        # `pids` lets the incident harvester attribute each flight dump it
        # stages to this node (and hence this node's clock offset).
        return {
            "path": path,
            "workers_signaled": signaled,
            "dir": _fr.flight_dir(),
            "pids": pids,
        }

    def profile(self, seconds: float = 5.0) -> dict:
        """`ray-tpu debug profile`: runs the in-process sampling profiler
        for `seconds` and dumps hottest stacks (JSON for the trace merge
        + text for humans). Blocking by design — the RPC returns when the
        dump is on disk; the server thread pool absorbs the wait."""
        from ..utils import sampling_profiler

        return sampling_profiler.run_for(
            seconds, name=f"raylet-{self.node_id[:12]}"
        )

    # -------------------------------------------------------------- logs
    _TAIL_FILTER_KEYS = (
        "component",
        "level",
        "task_id",
        "actor_id",
        "trace_id",
        "worker_id",
        "node_id",
        "grep",
        "since_ts",
    )

    def tail_logs(self, filters: Optional[dict] = None) -> List[dict]:
        """Filtered structured log records from this node's session log
        dir (`ray-tpu logs` fans this out cluster-wide). Raw worker
        prints appear too: the log monitor mirrors captured stdout/stderr
        lines into capture records with worker/actor attribution."""
        from ..observability import logs as _logs

        filters = dict(filters or {})
        tail = filters.pop("tail", 1000)
        clean = {
            k: v for k, v in filters.items() if k in self._TAIL_FILTER_KEYS
        }
        return _logs.read_records(self._log_dir, tail=tail, **clean)

    def _worker_attribution(self, worker_id: str) -> Tuple[Optional[int], Optional[str], Optional[str]]:
        """(pid, actor_id, actor_name) for one worker — the identity the
        capture path stamps onto its output lines."""
        with self._workers_lock:
            w = self._workers.get(worker_id)
        pid = getattr(getattr(w, "proc", None), "pid", None) if w else None
        aid = w.actor_id if w else None
        name = None
        if aid:
            with self._actor_lock:
                a = self._actors.get(aid)
                entry = (a or {}).get("creation_entry") or {}
            name = entry.get("name") or f"Actor({aid[:8]})"
        return pid, aid, name

    def _log_monitor_loop(self) -> None:
        """Tails worker_*.out / worker_*.err under the node's log dir:
        complete new lines are (1) published on the `logs` pubsub channel
        for the driver's attributed re-print and (2) re-logged as
        structured capture records (component stdout/stderr, the ORIGIN
        worker's ids attached) so the query paths see raw prints."""
        from ..observability import logs as _logs

        offsets: Dict[str, int] = {}
        while not self._stop.wait(0.2):
            try:
                names = sorted(os.listdir(self._log_dir))
            except OSError:
                continue
            for name in names:
                if not (
                    name.startswith("worker_")
                    and (name.endswith(".out") or name.endswith(".err"))
                ):
                    continue
                path = os.path.join(self._log_dir, name)
                try:
                    size = os.path.getsize(path)
                except OSError:
                    offsets.pop(name, None)
                    continue
                pos = offsets.get(name, 0)
                if pos > size:
                    pos = 0  # file truncated/replaced: start over
                if size <= pos:
                    continue
                try:
                    with open(path, "rb") as f:
                        f.seek(pos)
                        data = f.read(size - pos)
                except OSError:
                    continue
                cut = data.rfind(b"\n")
                if cut < 0:
                    continue  # no complete line yet
                offsets[name] = pos + cut + 1
                lines = data[: cut + 1].decode(errors="replace").splitlines()
                if not lines:
                    continue
                stream = name.rsplit(".", 1)[1]
                wid = name[len("worker_"): -len(".out")]
                pid, aid, actor_name = self._worker_attribution(wid)
                now = time.time()
                _logs.write_capture_records(
                    [
                        _logs.capture_record(
                            line, stream, self.node_id, wid, aid, pid, ts=now
                        )
                        for line in lines
                    ]
                )
                imet.LOG_LINES_PUBLISHED.inc(len(lines))
                # Chunked publish: one pubsub message must stay small
                # enough for the bounded retention window to hold a burst
                # from several workers at once.
                for i in range(0, len(lines), 200):
                    msg = {
                        "node_id": self.node_id,
                        "worker_id": wid,
                        "pid": pid,
                        "actor": actor_name,
                        "stream": stream,
                        "lines": lines[i: i + 200],
                    }
                    try:
                        self.gcs.notify("pubsub_publish", "logs", msg)
                    except Exception:
                        break  # GCS unreachable; lines stay on disk
            # Retention GC rides the monitor cadence, throttled to ~10 s.
            # Live workers' files (plus this node's daemons') are
            # protected: their writers hold the fds open, and an unlink
            # would silently void all their future output.
            now = time.monotonic()
            if now - getattr(self, "_last_log_gc", 0.0) > 10.0:
                self._last_log_gc = now
                try:
                    with self._workers_lock:
                        live = [f"worker_{wid}" for wid in self._workers]
                    _logs.gc_log_dir(
                        self._log_dir,
                        protect_prefixes=live + ["gcs", "raylet_", "zygote"],
                    )
                except Exception as e:
                    _log.debug("log-dir GC failed this round: %r", e)

    def _worker_log_tail(self, worker_id: str, n_lines: int = 50) -> str:
        """The last captured output lines of one worker (its .out/.err
        files) — the crash-postmortem tail appended to TaskError/actor
        death messages and written next to the flight dumps."""
        chunks: List[str] = []
        for ext in (".err", ".out"):
            path = os.path.join(self._log_dir, f"worker_{worker_id}{ext}")
            try:
                size = os.path.getsize(path)
                with open(path, "rb") as f:
                    f.seek(max(0, size - 16384))
                    data = f.read()
            except OSError:
                continue
            lines = data.decode(errors="replace").splitlines()[-n_lines:]
            if lines:
                chunks.append(f"--- worker_{worker_id}{ext} (tail) ---")
                chunks.extend(lines)
        return "\n".join(chunks)

    def _exit_status(self, w: "_Worker") -> str:
        """How a dead worker's process ended, in words: "exit code 1",
        "killed by SIGKILL". A zygote-forked worker's status is the
        daemon's to know (it is the parent); PidHandle.poll() says -1 for
        every death."""
        from .zygote import PidHandle

        code = w.proc.poll()
        if isinstance(w.proc, PidHandle):
            code = self._pool.zygote_exit_code(w.proc.pid) if self._pool is not None else None
        if code is None:
            return "exit status unknown"
        if code < 0:
            try:
                return f"killed by {signal.Signals(-code).name}"
            except ValueError:
                return f"killed by signal {-code}"
        return f"exit code {code}"

    def _write_postmortem(self, w: "_Worker", tail: str) -> Optional[str]:
        """Pairs a dying worker's output tail with the flight dumps:
        `ray-tpu debug dump` output and the trace merge both sweep the
        flight dir, so the post-mortem lands where the rings are."""
        from ..observability import flight_recorder as _fr

        try:
            d = _fr.flight_dir()
            os.makedirs(d, exist_ok=True)
            path = os.path.join(
                d, f"postmortem_{w.worker_id}_{time.time_ns() // 1000}.json"
            )
            payload = {
                "worker_id": w.worker_id,
                "node_id": self.node_id,
                "actor_id": w.actor_id,
                "exit_code": w.proc.poll(),
                "exit_status": self._exit_status(w),
                "task": (w.busy_with or {}).get("desc"),
                "tail": tail.splitlines(),
            }
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(payload, f, default=repr)
            os.replace(tmp, path)
            return path
        except OSError:
            return None

    # ----------------------------------------------------- worker service
    def worker_poll(self, worker_id: str) -> dict:
        """Long-poll: the worker's task mailbox (reference: the PushTask
        direction is inverted — workers pull — which removes per-worker
        server sockets)."""
        with self._workers_lock:
            w = self._workers.get(worker_id)
        if w is None:
            return {"type": "stop"}
        w.ready = True  # boot complete: this worker counts as warm
        if w.busy_with is not None and w.mailbox.empty():
            # A serial worker only polls after completing its current task,
            # and its completion report is processed before this poll — so
            # a poll arriving with busy_with still set means the reply that
            # carried this entry was lost (client reconnect+resend):
            # re-deliver instead of wedging the task forever.
            return {"type": "task", "entry": w.busy_with}
        try:
            msg = w.mailbox.get(timeout=POLL_TIMEOUT_S)
        except queue.Empty:
            msg = {"type": "noop"}
        if msg.get("type") != "direct" and worker_id in self._leases:
            # A leased worker never polls for pool work while serving its
            # lease — so a non-"direct" poll from a lease holder means the
            # worker already left direct mode and its return_worker_lease
            # notification was lost (observed under owner-janitor close
            # races). Without this reclaim the held CPUs leak FOREVER,
            # starving later placement groups / gang re-forms. The grace
            # window covers the grant→"direct"-delivery hop (the worker
            # may poll "noop" between the lease being recorded and the
            # mailbox message reaching it).
            lease = self._leases.get(worker_id)
            if (
                lease is not None
                and time.monotonic() - lease.get("granted_at", 0.0) > 2.0
            ):
                self.return_worker_lease(worker_id, lease.get("token"))
        return msg

    def worker_step(self, worker_id: str, done: Optional[dict] = None) -> dict:
        """Combined completion report + next-task poll: the serial worker
        loop costs ONE RPC per task instead of a done-notify plus a poll
        (reference: the PushTask reply carrying the result inverts the same
        two messages into one)."""
        if done is not None:
            self.worker_done(
                worker_id,
                done.get("ok", True),
                done.get("sealed"),
                done.get("task_id"),
            )
        return self.worker_poll(worker_id)

    def worker_done(
        self,
        worker_id: str,
        ok: bool,
        sealed: Optional[List[str]] = None,
        task_id: Optional[str] = None,
    ) -> bool:
        with self._workers_lock:
            w0 = self._workers.get(worker_id)
            if w0 is not None and task_id is not None and w0.last_done == task_id:
                # Duplicate report (RPC client reconnect re-sent the step):
                # task ids are unique, so matching last_done alone is
                # sufficient — and requiring busy_with None here would let
                # a dup clobber a NEWLY assigned task (mark it finished
                # without ever executing it).
                return True
        if sealed:
            # The task's return objects: wake local waiters + batch the
            # directory update (folded into this RPC so completion costs one
            # round trip, not one per return object).
            self._notify_sealed(sealed)
        if task_id is not None:
            self._cancelled.pop(task_id, None)
        with self._workers_lock:
            w = self._workers.get(worker_id)
            if w is None:
                return False
            entry = w.busy_with
            w.busy_with = None
            w.last_done = task_id
            if w.actor_id is None:
                idle = self._idle.setdefault(w.env_key, [])
                if worker_id not in idle:
                    idle.append(worker_id)
        if w.actor_id is not None and entry is None:
            # Actor task completion: remove the matching in-flight entry
            # (by task id — concurrent actors complete out of order).
            with self._actor_lock:
                a = self._actors.get(w.actor_id)
                if a and a["inflight"]:
                    idx = 0
                    if task_id is not None:
                        idx = next(
                            (
                                i
                                for i, e in enumerate(a["inflight"])
                                if e["task_id"] == task_id
                            ),
                            None,
                        )
                    if idx is not None:
                        done = a["inflight"].pop(idx)
                        self._task_event(
                            done["task_id"], "FINISHED" if ok else "FAILED"
                        )
        if entry is not None:
            self._task_event(entry["task_id"], "FINISHED" if ok else "FAILED")
            if entry["type"] == "task":
                self._release_entry(entry)
            elif entry["type"] == "actor_creation":
                aid = entry["actor_id"]
                if ok:
                    with self._actor_lock:
                        a = self._actors.get(aid)
                        if a:
                            a["state"] = "ALIVE"
                    # Coalesced registration: the actor_started report
                    # rides the batched GCS flush (wake-driven, so the
                    # added latency is sub-millisecond) — a launch storm
                    # costs the GCS one RPC per batch instead of one per
                    # actor. Duplicate-instance verdicts and fencing are
                    # handled at flush time (_flush_actor_started).
                    with self._buf_lock:
                        self._started_buf.append(aid)
                    self._buf_wake.set()
                else:
                    with self._actor_lock:
                        a = self._actors.get(aid)
                        if a:
                            a["state"] = "DEAD"
                    self._gcs_call_fenced(
                        "actor_died", "actor_died", aid,
                        "constructor failed", True, self.node_id,
                    )
        self._sched_wake.set()  # freed worker/resources: dispatch more
        return True

    # --------------------------------------------------------- scheduling
    def _scheduler_loop(self) -> None:
        while not self._stop.is_set():
            self._sched_wake.wait(timeout=0.05)
            self._sched_wake.clear()
            # Drain the whole burst: one entry per wakeup would make a
            # 1k-task submission storm O(n^2) in scheduler scans.
            while True:
                try:
                    self._waiting.append(self._pending.get_nowait())
                except queue.Empty:
                    break
            # Try to dispatch every waiting entry whose deps + resources are
            # ready (reference: local_task_manager.cc dispatch loop). One
            # malformed entry must not kill the scheduler thread (that
            # bricks the node): fail the entry instead.
            still: List[dict] = []
            for e in self._waiting:
                try:
                    if e.get("task_id") in self._cancelled:
                        # Checked BEFORE deps: a cancel must take effect even
                        # while the task waits on a never-arriving dep.
                        self._cancelled.pop(e["task_id"], None)
                        self._store_error_for(
                            e,
                            exc.TaskCancelledError(
                                f"{e.get('desc','task')} was cancelled"
                            ),
                        )
                        continue
                    if not self._deps_ready(e):
                        still.append(e)
                        continue
                    if not self._dispatch(e):
                        still.append(e)
                except Exception as sched_err:  # noqa: BLE001
                    try:
                        self._store_error_for(e, sched_err)
                    except Exception as store_err:
                        # The error object is load-bearing: without it the
                        # caller's get() hangs, so its loss must be loud.
                        _log.warning("could not store scheduling error for %s: %r",
                                     e.get("task_id", "?")[:8], store_err)
            self._waiting = still
            imet.SCHED_QUEUE_DEPTH.set(len(still) + self._pending.qsize())

    def _deps_ready(self, entry: dict) -> bool:
        for dep_hex in entry.get("deps", []):
            oid = ObjectID.from_hex(dep_hex)
            if not self.store.contains(oid):
                # Kick off a DEDUPED pull; non-blocking check next round.
                # (The scheduler rescans waiting entries ~20x/s — a raw
                # thread per miss per scan once fork-bombed the node.)
                self._pull_async(dep_hex)
                return False
        return True

    def _obs_dispatch(self, entry: dict) -> None:
        ts = entry.pop("_q_ts", None)
        if ts is not None:
            imet.SCHED_DISPATCH_LATENCY.observe((time.monotonic() - ts) * 1e3)
        _flight_record("sched.dispatch", (entry.get("task_id") or "")[:16])
        # The middle rung of the submit->schedule->execute flow ladder:
        # a near-zero-width span at the dispatch decision, chained into
        # the entry's flow id as a Perfetto step event. Tracing off =
        # one dict lookup.
        ctx = entry.get("trace_ctx")
        if ctx and entry.get("type") == "task" and _tracing.is_enabled():
            with _tracing.continue_context(
                dict(ctx, flow=None),  # step, not head: flow_in stays unset
                f"schedule {entry.get('desc', 'task')}",
                {
                    "task_id": entry.get("task_id", ""),
                    "node_id": self.node_id[:12],
                    "flow_step": ctx.get("flow"),
                },
            ):
                pass

    def _dispatch(self, entry: dict) -> bool:
        kind = entry["type"]
        if entry.get("_node_incarnation", self._incarnation) is not self._incarnation:
            # Queued by a since-fenced incarnation (it sat dep-blocked in
            # _waiting across the fence; the token regenerates at fence
            # START, so this holds even mid-fence and when re-registration
            # is still failing): the GCS already failed this node's tasks
            # at death and the owner has retried elsewhere —
            # executing it here too would double-apply its side effects.
            # Dropped SILENTLY: a FAILED event here would carry the fresh
            # epoch, slip past the GCS fence, and clobber a live retry's
            # RUNNING record (the owner would resubmit a second time while
            # the retry still runs, and the retry's eventual FINISHED
            # would be blocked by the terminal-state rule). No error
            # object either: the owner's retry reuses these return ids.
            _flight_record("sched.drop_stale_epoch", (entry.get("task_id") or "")[:16])
            return True
        if entry.get("task_id") in self._cancelled:
            self._cancelled.pop(entry["task_id"], None)
            self._store_error_for(
                entry,
                exc.TaskCancelledError(
                    f"{entry.get('desc','task')} was cancelled before dispatch"
                ),
            )
            return True
        if kind == "task":
            if self._fail_if_unschedulable(entry):
                return True
            if not self._try_acquire_entry(entry):
                self._maybe_reclaim_leases(entry["resources"])
                return False
            w = self._checkout_worker(self._env_key(entry))
            if w is None:
                self._release_entry(entry)
                return False
            self._obs_dispatch(entry)
            w.busy_with = entry
            self._task_event(entry["task_id"], "RUNNING")
            w.mailbox.put({"type": "task", "entry": entry})
            return True
        if kind == "actor_creation":
            if self._fail_if_unschedulable(entry):
                with self._actor_lock:
                    a = self._actors.get(entry["actor_id"])
                    if a:
                        a["state"] = "DEAD"
                self._gcs_call_fenced(
                    "actor_died", "actor_died", entry["actor_id"],
                    "placement bundle gone", True, self.node_id,
                )
                return True
            if not self._try_acquire_entry(entry):
                self._maybe_reclaim_leases(entry["resources"])
                return False
            # Prefer converting an IDLE pooled worker over spawning: a
            # fresh python process pays ~2s of interpreter+jax startup on
            # this image, the pool already paid it (reference: the shared
            # worker_pool serving actor creations, worker_pool.h PopWorker).
            # The span parents to the driver's actor_launch span via the
            # entry's propagated trace_ctx (VERDICT: the per-phase launch
            # breakdown `ray-tpu timeline` surfaces).
            env_key = self._env_key(entry)
            with _tracing.continue_context(
                entry.get("trace_ctx"),
                "actor_launch.worker_spawn",
                {"actor_id": entry.get("actor_id", "")},
            ) as sp:
                with self._workers_lock:
                    w = self._pop_idle_locked(env_key)
                    if w is not None:
                        w.actor_id = entry["actor_id"]
                if w is None:
                    w = self._spawn_worker(
                        actor_id=entry["actor_id"],
                        env_key=env_key,
                        runtime_env=entry.get("runtime_env"),
                    )
                    if sp is not None:
                        sp["attrs"]["mode"] = "spawned"
                else:
                    # Warm-path hit: the launch adopted a live pooled
                    # worker — worker_spawn collapses to this pop.
                    if self._pool is not None:
                        self._pool.note_hit("idle")
                    if sp is not None:
                        sp["attrs"]["mode"] = "pooled"
            self._obs_dispatch(entry)
            with self._actor_lock:
                a = self._actors.get(entry["actor_id"])
                if a is not None:
                    a["worker_id"] = w.worker_id
                    a["resources_held"] = True
                    w.actor_rec = a
            w.busy_with = entry
            self._task_event(entry["task_id"], "RUNNING")
            w.mailbox.put({"type": "task", "entry": entry})
            return True
        if kind == "actor_task":
            aid = entry["actor_id"]
            with self._actor_lock:
                a = self._actors.get(aid)
                if a is None or a["state"] == "DEAD":
                    self._store_error_for(entry, RuntimeError(f"actor {aid[:8]} dead"))
                    return True
                wid = a.get("worker_id")
            if wid is None:
                return False  # still constructing
            with self._workers_lock:
                w = self._workers.get(wid)
            if w is None:
                return False
            # Actor mailbox preserves submission order; the worker executes
            # serially (reference: actor_scheduling_queue.h ordered queue).
            with self._actor_lock:
                a["inflight"].append(entry)
            self._obs_dispatch(entry)
            self._task_event(entry["task_id"], "RUNNING")
            w.mailbox.put({"type": "task", "entry": entry})
            return True
        return True

    def _env_key(self, entry: dict) -> str:
        """Composite worker-env descriptor: runtime_env + the TPU chip
        binding of the entry's bundle. Workers are pooled per descriptor
        (reference: worker_pool PopWorker matching runtime_env_hash +
        accelerator visibility)."""
        desc: Dict[str, Any] = {}
        if entry.get("runtime_env"):
            desc["runtime_env"] = entry["runtime_env"]
        key = self._entry_bundle_key(entry)
        if key is not None:
            with self._res_lock:
                b = self._bundles.get(key)
                chips = list(b.get("chips") or ()) if b else None
            if chips:
                desc["tpu"] = {
                    "chips": chips,
                    "slice": self.labels.get("slice_name", ""),
                    "worker_index": int(self.labels.get("worker_index", 0)),
                }
        if not desc:
            return ""
        return json.dumps(desc, sort_keys=True)

    def _pop_idle_locked(self, env_key: str) -> Optional["_Worker"]:
        """Pops a LIVE idle worker for this env (callers hold
        _workers_lock); shared by task checkout and actor-creation
        conversion so liveness checks stay in one place. READY workers
        (boot complete, first poll seen) are preferred: a refill-spawned
        worker enters the pool at fork time, and handing a launch a
        still-booting worker serializes the launch behind that boot —
        seconds on a loaded box — while booted pool-mates sit idle."""
        idle = self._idle.setdefault(env_key, [])
        # Front-to-back: refills APPEND, so ready (oldest) workers sit at
        # the head and the first hit is O(1) amortized — a back-to-front
        # scan would walk the freshly-forked un-ready tail doing a /proc
        # liveness read per entry under _workers_lock on every dispatch.
        for i in range(len(idle)):
            w = self._workers.get(idle[i])
            if w is not None and w.ready and w.proc.poll() is None and w.actor_id is None:
                del idle[i]
                return w
        while idle:
            wid = idle.pop()
            w = self._workers.get(wid)
            if w is not None and w.proc.poll() is None and w.actor_id is None:
                return w
        return None

    def _checkout_worker(self, env_key: str = "") -> Optional[_Worker]:
        with self._workers_lock:
            w = self._pop_idle_locked(env_key)
            if w is not None:
                if self._pool is not None:
                    self._pool.note_hit("idle")
                return w
            n_task_workers = sum(1 for w in self._workers.values() if w.actor_id is None)
            if n_task_workers < self._max_task_workers:
                return self._spawn_worker_locked(env_key=env_key)
            # At the cap with only mismatched-env idle workers: retire one
            # and spawn for this env (reference: worker_pool killing idle
            # workers with stale runtime envs).
            for k, lst in self._idle.items():
                if k != env_key and lst:
                    wid = lst.pop()
                    old = self._workers.pop(wid, None)
                    if old is not None:
                        old.mailbox.put({"type": "stop"})
                    return self._spawn_worker_locked(env_key=env_key)
        return None

    def _default_spawn_spec(self) -> Tuple[str, List[str], Dict[str, str], str]:
        """(worker_id, argv, env, log_base) — the SINGLE assembly of a
        worker's base spawn identity, shared by _spawn_worker_locked and
        the zygote batch-prestart path (two copies would silently drift:
        an env var added to one class of 'default' worker and not the
        other)."""
        worker_id = uuid.uuid4().hex[:12]
        env = dict(os.environ)
        env["RAY_TPU_WORKER"] = "1"
        # Workers write their structured JSONL log next to their captured
        # stdout/stderr, under this node's session log dir.
        env["RAY_TPU_LOG_DIR"] = self._log_dir
        log_base = os.path.join(self._log_dir, f"worker_{worker_id}")
        argv = [
            self.sock_path,
            self.store_path,
            self.gcs_sock,
            worker_id,
            self.node_id,
        ]
        return worker_id, argv, env, log_base

    def _prestart_idle(self, n: int) -> int:
        """Spawns `n` default-env idle workers into the pool (boot
        prestart + the pool manager's refill). Batched through the
        zygote when it is up — ONE socket round trip forks all of them,
        each preferentially taking a parked pre-forked child — with a
        per-worker Popen fallback. Prestarted workers MUST enter the
        idle pool: they are otherwise invisible to _checkout_worker
        while still counting against _max_task_workers — a prestart that
        fills the cap before the first submit would leave the node
        unable to dispatch anything, ever."""
        if n <= 0:
            return 0
        from .zygote import PidHandle, ZygoteClient

        pool = self._pool
        if pool is not None and CONFIG.worker_zygote:
            specs, wids = [], []
            for _ in range(n):
                wid, argv, env, log_base = self._default_spawn_spec()
                specs.append(
                    ZygoteClient.spawn_spec(
                        argv, env, log_base + ".out", log_base + ".err"
                    )
                )
                wids.append(wid)
            try:
                t0 = time.perf_counter()
                results = pool.zygote_spawn_batch(specs)
                per_ms = (time.perf_counter() - t0) * 1e3 / max(1, len(results))
                with self._workers_lock:
                    for wid, (pid, _warm) in zip(wids, results):
                        w = _Worker(wid, PidHandle(pid), env_key="")
                        self._workers[wid] = w
                        self._idle.setdefault("", []).append(wid)
                for _pid, warm in results:
                    mode = "prefork" if warm else "zygote"
                    imet.WORKER_SPAWN_TOTAL.inc(mode=mode)
                    imet.ZYGOTE_FORK_LATENCY.observe(per_ms, mode=mode)
                self._sched_wake.set()
                return len(results)
            except Exception as e:
                _log.debug("batched prestart fell back to popen: %r", e)
        spawned = 0
        for _ in range(n):
            if self._stop.is_set():
                break
            try:
                with self._workers_lock:
                    w = self._spawn_worker_locked(env_key="", _pool_refill=True)
                    self._idle.setdefault("", []).append(w.worker_id)
                spawned += 1
            except Exception as e:  # noqa: BLE001
                _log.warning("worker prestart failed: %r", e)
                break
        if spawned:
            self._sched_wake.set()
        return spawned

    def _retire_idle(self, k: int) -> int:
        """Stops up to `k` idle pooled workers (pool-manager shrink once
        demand decays). Popped out of the idle lists under the lock
        first, so a concurrent checkout can never adopt a worker that
        was just told to stop."""
        retired = 0
        with self._workers_lock:
            for lst in self._idle.values():
                while lst and retired < k:
                    wid = lst.pop(0)  # oldest first
                    w = self._workers.get(wid)
                    if w is None or w.proc.poll() is not None:
                        continue
                    w.mailbox.put({"type": "stop"})
                    retired += 1
                if retired >= k:
                    break
        return retired

    def _spawn_worker(
        self, actor_id: Optional[str] = None, env_key: str = "", runtime_env=None
    ) -> _Worker:
        with self._workers_lock:
            return self._spawn_worker_locked(actor_id, env_key, runtime_env)

    def _spawn_worker_locked(
        self,
        actor_id: Optional[str] = None,
        env_key: str = "",
        runtime_env=None,
        _pool_refill: bool = False,
    ) -> _Worker:
        worker_id, worker_args, env, log_base = self._default_spawn_spec()
        desc = json.loads(env_key) if env_key else {}
        if runtime_env:
            desc.setdefault("runtime_env", runtime_env)
        renv = desc.get("runtime_env")
        py_exe = sys.executable
        if renv:
            # Materialize dependencies BEFORE spawn: package URIs extract
            # into the node cache and a pip spec builds/reuses a venv whose
            # python runs this worker (reference: runtime_env_agent
            # building the env ahead of worker start; pip.py venv plugin).
            # Raises on setup failure — the scheduler converts that into a
            # stored error on the triggering entry.
            from .runtime_env import materialize_runtime_env

            py_exe, renv = materialize_runtime_env(renv, self.gcs)
            # Apply env_vars at spawn; working_dir is applied by the worker
            # itself (reference: runtime_env_agent building the env).
            for k, v in (renv.get("env_vars") or {}).items():
                env[str(k)] = str(v)
            env["RAY_TPU_RUNTIME_ENV"] = json.dumps(renv)
        tpu = desc.get("tpu")
        if tpu and self._tpu_manager is not None:
            # Chip isolation for co-located gangs: the accelerator manager
            # owns the env-var protocol (reference:
            # _private/accelerators/tpu.py set_accelerator_visible).
            env.update(
                self._tpu_manager.worker_visibility_env(
                    tpu["chips"],
                    slice_name=tpu.get("slice"),
                    worker_index=tpu.get("worker_index", 0),
                )
            )
        prefix = (renv or {}).get("_command_prefix")
        if (
            self._pool is not None
            and py_exe == sys.executable
            and not prefix
            and not (renv or {}).get("env_vars")
        ):
            # Fast path: fork from the pre-warmed zygote — only for
            # workers running THIS interpreter, no container wrap, and
            # no user env_vars: the zygote pre-imported the worker stack,
            # so import-time vars (JAX_*, RAY_TPU_* config) set after the
            # fork would silently not take effect; those envs Popen.
            # A parked pre-forked child serves the request in ~1-2 ms
            # (pool hit, tier=prefork); an empty parked pool pays the
            # ~10 ms fork (miss, mode=zygote).
            try:
                t0 = time.perf_counter()
                pid, warm = self._pool.zygote_spawn(
                    worker_args, env, log_base + ".out", log_base + ".err"
                )
                mode = "prefork" if warm else "zygote"
                imet.ZYGOTE_FORK_LATENCY.observe(
                    (time.perf_counter() - t0) * 1e3, mode=mode
                )
                imet.WORKER_SPAWN_TOTAL.inc(mode=mode)
                if not _pool_refill:
                    if warm:
                        self._pool.note_hit("prefork")
                    else:
                        self._pool.note_miss("zygote")
                from .zygote import PidHandle

                w = _Worker(worker_id, PidHandle(pid), env_key=env_key)
                w.actor_id = actor_id
                self._workers[worker_id] = w
                return w
            except Exception:  # lint: swallow-ok(pool manager was notified and respawns; THIS spawn falls back to Popen below)
                pass
        out_f = open(log_base + ".out", "ab", buffering=0)
        err_f = open(log_base + ".err", "ab", buffering=0)
        argv = [py_exe, "-m", "ray_tpu.core.worker_proc", *worker_args]
        # Container plugin (image_uri): the whole worker command runs
        # inside `podman run ...` (reference: image_uri.py wrapping the
        # worker command; runtime_env.ImageUriPlugin builds the prefix).
        if prefix:
            from .runtime_env import ImageUriPlugin

            expanded: List[str] = []
            for part in prefix:
                if part == ImageUriPlugin.ENV_ARGS_SENTINEL:
                    # Forward every env var this spawn ADDED beyond the
                    # inherited process env (docker has no --env-host).
                    for k, v in env.items():
                        if os.environ.get(k) != v:
                            expanded += ["--env", f"{k}={v}"]
                else:
                    expanded.append(part)
            argv = expanded + argv
        try:
            t0 = time.perf_counter()
            proc = subprocess.Popen(
                argv,
                env=env,
                stdout=out_f,
                stderr=err_f,
            )
            imet.ZYGOTE_FORK_LATENCY.observe(
                (time.perf_counter() - t0) * 1e3, mode="popen"
            )
            imet.WORKER_SPAWN_TOTAL.inc(mode="popen")
            if self._pool is not None and not _pool_refill:
                self._pool.note_miss("popen")
        finally:
            out_f.close()
            err_f.close()
        w = _Worker(worker_id, proc, env_key=env_key)
        w.actor_id = actor_id
        self._workers[worker_id] = w
        return w

    # ---------------------------------------------------------- failures
    def _store_error_for(self, entry: dict, error: BaseException) -> None:
        sealed = []
        for rid_hex in entry["return_ids"]:
            oid = ObjectID.from_hex(rid_hex.decode() if isinstance(rid_hex, bytes) else rid_hex)
            try:
                err_obj = StoredError(error, entry.get("desc", ""))
                try:
                    self.store.put(oid, err_obj)
                except exc.ObjectStoreFullError as e:
                    # The error object MUST land or the caller's get() hangs
                    # and mislabels the failure as object loss.
                    self.ensure_space(e.nbytes)
                    self.store.put(oid, err_obj)
                sealed.append(oid.hex())
            except Exception as put_err:
                # Same contract as the comment above: a return slot with no
                # error object hangs the caller — make the loss visible.
                _log.warning("failed to store error object for %s: %r",
                             oid.hex()[:8], put_err)
        self._notify_sealed(sealed)
        self._task_event(entry["task_id"], "FAILED", reason=str(error))

    def _monitor_loop(self) -> None:
        """Detects worker-process death; fails in-flight work and drives the
        actor restart state machine (reference: node_manager worker-failure
        handling + gcs_actor_manager.h:548)."""
        while not self._stop.wait(CONFIG.worker_monitor_interval_s):
            dead: List[_Worker] = []
            with self._workers_lock:
                for w in list(self._workers.values()):
                    if w.proc.poll() is not None:
                        dead.append(w)
                        del self._workers[w.worker_id]
                        idle_list = self._idle.get(w.env_key)
                        if idle_list and w.worker_id in idle_list:
                            idle_list.remove(w.worker_id)
            for w in dead:
                lease = self._leases.pop(w.worker_id, None)
                if lease is not None:
                    # Leased worker died: hand back the lease's resources;
                    # the owner's direct socket EOF drives task retries.
                    self._release(lease["resources"])
                try:
                    os.unlink(self._direct_sock(w.worker_id))
                except OSError:
                    pass
                entry = w.busy_with
                # Crash post-mortem: on an ABNORMAL exit, capture the
                # dying process's last output lines — appended to the
                # error surfaced to the owner, written next to the
                # flight dumps, and reported to the cluster error table.
                # DELIBERATE kills (kill_actor marks the actor DEAD before
                # signaling; force-cancel marks the task cancelled) are
                # normal teardown, not crashes — reporting them would bury
                # real failures in `ray-tpu status` noise.
                deliberate = False
                if w.actor_id is not None:
                    with self._actor_lock:
                        a = self._actors.get(w.actor_id)
                    deliberate = a is not None and a.get("state") == "DEAD"
                if entry is not None and entry.get("task_id") in self._cancelled:
                    deliberate = True
                tail = status = ""
                if not deliberate and w.proc.poll() not in (0, None):
                    tail = self._worker_log_tail(w.worker_id)
                    status = self._exit_status(w)
                    self._write_postmortem(w, tail)
                    if entry is not None or w.actor_id is not None:
                        _log.warning(
                            "worker %s died abnormally (%s, task=%s)",
                            w.worker_id,
                            status,
                            (entry or {}).get("desc"),
                        )
                        try:
                            self.gcs.notify(
                                "report_error",
                                {
                                    "type": "worker_crash",
                                    "node_id": self.node_id,
                                    "worker_id": w.worker_id,
                                    "actor_id": w.actor_id,
                                    "error": (
                                        f"worker died ({status})"
                                        + (
                                            f" executing {entry.get('desc', 'task')}"
                                            if entry
                                            else ""
                                        )
                                    ),
                                    "log_tail": tail[-4000:],
                                },
                            )
                        except Exception:  # lint: swallow-ok(postmortem report is best-effort; death handling below is the guarantee)
                            pass
                # What the owner reads in WorkerCrashedError / ActorDiedError:
                # how the process ended, then the last lines it wrote.
                tail_note = f" ({status})" if status else ""
                if tail:
                    tail_note += f"; last output:\n{tail[-2000:]}"
                if entry is not None:
                    if entry["type"] == "task":
                        self._release_entry(entry)
                    mr = entry.get("max_retries", 0)
                    if entry.get("task_id") in self._cancelled:
                        self._cancelled.pop(entry["task_id"], None)
                        self._store_error_for(
                            entry,
                            exc.TaskCancelledError(
                                f"{entry.get('desc','task')} was cancelled"
                            ),
                        )
                    elif entry["type"] == "task" and (
                        mr < 0 or mr - entry.get("attempt", 0) > 0
                    ):
                        # Raylet-side retry on worker death (reference:
                        # task_manager.h:250-256 RetryTask — the owner's
                        # TaskManager there; here the raylet re-queues since
                        # the deps are still local).
                        entry["attempt"] = entry.get("attempt", 0) + 1
                        imet.TASKS_RETRIED.inc()
                        self._task_event(
                            entry["task_id"], "QUEUED", retry=entry["attempt"]
                        )
                        self._enqueue(entry)
                    else:
                        self._store_error_for(
                            entry,
                            exc.WorkerCrashedError(
                                f"worker died executing {entry.get('desc','task')}"
                                f"{tail_note}"
                            ),
                        )
                if w.actor_id is not None:
                    self._on_actor_worker_death(w, tail_note)
            with self._buf_lock:
                retry, self._deferred_deletes = list(self._deferred_deletes), set()
            if retry:
                self.delete_objects(retry)
            # Background pressure relief: spill ahead of allocation failures.
            cap = self.store.capacity()
            if self.store.bytes_in_use() > CONFIG.spill_threshold * cap:
                self._spill_to(int(0.75 * CONFIG.spill_threshold * cap))

    def _on_actor_worker_death(self, w: _Worker, tail_note: str = "") -> None:
        aid = w.actor_id
        with self._actor_lock:
            a = self._actors.get(aid)
            if a is None:
                return
            if (w.actor_rec is not None and a is not w.actor_rec) or a.get(
                "worker_id"
            ) not in (None, w.worker_id):
                # The record was already re-created (a kill-with-restart's
                # fresh instance landed back on this node before the old
                # worker's death was processed): this death belongs to the
                # BYGONE instance — touching the fresh record would
                # misattribute it and trigger a second restart. The
                # identity compare catches even a still-PENDING fresh
                # record (worker_id None) — create_actor installs a new
                # dict, so `is` distinguishes incarnations exactly.
                return
            was_dead = a["state"] == "DEAD"  # deliberate kill_actor()
            a["state"] = "DEAD"
            a["worker_id"] = None
            inflight, a["inflight"] = list(a.get("inflight", [])), []
            creation_entry = a.get("creation_entry")
            held, a["resources_held"] = a.get("resources_held", False), False
        # Fail everything dispatched or queued to the dead worker so gets
        # raise instead of hanging (reference: ActorDiedError path).
        err = RuntimeError(
            f"actor {aid[:8]} died (worker process exited){tail_note}"
        )
        for e in inflight:
            self._store_error_for(e, err)
        while True:
            try:
                m = w.mailbox.get_nowait()
            except queue.Empty:
                break
            if m.get("type") == "task":
                self._store_error_for(m["entry"], err)
        if held and creation_entry is not None:
            self._release_entry(creation_entry)
        if was_dead:
            return  # killed deliberately; GCS already informed, no restart
        # Restart (place + create + budget charge) is the GCS's job: it
        # re-places off-thread via the same _restart_actor path node
        # death uses. _FENCED: this incarnation was fenced while the
        # worker died — the GCS has already rescheduled the actor, and
        # reporting would hijack the healthy successor; die as a member.
        self._gcs_call_fenced(
            "actor_died", "actor_died", aid,
            f"worker process died{tail_note[:1200]}", False, self.node_id,
        )

    # ---------------------------------------------------------- lifecycle
    def _heartbeat_loop(self) -> None:
        while not self._stop.wait(CONFIG.heartbeat_interval_s):
            rule = _chaos_inject("raylet.heartbeat", self.node_id)
            if rule is not None and rule.action == "kill":
                # Whole-node crash: SIGKILL the raylet daemon. Workers
                # orphan (their poll loop exits on raylet loss), the GCS
                # health loop expires the node, and gang reschedule /
                # autoscaler replacement take over — the un-noticed half
                # of the preemption story.
                _chaos_kill("raylet.heartbeat", self.node_id)
            with self._res_lock:
                avail = dict(self.available)
            with self._workers_lock:
                n_workers = len(self._workers)
                n_busy = sum(
                    1 for w in self._workers.values() if w.busy_with is not None
                )
                n_idle = sum(len(v) for v in self._idle.values())
            with self._spill_lock:
                n_spilled = len(self._spilled)
            imet.WORKER_POOL_IDLE.set(n_idle)
            imet.WORKER_POOL_BUSY.set(n_busy)
            imet.WORKER_POOL_LEASED.set(len(self._leases))
            stats = {
                "bytes_in_use": self.store.bytes_in_use(),
                "num_objects": self.store.num_objects(),
                "num_spilled": n_spilled,
                "num_workers": n_workers,
                # Wall-clock sample for the GCS's clock-offset estimate:
                # the incident-bundle merger shifts this node's flight/span
                # timestamps onto the GCS clock using the offset derived
                # from (gcs_now - wall_ts) at receive time.
                "wall_ts": time.time(),
            }
            if self._pool is not None:
                # Pool health rides the heartbeat: `ray-tpu status
                # --verbose` renders it per node without an extra RPC.
                stats["pool"] = self._pool.stats()
            if self._draining:
                # Propagate raylet-initiated drains (chaos, local admin)
                # into the GCS node record; GCS-initiated drains already
                # set it there first.
                stats["draining"] = True
            send_avail, send_stats = self._hb_codec.encode(avail, stats)
            try:
                # _FENCED: the GCS declared this node dead while a
                # partition hid its heartbeats — this incarnation is a
                # zombie; _fence kills its workers and rejoins fresh
                # (never resurrect in place). Not a dict, so it skips the
                # reply handling below.
                reply = self._gcs_call_fenced(
                    "heartbeat", "heartbeat", self.node_id, send_avail, send_stats
                )
                if isinstance(reply, dict):
                    self._cluster_size = reply.get("nodes", self._cluster_size)
                    if self._pool is not None:
                        # Demand hint: pending actors the GCS placed on
                        # this node + the autoscaler forecast share.
                        self._pool.set_hint(int(reply.get("pool_hint", 0) or 0))
                    if not reply.get("ok", True):
                        # The GCS restarted without our registration (lost
                        # or stale snapshot): re-register (reference:
                        # RayletNotifyGCSRestart, core_worker.proto:441).
                        reg = self.gcs.call(
                            "register_node",
                            self.node_id,
                            self.advertised,
                            self.store_path,
                            self.total,
                            self.labels,
                        )
                        if isinstance(reg, dict):
                            self.epoch = reg.get("epoch", self.epoch)
                        # The restarted GCS has no stats for this node:
                        # the next beat must resend everything.
                        self._hb_codec.force_full()
            except Exception as e:
                # Missed heartbeats are how this node gets declared dead:
                # say so while it is still alive to say anything.
                _log.debug("heartbeat to GCS failed (retried next tick): %r", e)
                # The codec advanced its baselines for a beat the GCS
                # never applied — deltas against them would silently skip
                # this tick's changes.
                self._hb_codec.force_full()

    def ping(self) -> str:
        return "pong"

    def _gcs_call_fenced(self, origin: str, method: str, *args) -> Any:
        """One epoch-fenced GCS mutation: captures self.epoch BEFORE the
        call, appends it as the RPC's epoch argument, and on
        StaleNodeEpochError runs the fence reaction for exactly the
        incarnation that spoke (the early capture is what lets _fence
        ignore rejections a completed fence already superseded). Returns
        _FENCED on rejection, the RPC result otherwise."""
        ep = self.epoch
        try:
            return self.gcs.call(method, *args, ep)
        except exc.StaleNodeEpochError:
            self._fence(origin, ep)
            return _FENCED

    def _fence(self, origin: str, epoch: Optional[int] = None) -> None:
        """Reaction to StaleNodeEpochError: the GCS declared this
        incarnation dead (partition, drain deadline) and has already
        rescheduled its actors and dropped its object locations. Acting
        on any of that state would be split-brain, so this node DIES AS A
        MEMBER — every worker is killed (duplicate named-actor instances
        die here), leases/bundles/chip leases and plasma pins are
        dropped, queued work is discarded (owners recover through the
        task table) — and then rejoins as a FRESH incarnation with a new
        epoch, indistinguishable from a brand-new node_added.

        `epoch` is the epoch the REJECTED RPC carried: when another
        thread's fence already completed (self.epoch advanced), the
        rejection is about a bygone incarnation and must be ignored —
        re-fencing here would SIGKILL the fresh incarnation's workers
        with the GCS none the wiser (no node_dead ever fires for them)."""
        with self._fence_guard:
            if self._fencing or self._stop.is_set():
                return
            if epoch is not None and epoch != self.epoch:
                return  # a completed fence already superseded this rejection
            self._fencing = True
            self._max_fenced_epoch = max(self._max_fenced_epoch, self.epoch)
            # New incarnation token at fence START: entries stamped by the
            # old life are droppable at dispatch immediately — during the
            # fence window itself, and even if re-registration below keeps
            # failing (self.epoch only advances on a successful register).
            self._incarnation = object()
        old_epoch = self.epoch
        try:
            _flight_record("node.fence", (self.node_id[:12], old_epoch, origin))
            _log.warning(
                "node %s (epoch %s) fenced by the GCS via %s: killing "
                "workers, dropping leases, re-registering fresh",
                self.node_id[:12], old_epoch, origin,
            )
            # Workers first: the old incarnation's actor instances and
            # in-flight tasks must stop producing side effects. Removed
            # from the table BEFORE the kill so the monitor loop never
            # reports their deaths as crashes of the (already-moved)
            # actor records.
            with self._workers_lock:
                victims = list(self._workers.values())
                self._workers.clear()
                self._idle.clear()
            for w in victims:
                try:
                    w.proc.kill()
                except OSError:
                    pass
            for w in victims:
                # Reap: these workers left the monitor's table above, so
                # nothing else will wait() them — an unreaped Popen child
                # lingers as a defunct /proc entry that looks like a
                # surviving zombie instance. (Zygote-forked workers are
                # reaped by the zygote; their PidHandle has no wait.)
                waiter = getattr(w.proc, "wait", None)
                if waiter is not None:
                    try:
                        waiter(timeout=2.0)
                    except Exception:  # lint: swallow-ok(best-effort reap of a SIGKILLed child)
                        pass
            self._leases.clear()
            with self._actor_lock:
                self._actors.clear()
            with self._res_lock:
                self._bundles.clear()
                self.available = dict(self.total)
                self._free_chips = set(self._all_chips)
            with self._seen_lock:
                self._seen_submits.clear()
            # Queued work belongs to the old life; owners have already
            # been failed over by the GCS (tasks marked FAILED at node
            # death). Entries parked in _waiting are fenced at dispatch
            # by their stale epoch stamp.
            try:
                while True:
                    self._pending.get_nowait()
            except queue.Empty:
                pass
            with self._buf_lock:
                self._loc_buf.clear()
                self._evt_buf.clear()
                self._started_buf.clear()
            # Pre-forked pool teardown: parked zygote children forked by
            # the old incarnation are drained (reaped like the leased
            # workers above) — no pre-forked worker may outlive the
            # incarnation that forked it; the pool manager rebuilds the
            # parked pool for the fresh incarnation.
            if self._pool is not None:
                self._pool.on_fence()
            # Plasma pins: the directory already dropped this node's
            # locations; forget the old life's primaries so post-rejoin
            # syncs cannot re-advertise them.
            with self._spill_lock:
                self._local_objects.clear()
                self._spilled.clear()
            self._draining = False
            reg = self.gcs.call(
                "register_node",
                self.node_id,
                self.advertised,
                self.store_path,
                self.total,
                self.labels,
            )
            if isinstance(reg, dict):
                self.epoch = reg.get("epoch", 0)
                self._cluster_size = reg.get("nodes", self._cluster_size)
            # Fresh incarnation: the GCS rebuilt this node's record, so
            # the first post-rejoin beat must carry full state.
            self._hb_codec.force_full()
            _log.warning(
                "node %s rejoined as epoch %s", self.node_id[:12], self.epoch
            )
            self._sched_wake.set()
        except Exception as e:
            # Re-registration can fail (the partition re-formed): the next
            # fenced heartbeat retries the whole sequence.
            _log.warning("fence of node %s did not complete (%r); will retry",
                         self.node_id[:12], e)
        finally:
            with self._fence_guard:
                self._fencing = False

    # chaos_partition / chaos_heal: inherited from ChaosPartitionRpc
    # (chaos/net.py) — one definition shared with the GCS.

    def drain(self, deadline_s: float = 30.0) -> bool:
        """Preemption-notice handling (reference: the DrainNode RPC,
        gcs_node_manager drain path): flips this node into drain state —
        new default-placement tasks are placed elsewhere, worker-lease
        requests spill to surviving nodes — while in-flight and
        bundle-pinned work keeps running through the grace window (gang
        supervisors own their members' checkpoint/stop). Idempotent."""
        if not self._draining:
            self._draining = True
            _flight_record("node.drain", (self.node_id[:12], deadline_s))
        self._sched_wake.set()
        return True

    def is_draining(self) -> bool:
        return self._draining

    def node_resources(self) -> Tuple[Dict[str, float], Dict[str, float]]:
        with self._res_lock:
            return dict(self.total), dict(self.available)

    def stop(self) -> bool:
        """Ends this raylet's children and returns when they are gone:
        every worker and, through the pool manager, the zygote with its
        parked pre-forks. Only then is `main` let out of its loop, so a
        caller that has the reply (or sees the process exit) knows the
        level below is empty (core/proctree.py)."""
        self._stop.set()
        # The trigger-bus forwarder wraps self.gcs; a publish after stop
        # (in-process raylets in tests) must not dial a dead GCS.
        from ..observability import postmortem as _postmortem

        _postmortem.disarm()
        try:
            with self._workers_lock:
                workers = list(self._workers.values())
            for w in workers:
                w.mailbox.put({"type": "stop"})
            left = proctree.wait_gone([w.proc for w in workers], proctree.GRACE_S)
            if self._pool is not None:
                self._pool.stop()
            proctree.end(left)
        finally:
            self._stopped.set()
        return True


def main(argv: List[str]) -> None:
    node_id, sock_path, store_path, gcs_sock, resources_json, capacity = argv[:6]
    labels = json.loads(argv[6]) if len(argv) > 6 else {}
    prestart = int(argv[7]) if len(argv) > 7 and argv[7] else 0
    tcp_spec = argv[8] if len(argv) > 8 and argv[8] else None

    from ..observability.flight_recorder import install_crash_hooks
    from ..observability.logs import configure as _logs_configure
    from ..utils.sampling_profiler import maybe_start_from_env

    maybe_start_from_env("raylet")
    install_crash_hooks("raylet")
    _logs_configure(
        "raylet",
        node_id=node_id,
        directory=os.path.join(os.path.dirname(sock_path) or ".", "logs"),
    )
    _log.info("raylet started (node %s, pid %d)", node_id[:12], os.getpid())

    # Multi-host mode: pre-bind the TCP endpoint (resolving an ephemeral
    # port) so the service can advertise it at registration; the service
    # object attaches right after construction (the RPC server holds early
    # connections until then). Local workers keep the UDS.
    tcp_server = RpcServer(tcp_spec, None) if tcp_spec else None
    service = RayletService(
        node_id,
        sock_path,
        store_path,
        gcs_sock,
        json.loads(resources_json),
        int(capacity),
        prestart_workers=prestart,
        labels=labels,
        advertise_address=tcp_server.address if tcp_server else None,
    )
    if tcp_server is not None:
        tcp_server.service = service
        print(f"RAYLET_TCP_ADDRESS={tcp_server.address}", flush=True)  # console-output: bootstrap protocol read by _read_announced
    server = RpcServer(sock_path, service)
    try:
        while not service._stopped.wait(0.5):
            _tracing.flush()  # a raylet may be killed: keep its span file current
    finally:
        if tcp_server is not None:
            tcp_server.shutdown()
        server.shutdown()


if __name__ == "__main__":
    main(sys.argv[1:])
