"""ClusterRuntime: the multi-process runtime (driver/worker side).

Re-design of the reference's driver bootstrap + CoreWorker client side
(reference: python/ray/_private/worker.py ray.init:1262 starting
Node.start_head_processes node.py:1354 — GCS and raylet daemons — and the
CoreWorker connecting to them, _raylet.pyx:3284). `create()` spawns the
head: one GCS process and one raylet process (more nodes via `Cluster`,
the analogue of python/ray/cluster_utils.py:135 used by every multi-node
test). The driver holds: a GCS client, its local raylet client, and the
node's shared-memory store.

Completion signaling rides the object plane: a task's results (or a
StoredError) appear in the store, and `get` waits on that — no
completion RPCs on the fast path.
"""

from __future__ import annotations

import atexit
import concurrent.futures
import json
import os
import pickle
import subprocess
import sys
import tempfile
import threading
import time
import uuid
from typing import Any, Dict, List, Optional, Sequence, Tuple

import cloudpickle

from .. import exceptions as exc
from .. import tracing as _tracing
from ..observability.logs import get_logger as _get_logger
from ..utils import internal_metrics as imet
from ..utils.config import CONFIG
from . import proctree
from .ids import ActorID, ObjectID, TaskID
from .object_transport import StoredError
from .rpc import RpcClient
from .runtime_base import Runtime
from .shm_store import SharedMemoryStore
from .task_spec import ArgRef, TaskSpec, TaskType

_log = _get_logger("driver")

# stream_next on the direct path: seconds without an ack before the raylet
# is asked. 2 s and not _get_one's 5 s: a stream's consumer expects its next
# item soon, so silence that long is already unusual, the check costs one
# RPC per blocked call per period, and it bounds how long a lost ack or a
# dead producer node can go unseen.
_STREAM_SILENCE_S = 2.0


def _entry_from_spec(spec: TaskSpec) -> dict:
    """Flattens a TaskSpec into the wire entry the raylet/worker consume."""
    deps = [a.object_id.hex() for a in spec.args if isinstance(a, ArgRef)]
    deps += [v.object_id.hex() for v in spec.kwargs.values() if isinstance(v, ArgRef)]
    resources = dict(spec.options.resources.to_dict()) if spec.options.resources else {}
    if spec.task_type == TaskType.NORMAL_TASK and not resources:
        resources = {"CPU": 1.0}
    streaming = spec.num_returns == "streaming"
    return {
        "task_id": spec.task_id.hex(),
        # Span context propagation (reference: tracing_helper.py:165 —
        # context injected into the spec so the executor's span parents
        # to the submitter's ambient span) plus a flow id for the
        # Perfetto submit->execute arrow. None when tracing is off.
        "trace_ctx": _tracing.inject_context(),
        "func_blob": spec.func_blob,
        "func_hash": spec.func_hash,
        "method_name": spec.method_name,
        "args_blob": cloudpickle.dumps((spec.args, spec.kwargs)),
        "deps": deps,
        # Streaming tasks pre-declare only the header (index 0); item ids
        # are derived as the generator yields (reference: dynamic return
        # ids of streaming generators, _raylet.pyx).
        "return_ids": (
            [spec.task_id.object_id_for_return(0).hex()]
            if streaming
            else [
                spec.task_id.object_id_for_return(i).hex()
                for i in range(spec.num_returns)
            ]
        ),
        "streaming": streaming,
        "resources": resources,
        "actor_id": spec.actor_id.hex() if spec.actor_id else None,
        "max_restarts": spec.options.max_restarts,
        "max_retries": spec.options.max_retries,
        "max_concurrency": spec.options.max_concurrency,
        "concurrency_groups": spec.options.concurrency_groups,
        "concurrency_group": spec.concurrency_group,
        "runtime_env": spec.options.runtime_env,
        "attempt": 0,
        "strategy": spec.options.scheduling_strategy,
        "pg_id": spec.options.placement_group_id,
        "bundle_index": spec.options.bundle_index,
        "name": spec.options.name,
        "namespace": spec.options.namespace,
        "desc": spec.description(),
    }


def _submit_span(entry: dict):
    """Submit-side anchor span for the Perfetto submit->execute flow
    arrow: carries `flow_out` paired with the flow id riding the entry's
    trace_ctx (the executing span reports it as `flow_in`). An entry made
    with tracing off carries no trace_ctx and gets no span."""
    ctx = entry.get("trace_ctx") or {}
    return _tracing.span(
        f"submit {entry.get('desc', 'task')}",
        {"task_id": entry.get("task_id", ""), "flow_out": ctx.get("flow")},
    )


class _ActorCreateBatcher:
    """Coalescing leader-follower batcher over the GCS `create_actors`
    RPC. A serial caller flushes immediately (batch of 1 — no artificial
    coalescing delay), but while any batch RPC is IN FLIGHT, concurrent
    creators queue behind it and whoever is waiting when it returns
    leads the next RPC with the whole accumulated batch — a creation
    storm from N threads pipelines into O(RPCs in flight) GCS round
    trips instead of N (reference: the submission-queue coalescing in
    NormalTaskSubmitter, applied to actor registration)."""

    def __init__(self, gcs: RpcClient):
        self._gcs = gcs
        self._cv = threading.Condition()
        self._queue: List[dict] = []
        self._inflight = False

    def create(self, spec: dict) -> dict:
        item = {"spec": spec, "done": False, "result": None}
        batch: Optional[List[dict]] = None
        with self._cv:
            self._queue.append(item)
            while not item["done"]:
                if not self._inflight and self._queue:
                    batch, self._queue = self._queue, []
                    self._inflight = True
                    break
                self._cv.wait()
        if batch is not None:
            results = None
            try:
                results = self._gcs.call(
                    "create_actors", [it["spec"] for it in batch]
                )
                if not isinstance(results, list) or len(results) != len(batch):
                    raise RuntimeError(
                        f"create_actors: malformed batch reply ({results!r:.120})"
                    )
            except Exception as e:  # noqa: BLE001
                results = [{"error": e}] * len(batch)
            finally:
                # Always release leadership — a BaseException escaping
                # the RPC (KeyboardInterrupt) must not strand followers
                # waiting on a leader that will never return.
                with self._cv:
                    if results is None:
                        interrupted = RuntimeError(
                            "create_actors batch interrupted"
                        )
                        results = [{"error": interrupted}] * len(batch)
                    for it, r in zip(batch, results):
                        it["result"] = r
                        it["done"] = True
                    self._inflight = False
                    self._cv.notify_all()
        result = item["result"]
        err = result.get("error")
        if err is not None:
            # Per-spec failures travel as pickled exception objects —
            # re-raised here so the caller sees the same typed error
            # (ActorNameTakenError, SchedulingError, ...) the old
            # two-RPC path raised.
            if isinstance(err, BaseException):
                raise err
            raise RuntimeError(str(err))
        return result


class _TaskRecord:
    """Owner-side record of a submitted task: the wire entry kept for retry
    and lineage reconstruction until the last reference to its outputs drops
    (reference: task_manager.h:208 — the lineage half :388-402)."""

    __slots__ = ("entry", "kind", "attempts", "last_submit", "lock")

    def __init__(self, entry: dict, kind: str):
        self.entry = entry
        self.kind = kind  # "task" | "actor_task"
        self.attempts = 0
        self.last_submit = time.monotonic()
        self.lock = threading.Lock()


class ClusterRuntime(Runtime):
    def __init__(
        self,
        gcs: RpcClient,
        raylet: RpcClient,
        store: SharedMemoryStore,
        node_id: str,
        session_dir: Optional[str] = None,
        driver: bool = True,
    ):
        self._gcs = gcs
        self._raylet = raylet
        self._store = store
        self._node_id = node_id
        self._session_dir = session_dir
        self._cluster: Optional["Cluster"] = None  # set by Cluster.runtime(): this driver owns the session
        self._driver = driver
        # Context identity (reference: runtime_context.py): workers override
        # _worker_id with their raylet-assigned id after attach.
        self._worker_id = f"driver-{os.getpid()}" if driver else f"worker-{os.getpid()}"
        self._namespace = "default"
        # Stamp this process's node onto its internal-metrics records
        # (workers re-configure with their raylet-assigned id after attach).
        from ..utils import internal_metrics as _imet

        _imet.configure(node_id=node_id, reporter=self._worker_id)
        # Flight recorder post-mortems: an unhandled crash in any runtime
        # process dumps the event ring to the session's flight dir.
        from ..observability import flight_recorder as _frec

        _frec.install_crash_hooks("driver" if driver else "worker")
        # Arm the anomaly trigger bus: cgraph timeouts, collective stalls,
        # and job failures detected in this process forward to the GCS's
        # report_trigger RPC (debounced client-side; see postmortem.py).
        from ..observability import postmortem as _postmortem

        _postmortem.arm_client(gcs)
        self._actor_location: Dict[str, str] = {}  # actor_id -> raylet sock
        self._raylet_clients: Dict[str, RpcClient] = {}
        # Actor creations coalesce through a leader-follower batcher
        # over the GCS's batched create_actors RPC (register + place +
        # forward in one round trip).
        self._actor_batcher = _ActorCreateBatcher(gcs)
        self._shutdown_done = False
        # Owner-side reference counting + task records (reference:
        # reference_count.h:64, task_manager.h:208). return-oid hex ->
        # shared _TaskRecord; pruned when the last local ref to any of the
        # task's outputs drops.
        # NOT tracked: the ref-count lock sits on the per-ObjectRef fast
        # path (~15 acquires per dispatch); the wrapper would cost ~10%
        # tasks/s. Cross-plane deadlock coverage comes from the raylet/
        # GCS/serve-controller locks, which are off the fastpath.
        # Re-entrant: any allocation inside a locked section can start a
        # cyclic GC pass on this thread, and a collected ObjectRef's
        # __del__ takes the lock again (remove_local_ref). With a plain
        # Lock that is a self-deadlock (seen: _record_submission ->
        # "Garbage-collecting" -> __del__ -> remove_local_ref, tier-1 hung).
        self._ref_lock = threading.RLock()
        self._local_refs: Dict[str, int] = {}
        self._owned: set = set()  # oids this process created (put / submit)
        self._records: Dict[str, _TaskRecord] = {}
        self._pending_free: List[str] = []
        self._borrow_buf: Dict[str, int] = {}
        # Oids whose refs were serialized out of this process (task args,
        # refs nested in put values): another process may borrow them, so
        # their frees must ride the GCS borrow-grace path. Everything else
        # is freed from the local pool eagerly on last-ref drop.
        self._escaped: set = set()
        self._dropped_records: List[_TaskRecord] = []
        self._free_wake = threading.Event()
        self._free_thread = threading.Thread(
            target=self._free_loop, daemon=True, name="free"
        )
        self._free_thread.start()
        # Submission coalescing: bursts of .remote() calls drain into one
        # submit_task_batch message (reference: NormalTaskSubmitter's
        # submission queue). A dedicated flusher keeps single submits at
        # one-thread-handoff latency while a tight loop batches naturally.
        self._submit_lock = threading.Lock()  # fastpath; see _ref_lock note
        self._submit_buf: List[dict] = []
        self._submit_wake = threading.Event()
        threading.Thread(target=self._submit_loop, daemon=True, name="submit").start()
        # Leased-worker fast path (direct owner->worker pushes; reference:
        # normal_task_submitter.cc:555 PushTask on a cached lease) and
        # per-actor ordered direct channels.
        from .fastpath import FastPath

        self._fastpath = FastPath(self)
        self._actor_channels: Dict[str, Any] = {}
        self._actor_channels_lock = threading.Lock()  # fastpath; see _ref_lock note
        self._cancelled_tids: set = set()
        # Fast-path completion wakeups: the worker's in-band ack marks the
        # outputs sealed, waking local get()s milliseconds before the
        # batched raylet/GCS notification lands. _fast_lock guards the
        # sets below (an RLock: a GC pass inside a locked section may run
        # a generator's __del__ -> stream_done on the same thread).
        self._fast_pending: set = set()
        self._fast_lock = threading.RLock()
        # Oids a local get()/wait()/stream_next() is CURRENTLY blocked on
        # -> the events of those calls (_await_ack). An ack sets the events
        # of the ids it delivers and no other: one shared condition with
        # notify_all at ack rate (10k+/s) woke every blocked consumer once
        # per completion — on a single shared core that context-switch
        # storm throttles the producer pipeline ~20x, and with N live
        # streams every token cost N wake-ups.
        self._ack_waiters: Dict[str, List[threading.Event]] = {}
        # Owner memory store: small direct-task results live here, never
        # touching shm or the GCS directory (reference: the CoreWorker
        # in-memory store, src/ray/core_worker/store_provider/memory_store/).
        self._memstore: Dict[str, bytes] = {}
        self._memstore_bytes = 0
        # Streaming tasks this owner is consuming: their dynamically-
        # discovered item oids (hex prefix == task id) are accepted into
        # the memory store even before adoption into _owned.
        self._stream_tasks: set = set()
        # Their items whose ack said "sealed in a store" (not inline), until
        # consumed: stream_next tells "not acked yet" (wait for the ack)
        # from "acked, bytes on another node" (go to the raylet and pull).
        self._stream_sealed: set = set()
        self._m_stream_next = {
            w: imet.STREAM_NEXT.labels(woken=w) for w in imet.STREAM_NEXT_WOKEN
        }
        self._renv_cache: Dict[str, dict] = {}
        # Structured logging: the driver's own records land in the
        # session's log dir (observability/logs.py), and captured worker
        # output arrives over the `logs` pubsub channel for attributed
        # re-printing (reference: log_monitor.py streaming worker logs to
        # the driver; disable with RAY_TPU_LOG_TO_DRIVER=0).
        self._log_session = session_dir or (
            None if raylet.path.startswith("tcp://") else os.path.dirname(raylet.path)
        )
        from ..observability import logs as _logs

        if driver:
            _logs.configure(
                "driver",
                node_id=node_id,
                directory=(
                    os.path.join(self._log_session, "logs")
                    if self._log_session
                    else None
                ),
            )
        self._log_printer = _logs.DedupPrinter()
        self._log_recent: List[str] = []  # last re-printed lines (tests/bench)
        if driver and os.environ.get("RAY_TPU_LOG_TO_DRIVER", "1") != "0":
            threading.Thread(
                target=self._log_subscriber, daemon=True, name="logmon"
            ).start()

    def _fast_register(self, entry: dict) -> None:
        with self._fast_lock:
            self._fast_pending.update(entry["return_ids"])

    def _fast_sealed(self, sealed: List[str], inline: Optional[dict] = None) -> bool:
        """Completion ack from a direct worker: record inline results in
        the owner's memory store (reference: CoreWorker's in-memory store
        for small returns — memory_store.h) and wake local waiters.
        Returns whether a waiter was notified (core.stream_ack records it)."""
        if inline:
            memstore = self._memstore
            for h, blob in inline.items():
                to_shm = False
                with self._ref_lock:
                    # Escape-check and memstore insert under ONE lock hold:
                    # mark_escaped (also under _ref_lock) either sees the
                    # blob already in the memstore and promotes it, or adds
                    # h to _escaped first and this branch routes to shm —
                    # no interleaving can strand an escaped result in the
                    # owner-only memstore.
                    wanted = h in self._owned or h[:24] in self._stream_tasks
                    if not wanted:
                        # Every ref was dropped while the task was in
                        # flight (fire-and-forget): storing the late result
                        # would leak it forever.
                        continue
                    if (
                        h in self._escaped
                        or self._memstore_bytes + len(blob) > 256 << 20
                    ):
                        # Escaped (another process may need it) or over the
                        # memstore cap: materialize to shm + directory.
                        to_shm = True
                    else:
                        memstore[h] = blob
                        self._memstore_bytes += len(blob)
                if to_shm:
                    try:
                        self._store.put_raw(ObjectID.from_hex(h), blob)
                        self._raylet.notify("notify_object", h)
                    except Exception:
                        memstore[h] = blob  # last resort: gets still work
                        self._memstore_bytes += len(blob)
        with self._fast_lock:
            self._fast_pending.difference_update(sealed)
            if inline:
                self._fast_pending.difference_update(inline.keys())
            if sealed and self._stream_tasks:
                self._stream_sealed.update(
                    h for h in sealed if h[:24] in self._stream_tasks
                )
            woke = False
            waiters = self._ack_waiters
            if waiters:
                for h in (*sealed, *inline) if inline else sealed:
                    for ev in waiters.get(h, ()):
                        ev.set()
                        woke = True
        return woke

    def _await_ack(self, hexes: List[str], timeout: float, landed) -> Optional[bool]:
        """Blocks until a direct connection's ack delivers one of `hexes`
        (True) or `timeout` seconds pass (False). `landed` is the caller's
        arrival check, repeated under the lock before waiting (None if it
        holds: no wait was made). _fast_sealed fills the memory store and
        the sets before it looks for waiters under the same lock, so no
        wake-up is lost."""
        woke = threading.Event()
        waiters = self._ack_waiters
        with self._fast_lock:
            if landed():
                return None
            for h in hexes:
                waiters.setdefault(h, []).append(woke)
        try:
            return woke.wait(timeout)
        finally:
            with self._fast_lock:
                for h in hexes:
                    evs = waiters[h]
                    evs.remove(woke)
                    if not evs:
                        del waiters[h]

    def _log_subscriber(self) -> None:
        """Re-prints captured worker output at the driver with
        `(ActorName pid=... node=...)` prefixes. Source is the `logs`
        pubsub channel the raylet log monitors publish on — works across
        hosts and for remote clients, unlike tailing local files.
        Identical repeated lines are deduped and the stream is
        rate-limited (logs.DedupPrinter) so a hot-loop actor cannot
        freeze the driver console."""
        from ..observability import logs as _logs

        # Position at the channel tail: output from BEFORE this driver
        # attached belongs to earlier jobs, not this console. A failed
        # positioning call must NOT fall back to cursor 0 — that would
        # replay a long-lived cluster's whole retained history the moment
        # the GCS recovers — so retry until it succeeds.
        cursor = None
        while cursor is None and not self._shutdown_done:
            try:
                entries = self._gcs.call(
                    "pubsub_poll", "logs", 0, 0.0, timeout=10.0
                )
                cursor = entries[-1][0] if entries else 0
            except Exception:
                time.sleep(0.5)
        if cursor is None:
            return
        printer = self._log_printer
        while not self._shutdown_done:
            try:
                entries = self._gcs.call(
                    "pubsub_poll", "logs", cursor, 1.0, timeout=11.0
                )
            except Exception:
                if self._shutdown_done:
                    return
                time.sleep(0.5)
                continue
            for seq, msg in entries:
                cursor = max(cursor, seq)
                if not isinstance(msg, dict):
                    continue
                prefix = _logs.capture_prefix(msg)
                for line in msg.get("lines") or ():
                    printer.emit(prefix, line)
                    self._log_recent.append(f"{prefix} {line}")
                if len(self._log_recent) > 1000:
                    del self._log_recent[:-500]
            printer.flush()

    # ------------------------------------------------------------ factory
    @classmethod
    def create(
        cls,
        address: Optional[str] = None,
        num_cpus: Optional[float] = None,
        num_tpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        namespace: Optional[str] = None,
        object_store_memory: Optional[int] = None,
        num_workers: Optional[int] = None,
    ) -> "ClusterRuntime":
        if address and address.startswith("tcp://"):
            # Remote-client mode (reference: ray client, util/client/):
            # a driver outside the cluster attaching by the head's TCP
            # address; object ops proxy through a gateway raylet.
            from .client_runtime import ClientRuntime

            rt = ClientRuntime.connect_tcp(address)
        elif address:
            rt = cls.connect(address)
        else:
            cluster = Cluster(
                num_cpus=num_cpus,
                num_tpus=num_tpus,
                resources=resources,
                object_store_memory=object_store_memory,
                num_workers=num_workers,
            )
            rt = cluster.runtime()
        if namespace:
            rt._namespace = namespace
        return rt

    @classmethod
    def connect(cls, session_dir: str) -> "ClusterRuntime":
        """Attaches a driver to an existing cluster by session dir."""
        with open(os.path.join(session_dir, "session.json")) as f:
            info = json.load(f)
        return cls.attach(
            gcs_sock=info["gcs_sock"],
            raylet_sock=info["head_raylet_sock"],
            store_path=info["head_store"],
            node_id=info["head_node_id"],
        )

    @classmethod
    def attach(
        cls,
        gcs_sock: str,
        raylet_sock: str,
        store_path: str,
        node_id: str,
        driver: bool = True,
    ) -> "ClusterRuntime":
        return cls(
            RpcClient(gcs_sock),
            RpcClient(raylet_sock),
            SharedMemoryStore(store_path),
            node_id,
            driver=driver,
        )

    # ----------------------------------------------------- reference count
    def add_local_ref(self, object_id: ObjectID) -> None:
        h = object_id.hex()
        borrowed = False
        with self._ref_lock:
            c = self._local_refs.get(h, 0)
            self._local_refs[h] = c + 1
            if c == 0 and h not in self._owned:
                # First ref to an object this process does not own: register
                # a borrow with the GCS so the owner's free is deferred
                # (reference: reference_count.h borrower protocol).
                self._borrow_buf[h] = self._borrow_buf.get(h, 0) + 1
                borrowed = True
        if borrowed:
            self._free_wake.set()

    def mark_escaped(self, object_id: ObjectID) -> None:
        h = object_id.hex()
        with self._ref_lock:
            self._escaped.add(h)
        blob = self._memstore.get(h)
        if blob is not None:
            # The ref is leaving this process: another worker may need the
            # value, so the memory-store object is promoted to shm and the
            # directory learns its location (reference: in-memory objects
            # are promoted to plasma when borrowed across processes).
            try:
                self._store.put_raw(object_id, blob)
            except exc.ObjectStoreFullError:
                try:
                    self._raylet.call("ensure_space", len(blob))
                    self._store.put_raw(object_id, blob)
                except Exception:
                    return  # keep it in memory; gets still work locally
            except Exception:
                return
            self._raylet.notify("notify_object", h)
            self._memstore_bytes -= len(blob)
            self._memstore.pop(h, None)

    def remove_local_ref(self, object_id: ObjectID) -> None:
        freed = False
        eager: List[str] = []
        with self._ref_lock:
            # Iterative cascade: freeing an output releases its task's
            # lineage pins on the deps, which may free those in turn
            # (reference: reference_count.h lineage pinning).
            work = [object_id.hex()]
            while work:
                h = work.pop()
                c = self._local_refs.get(h, 0) - 1
                if c > 0:
                    self._local_refs[h] = c
                    continue
                self._local_refs.pop(h, None)
                if h not in self._owned:
                    # Borrowed ref fully dropped here: return the borrow.
                    self._borrow_buf[h] = self._borrow_buf.get(h, 0) - 1
                    self._escaped.discard(h)  # re-serialized borrows too
                    freed = True
                    continue
                self._owned.discard(h)
                rec = self._records.pop(h, None)
                mem_blob = (
                    self._memstore.pop(h, None) if h not in self._escaped else None
                )
                if mem_blob is not None:
                    # Inline result never left this process: dropping the
                    # dict entry IS the free — no pool block, no GCS
                    # directory entry, no cluster-wide cleanup. (Escaped
                    # objects never take this branch: a borrower may still
                    # need the value, so they ride the GCS borrow path; a
                    # memstore-only escaped object was promoted to shm by
                    # mark_escaped, or, if that promotion failed, by the
                    # retry below.)
                    self._memstore_bytes -= len(mem_blob)
                    freed = True
                    if rec is not None and not any(
                        self._records.get(r) is rec for r in rec.entry["return_ids"]
                    ):
                        if rec.entry.get("deps"):
                            self._dropped_records.append(rec)
                    continue
                if h in self._escaped and h in self._memstore:
                    # Escaped but promotion failed at escape time: retry so
                    # the shm copy exists before our in-memory one goes.
                    try:
                        self._store.put_raw(ObjectID.from_hex(h), self._memstore[h])
                        self._raylet.notify("notify_object", h)
                        blob2 = self._memstore.pop(h)
                        self._memstore_bytes -= len(blob2)
                    except Exception as e:  # keep the blob; better a leak than data loss
                        _log.warning("could not escape %s to shm; keeping in-memory copy: %r",
                                     h[:8], e)
                if h not in self._escaped:
                    # No other process can hold a borrow (the ref never left
                    # this one): free the pool block now so the allocator
                    # reuses the hot low region instead of cycling through
                    # the arena. The GCS free still runs for directory
                    # cleanup. (reference: plasma deletes immediately when
                    # the owner knows there are no borrowers.)
                    eager.append(h)
                else:
                    self._escaped.discard(h)
                self._pending_free.append(h)
                freed = True
                if rec is not None and not any(
                    self._records.get(r) is rec for r in rec.entry["return_ids"]
                ):
                    # Last output ref dropped. The task may still be in
                    # flight (fire-and-forget), so its argument pins are
                    # released by the free loop only once the task reaches a
                    # terminal state (flight-time pinning, reference:
                    # reference_count.h submitted-task count).
                    if rec.entry.get("deps"):
                        self._dropped_records.append(rec)
        if not self._shutdown_done:
            for h in eager:
                try:
                    # Pinned readers make delete fail; the async GCS free
                    # path (which the raylet monitor retries) covers those.
                    self._store.delete(ObjectID.from_hex(h))
                except Exception:  # lint: swallow-ok(pinned readers; async GCS free path retries)
                    pass
        if freed:
            self._free_wake.set()

    def _release_dropped_records(self) -> None:
        """Releases argument pins of fully-dropped tasks that have finished
        (called from the free loop, no locks held)."""
        with self._ref_lock:
            pending, self._dropped_records = self._dropped_records, []
        if not pending:
            return
        keep: List[_TaskRecord] = []
        try:
            states = self._gcs.call(
                "get_task_states", [r.entry["task_id"] for r in pending]
            )
        except Exception:
            with self._ref_lock:
                self._dropped_records.extend(pending)
            return
        now = time.monotonic()
        for rec in pending:
            st = states.get(rec.entry["task_id"])
            terminal = st is not None and st["state"] in ("FINISHED", "FAILED")
            # Unknown state: either evicted (long terminal) or never reported
            # (raylet died); treat as terminal after a grace period.
            aged_out = st is None and now - rec.last_submit > 2 * CONFIG.heartbeat_timeout_s
            if terminal or aged_out:
                for dep in rec.entry.get("deps", []):
                    self.remove_local_ref(ObjectID.from_hex(dep))
            else:
                keep.append(rec)
        if keep:
            with self._ref_lock:
                self._dropped_records.extend(keep)

    def _free_loop(self) -> None:
        """Batches owner releases + borrow deltas into one RPC each
        (reference: the reference batches plasma Deletes the same way)."""
        while not self._shutdown_done:
            self._free_wake.wait(timeout=0.5)
            self._free_wake.clear()
            time.sleep(0.02)  # coalesce a burst of drops
            self._release_dropped_records()
            with self._ref_lock:
                batch, self._pending_free = self._pending_free, []
                borrows, self._borrow_buf = self._borrow_buf, {}
            borrows = {h: d for h, d in borrows.items() if d != 0}
            # Borrows first: a borrow must land before the owner's free does.
            if borrows:
                try:
                    self._gcs.call("update_borrows", borrows)
                except Exception:
                    with self._ref_lock:  # GCS hiccup: retry next round
                        for h, d in borrows.items():
                            self._borrow_buf[h] = self._borrow_buf.get(h, 0) + d
                    time.sleep(0.2)
            if batch:
                try:
                    self._gcs.call("free_objects", batch)
                except Exception:
                    with self._ref_lock:
                        self._pending_free = batch + self._pending_free
                    time.sleep(0.2)

    def flush_local_frees(self) -> None:
        """Synchronously pushes this owner's pending free batch to the GCS
        (called under pool pressure so dead objects free up space before
        anything live is spilled). Borrow deltas go first — a free landing
        before this process's own borrow registration would be executed
        against an undercounted object."""
        with self._ref_lock:
            batch, self._pending_free = self._pending_free, []
            borrows, self._borrow_buf = self._borrow_buf, {}
        borrows = {h: d for h, d in borrows.items() if d != 0}
        if borrows:
            try:
                self._gcs.call("update_borrows", borrows)
            except Exception:
                with self._ref_lock:
                    for h, d in borrows.items():
                        self._borrow_buf[h] = self._borrow_buf.get(h, 0) + d
        if batch:
            try:
                self._gcs.call("free_objects", batch)
            except Exception:
                with self._ref_lock:
                    self._pending_free = batch + self._pending_free

    def _record_submission(self, entry: dict, kind: str) -> None:
        rec = _TaskRecord(entry, kind)
        with self._ref_lock:
            for h in entry["return_ids"]:
                self._records[h] = rec
                self._owned.add(h)
                # Return ids are NOT eagerly escaped: every path that hands
                # this ref to another process (arg conversion, __reduce__,
                # broadcast) goes through owner-side mark_escaped, which
                # promotes a memstore blob to shm under _ref_lock before
                # the ref leaves. Eager escape here would route every
                # inline result through shm + a directory notify — ~2x the
                # per-task cost of the owner memstore path the inline ack
                # exists for (measured: 6.9k/s -> 9k/s async tasks).
            # Lineage-pin the arguments: they stay alive (and reconstructable)
            # while any output of this task is still referenced.
            for dep in entry.get("deps", []):
                self._local_refs[dep] = self._local_refs.get(dep, 0) + 1

    # ------------------------------------------------------------ objects
    def put(self, value: Any) -> ObjectID:
        oid = TaskID.for_task().object_id_for_return(0)
        self._store.put_with_pressure(
            oid, value, self._raylet, pre_pressure=self.flush_local_frees
        )
        with self._ref_lock:
            self._owned.add(oid.hex())
        self._raylet.notify("notify_object", oid.hex())
        return oid

    def _get_one(self, oid: ObjectID, deadline: Optional[float]) -> Any:
        h = oid.hex()
        fast_until: Optional[float] = None
        while True:
            blob = self._memstore.get(h)
            if blob is not None:
                from . import serialization

                value = serialization.unpack(blob)
                if isinstance(value, StoredError):
                    raise value.error
                return value
            if self._store.contains(oid):
                value = self._store.get(oid, timeout=5.0)
                if isinstance(value, StoredError):
                    raise value.error
                return value
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                raise exc.GetTimeoutError(f"get() timed out for {oid.hex()[:12]}")
            if h in self._fast_pending:
                # In flight on a direct connection: the completion ack wakes
                # this wait — no RPC. After ~5s of true silence (wall time,
                # not wakeups) we fall through to the raylet path as a
                # safety net.
                now = time.monotonic()
                if fast_until is None:
                    fast_until = now + 5.0
                if now < fast_until:
                    self._await_ack([h], 0.05, lambda: h not in self._fast_pending)
                    continue
            fast_until = None
            if h in self._memstore or self._store.contains(oid):
                # The ack landed between the checks at the loop top and
                # here (fast path completions are concurrent): re-check
                # before committing to a multi-second raylet wait that can
                # never see an inline-only object.
                continue
            poll = CONFIG.object_wait_poll_s
            if remaining is not None:
                poll = max(0.05, min(poll, remaining))
            # Event-driven wait on the local raylet (pulls remote copies in).
            ready = self._raylet.call(
                "wait_objects", [h], 1, poll, True, timeout=poll + 10.0
            )
            if ready:
                continue
            # Nothing appeared within the poll window: consult the task
            # table for failure/loss and retry or reconstruct.
            self._maybe_recover(oid)

    def get(self, object_ids: Sequence[ObjectID], timeout: Optional[float] = None) -> List[Any]:
        deadline = None if timeout is None else time.monotonic() + timeout
        return [self._get_one(oid, deadline) for oid in object_ids]

    def wait(self, object_ids, num_returns, timeout):
        deadline = None if timeout is None else time.monotonic() + timeout
        ids = list(object_ids)
        hexes = [oid.hex() for oid in ids]
        while True:
            # Inline results live in the owner's memory store only — the
            # raylet has never heard of them.
            mem_ready = {h for h in hexes if h in self._memstore}
            if len(mem_ready) >= num_returns:
                ready_h = mem_ready
                break
            pending_fast = [h for h in hexes if h in self._fast_pending]
            if pending_fast and len(mem_ready) + len(
                [h for h in hexes if self._store.contains(ObjectID.from_hex(h))]
            ) < num_returns:
                # Direct tasks in flight: wait on the ack wakeup first.
                pending = self._fast_pending
                self._await_ack(
                    pending_fast, 0.05, lambda: any(h not in pending for h in pending_fast)
                )
                if deadline is not None and time.monotonic() >= deadline:
                    ready_h = mem_ready | {
                        h for h in hexes if self._store.contains(ObjectID.from_hex(h))
                    }
                    break
                continue
            remaining = None if deadline is None else deadline - time.monotonic()
            poll = CONFIG.object_wait_poll_s
            if remaining is not None:
                poll = max(0.0, min(poll, remaining))
            ready_h = mem_ready | set(
                self._raylet.call(
                    "wait_objects",
                    [h for h in hexes if h not in mem_ready],
                    max(0, num_returns - len(mem_ready)),
                    poll,
                    False,
                    timeout=poll + 10.0,
                )
            )
            if len(ready_h) >= num_returns or (
                deadline is not None and time.monotonic() >= deadline
            ):
                break
            # Straggler window expired: nudge recovery for missing objects
            # (errors surface as stored error objects, which become ready).
            for oid in ids:
                if oid.hex() not in ready_h:
                    try:
                        self._maybe_recover(oid, store_errors=True)
                    except Exception as e:
                        _log.debug("recovery nudge for %s failed: %r", oid.hex()[:8], e)
        ready_idx = [i for i, h in enumerate(hexes) if h in ready_h][:num_returns]
        ready_set = set(ready_idx)
        return ready_idx, [i for i in range(len(ids)) if i not in ready_set]

    # --------------------------------------------------- failure recovery
    def _maybe_recover(self, oid: ObjectID, store_errors: bool = False) -> None:
        """Owner-side retry/reconstruction decision for an object that has
        not appeared (reference: object_recovery_manager.h:41 +
        task_manager.h retries). Raises (or stores an error object when
        `store_errors`) only when the object is provably unrecoverable."""
        h = oid.hex()
        rec = self._records.get(h)
        if rec is None:
            return  # a put / borrowed object: nothing to re-execute
        if rec.kind != "task":
            return  # actor task outputs surface errors via the raylet
        with rec.lock:
            # Throttle: give the (re)submission a full failure-detection
            # period before acting again.
            if time.monotonic() - rec.last_submit < CONFIG.heartbeat_timeout_s:
                return
            tid = rec.entry["task_id"]
            st = self._gcs.call("get_task_states", [tid]).get(tid)
            state = st["state"] if st else None
            if state in ("QUEUED", "RUNNING"):
                rec.last_submit = time.monotonic()  # alive; keep waiting
                return
            if self._gcs.call("get_object_locations", h):
                return  # exists somewhere; pull is in progress
            # FAILED(node_died), FINISHED-but-lost, or unknown (raylet died
            # before reporting): re-execute from lineage if retries remain.
            mr = rec.entry.get("max_retries", 0)
            budget = float("inf") if mr < 0 else max(1, mr)
            if mr == 0 and state != "FINISHED":
                budget = 0  # non-retryable task that never finished
            if rec.attempts >= budget:
                err = exc.ObjectLostError(h)
                if store_errors:
                    self._store_error_object(rec.entry, err)
                    return
                raise err
            rec.attempts += 1
            rec.last_submit = time.monotonic()
            entry = dict(rec.entry)
            entry["attempt"] = rec.attempts
        # Reconstruct missing dependencies first (2-deep+ lineage chains).
        for dep in entry.get("deps", []):
            dep_oid = ObjectID.from_hex(dep)
            if not self._store.contains(dep_oid) and not self._gcs.call(
                "get_object_locations", dep
            ):
                dep_rec = self._records.get(dep)
                if dep_rec is not None:
                    with dep_rec.lock:
                        dep_rec.last_submit = 0.0  # lift throttle for cascade
                    self._maybe_recover(dep_oid, store_errors=store_errors)
        self._submit_entry(entry)

    def _store_error_object(self, entry: dict, err: BaseException) -> None:
        for rid in entry["return_ids"]:
            rid_oid = ObjectID.from_hex(rid)
            try:
                self._store.put_with_pressure(
                    rid_oid,
                    StoredError(err, entry.get("desc", "")),
                    self._raylet,
                    deadline_s=5.0,
                    pre_pressure=self.flush_local_frees,
                )
                self._raylet.notify("notify_object", rid)
            except Exception as e:
                # A missing error object turns a clean failure into a hung
                # get(): this loss must be loud.
                _log.warning("failed to store fastpath error object: %r", e)

    def _fastpath_failed(self, entries: List[dict]) -> None:
        """A leased worker died with these tasks outstanding: retry via the
        raylet path (deps may have been lost with the node's worker — the
        scheduler re-gates them) or surface the failure as a stored error
        (reference: task_manager.h retry-on-worker-death budget)."""
        for entry in entries:
            entry.pop("_fast", None)
            if entry.get("task_id") in self._cancelled_tids:
                self._cancelled_tids.discard(entry["task_id"])
                self._store_error_object(
                    entry,
                    exc.TaskCancelledError(
                        f"{entry.get('desc','task')} was cancelled"
                    ),
                )
                continue
            mr = entry.get("max_retries", 0)
            attempt = entry.get("attempt", 0)
            if mr < 0 or attempt < mr:
                entry = dict(entry)
                entry["attempt"] = attempt + 1
                rec = self._records.get((entry.get("return_ids") or [None])[0])
                if rec is not None:
                    rec.attempts = entry["attempt"]
                    rec.last_submit = time.monotonic()
                self._submit_entry_slow(entry)
            else:
                self._store_error_object(
                    entry,
                    exc.WorkerCrashedError(
                        f"worker died executing {entry.get('desc','task')}"
                    ),
                )
            self._fast_sealed(entry["return_ids"])

    def _actor_fast_failed(self, actor_hex: str, entries: List[dict]) -> None:
        """In-flight direct actor calls when the actor's worker died: fail
        them like the raylet fails its in-flight list on actor death."""
        err = RuntimeError(f"actor {actor_hex[:8]} died (worker process exited)")
        for entry in entries:
            self._store_error_object(entry, err)
            self._fast_sealed(entry["return_ids"])

    def _submit_entry(self, entry: dict) -> None:
        if not entry.get("pg_id") and self._fastpath.try_submit(entry):
            return
        self._submit_entry_slow(entry)

    def _submit_entry_slow(self, entry: dict) -> None:
        if entry.get("pg_id"):
            target = self._gcs.call("pick_bundle", entry["pg_id"], entry["bundle_index"])
            if target is None:
                raise RuntimeError(
                    f"placement group {entry['pg_id'][:8]} bundle "
                    f"{entry['bundle_index']} is not schedulable"
                )
            entry = dict(entry)
            entry["bundle_index"] = target["bundle_index"]
            self._raylet_for(target["sock"]).notify("submit_task", pickle.dumps(entry))
        else:
            # One-way submit: return ids are owner-computed, infeasibility
            # surfaces as a stored error object, and lost submits are caught
            # by the task-table recovery path — no ack roundtrip needed.
            with self._submit_lock:
                self._submit_buf.append(entry)
            self._submit_wake.set()

    def _submit_loop(self) -> None:
        while not self._shutdown_done:
            self._submit_wake.wait(timeout=0.5)
            self._submit_wake.clear()
            self._drain_submit_buf()
        # Final drain: entries buffered in the instant before shutdown()
        # flipped the flag must not vanish without a trace.
        self._drain_submit_buf()

    def _drain_submit_buf(self) -> None:
        while True:
            with self._submit_lock:
                batch, self._submit_buf = self._submit_buf, []
            if not batch:
                return
            try:
                if len(batch) == 1:
                    self._raylet.notify("submit_task", pickle.dumps(batch[0]))
                else:
                    self._raylet.notify("submit_task_batch", pickle.dumps(batch))
            except Exception as e:
                # Submission is one-way; a dead local raylet surfaces as
                # stored error objects, matching the direct-notify path.
                for entry in batch:
                    try:
                        self._store_error_object(entry, e)
                    except Exception as store_err:
                        _log.warning("failed to store submit-error object for %s: %r",
                                     entry.get("task_id", "?")[:8], store_err)

    # --------------------------------------------- streaming returns
    def stream_next(self, task_id, index: int, timeout: Optional[float] = None):
        """Next item oid of a streaming task, or None at end of stream.

        Items land incrementally; the header at return index 0 closes the
        stream with the item count. What wakes a blocked call depends on
        what it can observe. While the producing task is in flight on a
        direct connection (its header id is in _fast_pending), every item
        and the header arrive as acks on that connection, and the ack
        wakes the call: no RPC, no poll. Otherwise (raylet-path task, or an
        item known to be sealed on another node) the raylet's wait_objects
        is the event-driven wait, from the first miss."""
        # core.stream_next: one span per call; `note` (its attrs) counts
        # what the call waited on, where it found the item and how its last
        # wait ended. Joins core.stream_item / core.stream_ack on (task,
        # index). raytpu_stream_next_total counts the same `woken`, always.
        note = {"waits": 0, "remote_checks": 0, "woken": "none"}
        with _tracing.span("core.stream_next") as sp:
            try:
                return self._stream_next(task_id, index, timeout, note)
            finally:
                self._m_stream_next[note["woken"]].inc()
                if sp is not None:
                    sp["attrs"].update(note, task=task_id.hex()[:24], index=index)

    def _stream_next(self, task_id, index: int, timeout: Optional[float], note: dict):
        from .object_ref import STREAM_COUNT_KEY

        header_oid = task_id.object_id_for_return(0)
        item_oid = task_id.object_id_for_return(index + 1)
        h_item, h_header = item_oid.hex(), header_oid.hex()
        memstore, pending = self._memstore, self._fast_pending
        sealed = self._stream_sealed
        deadline = None if timeout is None else time.monotonic() + timeout
        net_at: Optional[float] = None  # direct path: when the raylet net runs next
        while True:
            if h_item in memstore or self._store.contains(item_oid):
                note["found"] = "memstore" if h_item in memstore else "store"
                self._adopt_stream_item(h_item)
                if h_item in sealed:
                    with self._fast_lock:
                        sealed.discard(h_item)
                return item_oid
            # The item is known to exist but is not local (a large item made
            # on another node; its ack, or the header's count, says so):
            # only the raylet can pull it in, so no ack is waited for.
            remote = h_item in sealed
            if h_header in memstore or self._store.contains(header_oid):
                hdr = self._get_one(header_oid, None)  # raises task errors
                if index >= hdr.get(STREAM_COUNT_KEY, 0):
                    note["found"] = "header"
                    return None
                remote = True
            remaining = None if deadline is None else deadline - time.monotonic()
            if remaining is not None and remaining <= 0:
                note["woken"] = "timeout"
                raise exc.GetTimeoutError(
                    f"stream item {index} of {task_id.hex()[:12]} timed out"
                )
            poll = CONFIG.object_wait_poll_s
            if not remote and h_header in pending:
                # In flight on a direct connection: the item's ack (or the
                # header's, with the task's completion or failure) wakes
                # this wait; no RPC while acks keep coming.
                now = time.monotonic()
                if net_at is None:  # this call's first miss on the direct path
                    net_at = now + _STREAM_SILENCE_S
                if now < net_at:
                    to_net = net_at - now
                    by_net = remaining is None or to_net <= remaining
                    woke = self._await_ack(
                        [h_item, h_header],
                        to_net if by_net else remaining,
                        lambda: h_item in memstore or h_item in sealed or h_header not in pending,
                    )
                    if woke is not None:
                        note["waits"] += 1
                        if woke:
                            note["woken"] = "ack"
                        elif by_net:
                            note["woken"] = "poll"  # a lost wake-up, or a slow producer
                    continue
                # Silence (wall time, not wake-ups): the periodic net for a
                # lost ack or a dead producer node. Kept short, because an
                # ack that lands meanwhile waits for this call to return.
                net_at = now + _STREAM_SILENCE_S
                poll = 0.05
            if h_item in memstore or (not remote and h_header in memstore):
                # The ack landed after the check at the loop top (the header
                # leaves _fast_pending only after the memory store is
                # filled): the raylet never hears of an inline object.
                continue
            if remaining is not None:
                poll = max(0.05, min(poll, remaining))
            # Event-driven wait on the local raylet (pulls remote copies
            # in); returns as soon as a wanted object is local. A header
            # that is here already would only answer for the item.
            note["remote_checks"] += 1
            note["woken"] = "raylet"
            wanted = [h_item] if remote else [h_item, h_header]
            try:
                ready = self._raylet.call(
                    "wait_objects", wanted, 1, poll, True, timeout=poll + 10.0
                )
            except Exception:  # lint: swallow-ok(advisory remote check; producer-death net below)
                ready = None
            if not ready:
                # Producer-death safety net: the header's task record drives
                # retry/reconstruct or raises ObjectLostError — without this
                # a stream whose producing NODE died would block forever.
                self._maybe_recover(header_oid)

    def _adopt_stream_item(self, h: str) -> None:
        """First sight of a dynamically-created stream item: this process
        owns it (it owns the producing task). Inline items free locally;
        shm items ride the GCS directory path like normal returns."""
        with self._ref_lock:
            if h in self._owned:
                return
            self._owned.add(h)
            if h not in self._memstore:
                self._escaped.add(h)

    def stream_done(self, task_id) -> None:
        prefix = task_id.hex()[:24]
        with self._fast_lock:
            self._stream_tasks.discard(prefix)
            self._stream_sealed.difference_update(
                [h for h in self._stream_sealed if h.startswith(prefix)]
            )
        # Purge never-adopted inline items (consumer stopped early).
        for h in [k for k in self._memstore if k.startswith(prefix)]:
            with self._ref_lock:
                if h in self._owned:
                    continue
            blob = self._memstore.pop(h, None)
            if blob is not None:
                self._memstore_bytes -= len(blob)
        # Never-adopted shm items (abandoned mid-stream / trailing items):
        # adopt-and-drop so they ride the normal free path.
        from .object_ref import STREAM_COUNT_KEY

        header_oid = task_id.object_id_for_return(0)
        try:
            if self._store.contains(header_oid):
                hdr = self._get_one(header_oid, 0.5)
                count = int(hdr.get(STREAM_COUNT_KEY, 0))
                for i in range(count):
                    oid = task_id.object_id_for_return(i + 1)
                    h = oid.hex()
                    with self._ref_lock:
                        if h in self._owned:
                            continue  # adopted: the user's ref frees it
                        if not self._store.contains(oid):
                            continue
                        self._owned.add(h)
                        self._local_refs[h] = self._local_refs.get(h, 0) + 1
                    self.remove_local_ref(oid)
        except Exception:  # lint: swallow-ok(abandoned stream cleanup is best effort)
            pass

    def object_future(self, object_id: ObjectID) -> concurrent.futures.Future:
        fut: concurrent.futures.Future = concurrent.futures.Future()

        def poll():
            try:
                fut.set_result(self._get_one(object_id, None))
            except BaseException as e:  # noqa: BLE001
                fut.set_exception(e)

        threading.Thread(target=poll, daemon=True).start()
        return fut

    # -------------------------------------------------------------- tasks
    def _process_renv(self, spec: TaskSpec) -> None:
        """Driver-side runtime-env normalization: local working_dir /
        py_modules directories become content-addressed GCS packages
        (cached per env dict so a task loop zips once, not per call)."""
        renv = spec.options.runtime_env
        if not renv:
            return
        key = json.dumps(renv, sort_keys=True, default=str)
        cached = self._renv_cache.get(key)
        if cached is None:
            from .runtime_env import process_runtime_env

            cached = process_runtime_env(renv, self._gcs)
            self._renv_cache[key] = cached
        spec.options.runtime_env = cached

    def submit_task(self, spec: TaskSpec) -> List[ObjectID]:
        self._process_renv(spec)
        entry = _entry_from_spec(spec)
        spec.return_ids = [ObjectID.from_hex(h) for h in entry["return_ids"]]
        if entry.get("streaming"):
            with self._fast_lock:
                # Keyed by the 12-byte task prefix (first 24 hex chars of
                # any of the task's object ids).
                self._stream_tasks.add(spec.task_id.hex()[:24])
        self._record_submission(entry, "task")
        # Bundle-pinned tasks route straight to the node holding the reserved
        # bundle (reference: bundle scheduling bypasses the hybrid policy,
        # scheduling_policy.h NodeAffinity-like pinning).
        with _submit_span(entry):
            self._submit_entry(entry)
        return spec.return_ids

    def create_actor(self, spec: TaskSpec) -> ActorID:
        self._process_renv(spec)
        actor_id = spec.actor_id or ActorID.from_random()
        spec.actor_id = actor_id
        # The actor-launch trace (VERDICT: "actor launch is 48 ms with a
        # 10 ms fork — where are the other 38 ms?"): one parent span whose
        # context rides the creation entry, so the raylet's dispatch/spawn
        # and the worker's constructor phases parent under it and
        # `ray-tpu timeline` shows the per-phase breakdown.
        with _tracing.span("actor_launch", {"actor_id": actor_id.hex()}):
            entry = _entry_from_spec(spec)
            # Pin constructor args for the actor's lifetime: restarts re-run
            # the constructor from the registered spec, which must resolve
            # them.
            with self._ref_lock:
                for dep in entry.get("deps", []):
                    self._local_refs[dep] = self._local_refs.get(dep, 0) + 1
            entry["actor_id"] = actor_id.hex()
            blob = pickle.dumps(entry)
            # Register + place + forward collapse into ONE GCS round trip
            # (batched: the GCS groups a storm's forwards per raylet into
            # create_actor_batch calls) — the old path paid a second,
            # serial driver->raylet RPC per actor. The span keeps the
            # historical gcs_register name so launch-breakdown tooling
            # (ray-tpu timeline)
            # reads old and new traces uniformly; it now covers the
            # whole registration+submit leg.
            with _tracing.span(
                "actor_launch.gcs_register",
                {
                    # Tail of the launch flow arrow; the raylet's
                    # worker_spawn and the worker's init report the same
                    # id as flow_in, chaining register->spawn->init.
                    "flow_out": (entry.get("trace_ctx") or {}).get("flow"),
                },
            ):
                node = self._actor_batcher.create(
                    {
                        "actor_id": actor_id.hex(),
                        "spec_blob": blob,
                        # Placement bias (reference: actors use 1 CPU for
                        # SCHEDULING, 0 while alive): a DEFAULT actor holds
                        # nothing at runtime (entry["resources"] is empty)
                        # but is PLACED as if it cost a CPU, so
                        # utility-actor swarms spread instead of piling
                        # onto the most-utilized node. An EXPLICIT
                        # num_cpus=0 actor skips the bias — it must place
                        # on CPU-less custom-resource hosts.
                        "resources": entry["resources"]
                        or (
                            {"CPU": 1.0}
                            if spec.options.actor_placement_bias
                            else {}
                        ),
                        "max_restarts": spec.options.max_restarts,
                        "name": spec.options.name,
                        "namespace": spec.options.namespace,
                        "pg_id": spec.options.placement_group_id,
                        "bundle_index": spec.options.bundle_index,
                        "strategy": spec.options.scheduling_strategy,
                    }
                )
        self._actor_location[actor_id.hex()] = node["sock"]
        return actor_id

    def _raylet_for(self, sock: str) -> RpcClient:
        if sock == self._raylet.path:
            return self._raylet
        cli = self._raylet_clients.get(sock)
        if cli is None:
            cli = RpcClient(sock)
            self._raylet_clients[sock] = cli
        return cli

    def _actor_raylet(self, actor_id: ActorID) -> RpcClient:
        sock = self._actor_location.get(actor_id.hex())
        if sock is None:
            info = self._gcs.call("get_actor", actor_id.hex())
            if info is None or info.get("sock") is None:
                raise exc.ActorDiedError(
                    actor_id.hex(), (info or {}).get("death_reason", "unknown actor")
                )
            sock = info["sock"]
            self._actor_location[actor_id.hex()] = sock
        return self._raylet_for(sock)

    def submit_actor_task(self, spec: TaskSpec) -> List[ObjectID]:
        entry = _entry_from_spec(spec)
        spec.return_ids = [ObjectID.from_hex(h) for h in entry["return_ids"]]
        if entry.get("streaming"):
            with self._fast_lock:
                self._stream_tasks.add(spec.task_id.hex()[:24])
        self._record_submission(entry, "actor_task")
        with _submit_span(entry):
            self._actor_channel(spec.actor_id.hex()).submit(entry)
        return spec.return_ids

    def _actor_channel(self, actor_hex: str):
        with self._actor_channels_lock:
            ch = self._actor_channels.get(actor_hex)
            if ch is None:
                from .fastpath import ActorChannel

                ch = ActorChannel(self, actor_hex)
                self._actor_channels[actor_hex] = ch
            return ch

    def _submit_actor_slow(self, entry: dict) -> None:
        """Raylet-mediated actor submission (remote nodes, fallback)."""
        actor_id = ActorID.from_hex(entry["actor_id"])
        try:
            self._actor_raylet(actor_id).call("submit_actor_task", pickle.dumps(entry))
        except exc.ActorDiedError:
            raise
        except Exception:
            # Location may be stale (actor restarted elsewhere): refresh once.
            self._actor_location.pop(entry["actor_id"], None)
            self._actor_raylet(actor_id).call("submit_actor_task", pickle.dumps(entry))

    def cancel(self, object_id: ObjectID, force: bool = False) -> None:
        """Cancels the task producing `object_id` (reference: worker.py
        ray.cancel -> CoreWorker::CancelTask). Queued tasks are failed with
        TaskCancelledError; running tasks are interrupted (force: worker
        killed)."""
        rec = self._records.get(object_id.hex())
        if rec is None or rec.kind != "task":
            raise ValueError(
                "cancel() requires the ObjectRef of a submitted (non-actor) task"
            )
        tid = rec.entry["task_id"]
        rec.entry["max_retries"] = 0  # a cancelled task must not be retried
        if rec.entry.get("_fast"):
            # Fast-path task: it lives on a leased worker this owner chose —
            # no task-table lookup needed. The worker is interrupted and a
            # force-kill surfaces as TaskCancelledError via the lease EOF.
            self._cancelled_tids.add(tid)
            try:
                self._raylet.call(
                    "cancel_lease_task", rec.entry["_fast"], tid, force
                )
            except Exception as e:
                _log.debug("cancel_lease_task for %s failed: %r", tid[:8], e)
            return
        # Task events are batch-flushed (~0.2s): wait briefly for the
        # holding node to be known; if it stays unknown (early cancel of a
        # forwarded task), broadcast to every alive raylet.
        sock = None
        deadline = time.monotonic() + 1.0
        while True:
            st = self._gcs.call("get_task_states", [tid]).get(tid)
            if st is not None and st.get("node"):
                node = self._gcs.call("node_info", st["node"])
                if node is not None and node.get("alive"):
                    sock = node["sock"]
                break
            if time.monotonic() > deadline:
                break
            time.sleep(0.05)
        if sock is not None:
            self._raylet_for(sock).call("cancel_task", tid, force)
            return
        for n in self._gcs.call("list_nodes"):
            if n.get("Alive"):
                try:
                    self._raylet_for(n["sock"]).call("cancel_task", tid, force)
                except Exception:  # lint: swallow-ok(node may be dead; cancel is best-effort per node)
                    pass

    def kill_actor(self, actor_id: ActorID, no_restart: bool = True) -> None:
        try:
            self._actor_raylet(actor_id).call("kill_actor", actor_id.hex(), no_restart)
        except exc.ActorDiedError:
            pass
        self._actor_location.pop(actor_id.hex(), None)

    def get_named_actor(self, name: str, namespace: Optional[str]) -> ActorID:
        aid = self._gcs.call("lookup_named_actor", name, namespace)
        if aid is None:
            raise ValueError(f"Failed to look up actor with name {name!r}")
        return ActorID.from_hex(aid)

    # ------------------------------------------------------------ cluster
    def cluster_resources(self) -> Dict[str, float]:
        return self._gcs.call("cluster_resources")

    def available_resources(self) -> Dict[str, float]:
        return self._gcs.call("available_resources")

    def nodes(self) -> List[dict]:
        return self._gcs.call("list_nodes")

    def node_id(self) -> str:
        return self._node_id

    def is_driver(self) -> bool:
        return self._driver

    # ---------------------------------------------------- placement groups
    def create_placement_group(self, bundles, strategy, name=""):
        from .placement_group import PlacementGroupHandle

        pg_id = uuid.uuid4().hex
        try:
            result = self._gcs.call("create_placement_group", pg_id, bundles, strategy)
        except Exception:
            # Cannot be placed NOW: register as PENDING — creation is
            # asynchronous as in the reference (gcs_placement_group_manager
            # PENDING + autoscaler demand); ready()/wait() poll until
            # capacity (e.g. an autoscaled slice) arrives.
            self._gcs.call(
                "register_pending_placement_group", pg_id, bundles, strategy
            )
            result = {"placements": []}
        handle = PlacementGroupHandle(pg_id, bundles, strategy, name)
        handle.bundle_placements = dict(enumerate(result["placements"]))
        return handle

    def remove_placement_group(self, pg_id) -> None:
        self._gcs.call("remove_placement_group", pg_id)

    def placement_group_ready(self, pg_id, timeout=None) -> bool:
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            info = self._gcs.call("get_placement_group", pg_id)
            if info is not None and info.get("state") == "CREATED":
                return True
            if info is not None and info.get("state") == "PENDING":
                # Poller-driven retry: capacity may have arrived since.
                try:
                    if self._gcs.call("retry_pending_placement_group", pg_id):
                        return True
                except Exception:  # lint: swallow-ok(poller-driven retry; next poll covers it)
                    pass
            if deadline is None or time.monotonic() >= deadline:
                return info is not None and info.get("state") == "CREATED"
            time.sleep(0.25)

    def placement_group_table(self) -> Dict[str, dict]:
        return self._gcs.call("placement_group_table")

    # ---------------------------------------------------------- lifecycle
    def shutdown(self) -> None:
        if self._shutdown_done:
            return
        self._shutdown_done = True
        # Disarm the trigger bus first: its forwarder wraps this
        # runtime's GCS client, and anything published during or after
        # teardown (chaos injection in a later test, a watchdog tick)
        # would otherwise dial a dead control plane.
        from ..observability import postmortem as _postmortem

        _postmortem.disarm()
        self._free_wake.set()
        self._submit_wake.set()
        try:
            self._fastpath.close()
            with self._actor_channels_lock:
                channels = list(self._actor_channels.values())
            for ch in channels:
                ch.close()
        except Exception:  # lint: swallow-ok(best-effort channel close during shutdown)
            pass
        if self._driver and self._cluster is not None:
            # Raylets joined from elsewhere (start_worker_node) are not
            # this cluster's children: ask them to end themselves, as
            # their own host's `ray-tpu stop` would. The cluster's own
            # daemons are ended, reaped and swept by Cluster.shutdown.
            own = set(self._cluster._node_procs)
            try:
                joined = [
                    n for n in self.nodes() if n.get("Alive") and n["NodeID"] not in own
                ]
            except Exception as e:
                _log.warning("no node table at shutdown (%r): joined nodes are not asked to stop", e)
                joined = []
            for node in joined:
                try:
                    self._raylet_for(node["sock"]).call("stop", timeout=proctree.DAEMON_STOP_S)
                except Exception as e:
                    _log.warning("joined node %s did not stop: %r", node["NodeID"][:12], e)
            self._cluster.shutdown()
        self._store.close()
        self._gcs.close()
        self._raylet.close()
        for cli in self._raylet_clients.values():
            cli.close()


_SESSION_START_GRACE_S = 60.0


def _session_alive(session_dir: str) -> bool:
    """A session is alive iff one of its daemon sockets accepts a
    connection: gcs.sock for a head session, raylet_*.sock for a
    worker-node session created by start_worker_node (which has no GCS —
    sweeping those by gcs.sock absence would destroy a LIVE joined node's
    pool and socket), or it is still starting: its directory changed within
    the last minute (the pool file exists before the daemon's socket does,
    and another process's sweep in that interval took the node for dead)."""
    import glob as _glob

    try:
        if time.time() - os.stat(session_dir).st_mtime < _SESSION_START_GRACE_S:
            return True
    except OSError:
        pass
    candidates = [os.path.join(session_dir, "gcs.sock")]
    candidates += _glob.glob(os.path.join(session_dir, "raylet_*.sock"))
    for sock_path in candidates:
        if os.path.exists(sock_path) and proctree.uds_accepts(sock_path):
            return True
    return False


def _spawn_logged_cmd(log_dir: str, name: str, cmd: List[str]) -> subprocess.Popen:
    """Spawns a daemon with stdout/stderr captured under the session's log
    dir (reference: session_latest/logs; DEVNULLing them made any daemon
    crash undiagnosable)."""
    out = open(os.path.join(log_dir, f"{name}.out"), "ab", buffering=0)
    err = open(os.path.join(log_dir, f"{name}.err"), "ab", buffering=0)
    try:
        return subprocess.Popen(cmd, stdout=out, stderr=err)
    finally:
        out.close()
        err.close()


def _pick_store_path(session_dir: str, node_id: str, capacity: int, claimed: int = 0) -> str:
    """Object-pool file placement: tmpfs when it fits (like plasma's
    /dev/shm default — a disk-backed mmap caps put() at disk writeback
    speed), else the session dir. Pool files are sparse, so statvfs alone
    would let every node pass the same check; `claimed` counts capacity
    already promised to this cluster's earlier stores (overcommit ->
    SIGBUS)."""
    path = os.path.join(session_dir, f"store_{node_id}")
    if os.path.isdir("/dev/shm"):
        st = os.statvfs("/dev/shm")
        if st.f_bavail * st.f_frsize - claimed > capacity * 1.1:
            path = f"/dev/shm/rtpu_{os.path.basename(session_dir)}_{node_id}"
    return path


def _sweep_orphaned_pools() -> None:
    """Unlinks /dev/shm pools (and session dirs) of dead sessions: a
    SIGKILLed driver never runs atexit, and tmpfs pages would otherwise
    accumulate until /dev/shm fills (reference: ray's GC of old
    /tmp/ray/session_* dirs)."""
    import glob
    import shutil

    tmp = tempfile.gettempdir()
    alive_cache: Dict[str, bool] = {}
    for path in glob.glob("/dev/shm/rtpu_*"):
        # Name layout: rtpu_<session_basename>_<node_id>.
        base = os.path.basename(path)[len("rtpu_"):]
        session_base = base.rsplit("_", 1)[0]
        session_dir = os.path.join(tmp, session_base)
        if session_base not in alive_cache:
            alive_cache[session_base] = _session_alive(session_dir)
        if not alive_cache[session_base]:
            try:
                os.unlink(path)
            except OSError:
                pass
    for session_base, alive in alive_cache.items():
        if not alive:
            shutil.rmtree(os.path.join(tmp, session_base), ignore_errors=True)


class Cluster:
    """Multi-node-on-one-machine test cluster (reference:
    python/ray/cluster_utils.py:135 Cluster, add_node :201, remove_node
    :282 — the fixture every reference multi-node test builds on)."""

    def __init__(
        self,
        num_cpus: Optional[float] = None,
        num_tpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        object_store_memory: Optional[int] = None,
        num_workers: Optional[int] = None,
        head_port: Optional[int] = None,
        node_ip: str = "127.0.0.1",
        labels: Optional[Dict[str, Any]] = None,
    ):
        """head_port enables multi-host mode: the GCS additionally listens
        on tcp://node_ip:head_port (0 = ephemeral) and every raylet serves
        + advertises a TCP endpoint, so raylets started on OTHER hosts
        (`start_worker_node`, `ray-tpu start --address`) can join
        (reference: `ray start --head --port` bootstrapping)."""
        from ..utils.config import CONFIG

        _sweep_orphaned_pools()
        self.session_dir = tempfile.mkdtemp(prefix="ray_tpu_session_")
        self.gcs_sock = os.path.join(self.session_dir, "gcs.sock")
        self._procs: List[subprocess.Popen] = []
        self._node_procs: Dict[str, subprocess.Popen] = {}
        self._store_paths: Dict[str, str] = {}
        self._shm_claimed = 0
        self._store_capacity = int(object_store_memory or CONFIG.object_store_memory)
        self._node_ip = node_ip
        self._tcp_mode = head_port is not None

        self.log_dir = os.path.join(self.session_dir, "logs")
        os.makedirs(self.log_dir, exist_ok=True)
        self.gcs_snapshot = os.path.join(self.session_dir, "gcs_state.pkl")
        self._gcs_cmd = [sys.executable, "-m", "ray_tpu.core.gcs", self.gcs_sock, self.gcs_snapshot]
        if self._tcp_mode:
            self._gcs_cmd.append(f"tcp://{node_ip}:{head_port}")
        gcs_proc = self._spawn_logged(self._gcs_cmd, "gcs")
        self._procs.append(gcs_proc)
        self._gcs_proc = gcs_proc
        RpcClient(self.gcs_sock).call("ping")  # wait for boot
        self.gcs_tcp_address: Optional[str] = (
            self._read_announced("gcs.out", "GCS_TCP_ADDRESS=") if self._tcp_mode else None
        )
        if self._tcp_mode:
            # Pin the resolved port into the respawn command: restart_gcs
            # must come back on the address already advertised to joiners
            # (an ephemeral :0 would re-roll).
            self._gcs_cmd[-1] = self.gcs_tcp_address

        head_res = dict(resources or {})
        head_res.setdefault("CPU", float(num_cpus if num_cpus is not None else os.cpu_count() or 1))
        if num_tpus:
            head_res.setdefault("TPU", float(num_tpus))
        elif num_tpus is None and "TPU" not in head_res:
            # Autodetect through the accelerator registry (env/devdir/
            # metadata chain) so a head started on a real TPU VM registers
            # its chips without flags (reference: ray_params resolving
            # resources via the accelerator managers at node start).
            from ..accelerators import detect_accelerators

            for k, v in detect_accelerators().items():
                head_res.setdefault(k, v)
        self.head_node_id = self.add_node(
            resources=head_res, num_workers=num_workers, labels=labels
        )
        info = {
            "gcs_sock": self.gcs_sock,
            "gcs_tcp_address": self.gcs_tcp_address,
            "head_raylet_sock": self._sock_for(self.head_node_id),
            "head_store": self._store_for(self.head_node_id),
            "head_node_id": self.head_node_id,
        }
        with open(os.path.join(self.session_dir, "session.json"), "w") as f:
            json.dump(info, f)
        self._shutdown_done = False
        atexit.register(self.shutdown)

    def _read_announced(self, log_name: str, prefix: str, timeout: float = 10.0) -> str:
        """Reads a KEY=value announcement a daemon printed to its log
        (ephemeral ports are only known after bind)."""
        path = os.path.join(self.log_dir, log_name)
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            try:
                with open(path) as f:
                    for line in f:
                        if line.startswith(prefix):
                            return line[len(prefix):].strip()
            except OSError:
                pass
            time.sleep(0.05)
        raise RuntimeError(f"daemon never announced {prefix} in {log_name}")

    def _spawn_logged(self, cmd: List[str], name: str) -> subprocess.Popen:
        return _spawn_logged_cmd(self.log_dir, name, cmd)

    def _sock_for(self, node_id: str) -> str:
        return os.path.join(self.session_dir, f"raylet_{node_id}.sock")

    def _store_for(self, node_id: str) -> str:
        path = self._store_paths.get(node_id)
        if path is None:
            path = _pick_store_path(
                self.session_dir, node_id, self._store_capacity, self._shm_claimed
            )
            if path.startswith("/dev/shm/"):
                self._shm_claimed += self._store_capacity
            self._store_paths[node_id] = path
        return path

    # ---------------------------------------------------------- add node
    def add_node(
        self,
        num_cpus: Optional[float] = None,
        resources: Optional[Dict[str, float]] = None,
        num_workers: Optional[int] = None,
        labels: Optional[Dict[str, Any]] = None,
    ) -> str:
        node_id = uuid.uuid4().hex[:12]
        res = dict(resources or {})
        if num_cpus is not None:
            res["CPU"] = float(num_cpus)
        res.setdefault("CPU", 1.0)
        cmd = [
            sys.executable,
            "-m",
            "ray_tpu.core.raylet",
            node_id,
            self._sock_for(node_id),
            self._store_for(node_id),
            self.gcs_sock,
            json.dumps(res),
            str(self._store_capacity),
            json.dumps(labels or {}),
            str(num_workers if num_workers is not None else 0),
        ]
        if self._tcp_mode:
            cmd.append(f"tcp://{self._node_ip}:0")
        proc = self._spawn_logged(cmd, f"raylet_{node_id}")
        self._procs.append(proc)
        self._node_procs[node_id] = proc
        RpcClient(self._sock_for(node_id)).call("ping")
        return node_id

    def restart_gcs(self) -> None:
        """Kills and restarts the GCS daemon; state reloads from the
        snapshot and raylets re-attach (reference: GCS fault-tolerance
        tests around redis-backed restart)."""
        self._gcs_proc.kill()
        self._gcs_proc.wait(timeout=5.0)
        self._procs.remove(self._gcs_proc)
        # Same command as the original spawn: in multi-host mode the tcp://
        # endpoint must come back on the SAME port or joined hosts are
        # orphaned (their clients reconnect to the advertised address).
        self._gcs_proc = self._spawn_logged(self._gcs_cmd, "gcs")
        self._procs.append(self._gcs_proc)
        RpcClient(self.gcs_sock).call("ping")

    def remove_node(self, node_id: str) -> None:
        """Simulated node failure (reference: cluster_utils remove_node)."""
        proc = self._node_procs.pop(node_id, None)
        if proc is not None:
            proc.kill()
            proc.wait(timeout=5.0)
        try:
            RpcClient(self.gcs_sock).call("drain_node", node_id)
        except Exception:  # lint: swallow-ok(test harness remove_node; GCS health check catches it)
            pass

    def runtime(self) -> ClusterRuntime:
        rt = ClusterRuntime(
            RpcClient(self.gcs_sock),
            RpcClient(self._sock_for(self.head_node_id)),
            SharedMemoryStore(self._store_for(self.head_node_id)),
            self.head_node_id,
            session_dir=self.session_dir,
        )
        rt._cluster = self
        return rt

    def shutdown(self) -> None:
        """Ends the session: when this returns, no process of it is alive
        (core/proctree.py). Also the `atexit` hook; safe to call twice."""
        if self._shutdown_done:
            return
        self._shutdown_done = True
        atexit.unregister(self.shutdown)
        proctree.end_session(self.session_dir, self._procs)
        # Unlink tmpfs pool files (nothing reclaims /dev/shm automatically).
        for path in self._store_paths.values():
            try:
                os.unlink(path)
            except OSError:
                pass


def start_worker_node(
    gcs_address: str,
    node_ip: Optional[str] = None,
    num_cpus: Optional[float] = None,
    num_tpus: Optional[float] = None,
    resources: Optional[Dict[str, float]] = None,
    object_store_memory: Optional[int] = None,
    labels: Optional[Dict[str, Any]] = None,
) -> dict:
    """Starts a raylet on THIS host that joins a remote GCS over TCP
    (reference: `ray start --address=head:port` worker-node bootstrap).
    The raylet serves local workers over a UDS in its own session dir,
    advertises tcp://node_ip:<ephemeral> to the cluster, and hosts its own
    shm object pool. When node_ip is omitted it is derived from the route
    to the GCS (the local address of a socket connected to it) — the ip
    the head can dial back. Returns {node_id, session_dir, sock, proc}."""
    import socket as _socket

    from ..utils.config import CONFIG
    from .rpc import parse_address

    kind, target = parse_address(gcs_address)
    if kind != "tcp":
        raise ValueError("gcs_address must be tcp://host:port (the head's GCS endpoint)")
    if node_ip is None:
        probe = _socket.create_connection(target, timeout=10.0)
        try:
            node_ip = probe.getsockname()[0]
        finally:
            probe.close()
    session_dir = tempfile.mkdtemp(prefix="ray_tpu_worker_")
    log_dir = os.path.join(session_dir, "logs")
    os.makedirs(log_dir, exist_ok=True)
    node_id = uuid.uuid4().hex[:12]
    res = dict(resources or {})
    if num_cpus is not None:
        res["CPU"] = float(num_cpus)
    res.setdefault("CPU", float(os.cpu_count() or 1))
    if num_tpus:
        res.setdefault("TPU", float(num_tpus))
    elif num_tpus is None and "TPU" not in res:
        # Same registry-backed autodetection as the head: a TPU-VM worker
        # joining with `ray-tpu start --address` advertises its chips (and
        # the raylet fills in slice labels from detection).
        from ..accelerators import detect_accelerators

        for k, v in detect_accelerators().items():
            res.setdefault(k, v)
    capacity = int(object_store_memory or CONFIG.object_store_memory)
    store = _pick_store_path(session_dir, node_id, capacity)
    sock = os.path.join(session_dir, f"raylet_{node_id}.sock")
    proc = _spawn_logged_cmd(
        log_dir,
        "raylet",
        [
            sys.executable,
            "-m",
            "ray_tpu.core.raylet",
            node_id,
            sock,
            store,
            gcs_address,
            json.dumps(res),
            str(capacity),
            json.dumps(labels or {}),
            "0",  # prestart count (argv[7]; tcp spec follows)
            f"tcp://{node_ip}:0",
        ],
    )
    RpcClient(sock).call("ping")
    return {"node_id": node_id, "session_dir": session_dir, "sock": sock, "proc": proc}
