"""Worker process: long-polls its raylet for tasks and executes them.

Re-design of the reference's worker loop (reference:
python/ray/_private/workers/default_worker.py ->
CoreWorkerProcess::RunTaskExecutionLoop, core_worker_process.h:100; task
execution callback _raylet.pyx:1698 execute_task). The worker owns a full
Runtime (ClusterRuntime in worker mode), so user tasks can themselves
submit tasks, create actors, and call get/put — nested remote calls work
exactly as on the driver.

Actor concurrency (reference: actor_scheduling_queue.h,
concurrency_group_manager.h, fiber.h async actors): an actor created with
max_concurrency > 1 executes its methods on a thread pool of that width;
an actor with coroutine methods runs them on a dedicated asyncio event
loop (max_concurrency concurrent coroutines). Completion is reported
per-task to the raylet, which tracks in-flight entries by task id.

Runtime envs: the raylet spawns this process with RAY_TPU_RUNTIME_ENV
(env_vars already applied to our environment by the spawner; working_dir
applied here as cwd + sys.path entry — reference:
_private/runtime_env/working_dir.py).
"""

from __future__ import annotations

import inspect
import json
import os
import signal
import sys
import threading
from typing import Any, Dict, List, Optional

import cloudpickle

# The FULL worker stack imports at module level (not lazily inside
# main()): the zygote pre-imports this module once, so every pre-forked
# child inherits the ~2 s import graph via COW pages and its remaining
# boot is just socket connects + store attach — the "fork after the
# expensive setup, not before" half of warm-path actor launch. All of
# these are import-safe (no jax backend init; tools/check_import_safety).
from .. import exceptions as exc
from ..chaos.controller import kill_now as _chaos_kill
from ..chaos.controller import maybe_inject as _chaos_inject
from . import runtime_base, serialization
from .cluster_runtime import ClusterRuntime
from .ids import ActorID, ObjectID
from .object_transport import StoredError
from .rpc import RpcClient, _recv_msg, _send_msg
from .shm_store import SharedMemoryStore
from .task_spec import GLOBAL_FUNCTION_TABLE


def _resolve_args(store, args_blob: bytes, raylet=None):
    from .object_transport import StoredError
    from .task_spec import ArgRef

    args, kwargs = cloudpickle.loads(args_blob)

    def fetch(a):
        if isinstance(a, ArgRef):
            try:
                # timeout=None raises KeyError immediately on a miss (deps
                # were sealed before dispatch, so absent == spilled/evicted).
                v = store.get(a.object_id, timeout=None)
            except KeyError:
                # Ask the raylet to restore/re-pull the spilled dep.
                if raylet is None:
                    raise
                if not raylet.call("pull_object", a.object_id.hex(), 30.0):
                    raise
                v = store.get(a.object_id, timeout=5.0)
            if isinstance(v, StoredError):
                raise v.error
            return v
        return a

    return tuple(fetch(a) for a in args), {k: fetch(v) for k, v in kwargs.items()}


def _apply_working_dir(runtime_env: dict) -> None:
    """Applies the node-resolved runtime env: cwd + import paths
    (reference: working_dir.py chdir + py_modules.py sys.path entries;
    paths here are already local — the raylet materialized any package
    URIs before spawning us)."""
    wd = (runtime_env or {}).get("working_dir")
    if wd:
        os.chdir(wd)
        sys.path.insert(0, wd)
    for p in reversed((runtime_env or {}).get("py_modules") or []):
        if isinstance(p, str) and p not in sys.path:
            sys.path.insert(0, p)


class _AsyncLoop:
    """A dedicated asyncio event loop thread for async actors
    (reference: fiber.h / async actor event loop in _raylet.pyx).
    Named concurrency groups get independent semaphores (reference:
    concurrency_group_manager.h:34 — per-group executors)."""

    def __init__(self, concurrency: int, groups=None):
        import asyncio

        self._asyncio = asyncio
        self.loop = asyncio.new_event_loop()
        self.sem = None
        self.group_sems = {}
        self._groups = dict(groups or {})
        self.concurrency = concurrency
        t = threading.Thread(target=self._run, daemon=True, name="actor-aio")
        t.start()

    def _run(self):
        self._asyncio.set_event_loop(self.loop)
        self.sem = self._asyncio.Semaphore(self.concurrency)
        self.group_sems = {
            k: self._asyncio.Semaphore(max(1, int(v)))
            for k, v in self._groups.items()
        }
        self.loop.run_forever()

    def submit(self, coro_fn, done_cb, group=None):
        async def wrapped():
            sem = self.group_sems.get(group) or self.sem
            async with sem:
                return await coro_fn()

        fut = self._asyncio.run_coroutine_threadsafe(wrapped(), self.loop)
        fut.add_done_callback(done_cb)


def main(argv: List[str]) -> None:
    raylet_sock, store_path, gcs_sock, worker_id, node_id = argv

    # FIRST: bind SIGUSR2 (flight-recorder dump) before anything slow —
    # `ray-tpu debug dump` fans the signal out to workers, and the default
    # disposition would TERMINATE a worker that hasn't bound it yet.
    from ..observability.flight_recorder import install_crash_hooks

    install_crash_hooks("worker")
    # A crash below Python (SIGSEGV, SIGABRT in a native library, a fatal
    # runtime error) leaves every thread's stack on fd 2, the .err file whose
    # tail the raylet puts into the error the owner sees.
    import faulthandler

    faulthandler.enable()

    # Our stdout/stderr fds are the per-worker capture files the raylet
    # opened at spawn. Line-buffer them: a task's print() must reach the
    # log monitor (and the driver) when the line completes, not when a
    # 8 KiB block buffer happens to fill.
    for _stream in (sys.stdout, sys.stderr):
        try:
            _stream.reconfigure(line_buffering=True)
        except (AttributeError, ValueError, OSError):
            pass

    from ..observability import logs as _logs

    # Structured records land in worker_<id>.jsonl next to the captured
    # stdout/stderr; INFO+ records also mirror a human line to stderr so
    # user `logging` output reaches the driver console like prints do.
    _logs.configure(
        "worker",
        node_id=node_id,
        worker_id=worker_id,
        mirror_stderr=True,
        capture_root=True,
    )
    _wlog = _logs.get_logger("worker")

    import pickle
    import queue
    import socket as socketlib
    import time

    # A worker may come to own a chip: place XLA's persistent compile cache
    # before anything here can compile (import + config only, no backend).
    from ..utils import compile_cache

    compile_cache.configure()
    runtime_env = json.loads(os.environ.get("RAY_TPU_RUNTIME_ENV", "{}") or "{}")
    _apply_working_dir(runtime_env)

    store = SharedMemoryStore(store_path)
    raylet = RpcClient(raylet_sock)
    runtime = ClusterRuntime.attach(
        gcs_sock=gcs_sock,
        raylet_sock=raylet_sock,
        store_path=store_path,
        node_id=node_id,
        driver=False,
    )
    runtime._worker_id = worker_id
    runtime_base.set_runtime(runtime)
    from ..utils import internal_metrics as _imet

    # Library metrics recorded in this worker (serve/data/train/rl) flush
    # through the runtime's GCS client, labeled with this node's id.
    _imet.configure(node_id=node_id, reporter=worker_id)

    actor_instance: Dict[str, Any] = {}  # actor_id -> instance

    # ----- cancellation: SIGINT interrupts the CURRENT main-thread task ---
    executing_main = threading.Event()
    pending_interrupt = threading.Event()

    def _sigint(signum, frame):
        if executing_main.is_set():
            raise KeyboardInterrupt
        # Between poll and execution: remember it — the targeted task may be
        # the one we are about to run (verified against the raylet below).
        pending_interrupt.set()

    signal.signal(signal.SIGINT, _sigint)

    INLINE_MAX = 64 * 1024  # results below this ride the completion ack

    def _put_value(entry: dict, rid: ObjectID, value: Any, sealed: List[str]):
        """Stores one return value; returns an inline-blob dict when the
        value rode the ack instead of shm."""
        inline = entry.get("_inline")
        if inline is not None:
            try:
                blob = serialization.pack(value)
            except Exception:
                blob = None
            if blob is not None and len(blob) <= INLINE_MAX:
                return {rid.hex(): blob}
            if blob is not None:
                try:
                    store.put_raw(rid, blob)
                    sealed.append(rid.hex())
                    return None
                except exc.ObjectStoreFullError:
                    pass
        store.put_with_pressure(
            rid, value, raylet, pre_pressure=runtime.flush_local_frees
        )
        sealed.append(rid.hex())
        return None

    def _store_stream(entry: dict, result: Any, sealed: List[str]) -> None:
        """Streaming returns: each yielded value becomes return object
        index i+1, delivered to the owner AS PRODUCED (in-band stream acks
        on the direct path, seal notifications otherwise); the header at
        index 0 carries the final count (reference: the streaming
        generator protocol of _raylet.pyx:281 — per-yield object reports).
        A mid-stream exception is stored AT its item index, surfacing when
        the consumer reaches it."""
        import inspect as _inspect

        from .. import tracing as _tracing
        from .ids import TaskID
        from .object_ref import STREAM_COUNT_KEY

        tid = TaskID.from_hex(entry["task_id"])
        report = entry.get("_stream_report")

        if _inspect.isasyncgen(result):
            agen = result

            def _sync_iter():
                import asyncio

                loop = asyncio.new_event_loop()
                try:
                    while True:
                        try:
                            yield loop.run_until_complete(agen.__anext__())
                        except StopAsyncIteration:
                            return
                finally:
                    loop.close()

            result = _sync_iter()
        it = iter(result)
        count = 0
        while True:
            try:
                item = next(it)
            except StopIteration:
                break
            except BaseException as e:  # noqa: BLE001
                err = e if isinstance(e, exc.RayTpuError) else exc.TaskError(
                    e, task_desc=entry.get("desc", "")
                )
                rid = tid.object_id_for_return(count + 1)
                item_sealed: List[str] = []
                inline_d = _put_value(
                    entry, rid, StoredError(err, entry.get("desc", "")), item_sealed
                )
                if report is not None:
                    report(item_sealed, inline_d)
                if item_sealed:
                    fp_report(item_sealed, None)
                count += 1
                break
            # core.stream_item: from the item in hand to its report sent.
            # Joins core.stream_ack / core.stream_next on (task, index).
            with _tracing.span("core.stream_item") as sp:
                rid = tid.object_id_for_return(count + 1)
                item_sealed = []
                inline_d = _put_value(entry, rid, item, item_sealed)
                if report is not None:
                    report(item_sealed, inline_d)
                if item_sealed:
                    fp_report(item_sealed, None)
                if sp is not None:
                    sp["attrs"].update(
                        task=rid.hex()[:24],
                        index=count,
                        route="inline" if inline_d else "shm",
                        reported="direct" if report is not None else "seal",
                    )
            count += 1
        header_inline = _put_value(
            entry, tid.object_id_for_return(0), {STREAM_COUNT_KEY: count}, sealed
        )
        if header_inline:
            entry["_inline"].update(header_inline)

    def store_returns(entry: dict, result: Any, sealed: List[str]) -> None:
        if entry.get("streaming"):
            _store_stream(entry, result, sealed)
            return
        rids = [ObjectID.from_hex(h) for h in entry["return_ids"]]
        if len(rids) == 1:
            values = [result]
        else:
            values = list(result)
            if len(values) != len(rids):
                raise ValueError(
                    f"task returned {len(values)} values, expected {len(rids)}"
                )
        inline = entry.get("_inline")
        for rid, v in zip(rids, values):
            if inline is not None:
                # Direct task: small results return in-band to the owner's
                # memory store — no shm write, no seal/location/free churn
                # (reference: small returns inline in PushTaskReply,
                # task_manager.cc HandleTaskReturn in-memory store path).
                try:
                    blob = serialization.pack(v)
                except Exception:
                    blob = None
                if blob is not None and len(blob) <= INLINE_MAX:
                    inline[rid.hex()] = blob
                    continue
                if blob is not None:
                    try:
                        store.put_raw(rid, blob)
                        sealed.append(rid.hex())
                        continue
                    except exc.ObjectStoreFullError:
                        pass  # fall through to the pressure-aware path
            store.put_with_pressure(
                rid, v, raylet, pre_pressure=runtime.flush_local_frees
            )
            sealed.append(rid.hex())

    # Uncaught-exception reports to the GCS error table (reference: the
    # error pubsub surfacing worker exceptions at the driver / in `ray
    # list cluster-events`). One-way, bounded per process so a tight
    # failure loop cannot flood the control plane.
    error_report_budget = [200]

    def _report_task_error(entry: dict, err: BaseException) -> None:
        if isinstance(err, (exc.TaskCancelledError, SystemExit)):
            return
        if error_report_budget[0] <= 0:
            return
        error_report_budget[0] -= 1
        import traceback as _tb

        try:
            runtime._gcs.notify(
                "report_error",
                {
                    "type": "task_error",
                    "node_id": node_id,
                    "worker_id": worker_id,
                    "task_id": entry.get("task_id"),
                    "actor_id": entry.get("actor_id"),
                    "task": entry.get("desc", ""),
                    "error": repr(err),
                    "traceback": _tb.format_exc()[-4000:],
                },
            )
        except Exception:  # lint: swallow-ok(crash postmortem is best-effort; error object is the contract)
            pass

    def store_error(entry: dict, err: BaseException, sealed: List[str]) -> None:
        if not isinstance(err, exc.RayTpuError):
            err = exc.TaskError(err, task_desc=entry.get("desc", ""))
        _report_task_error(entry, err)
        inline = entry.get("_inline")
        if inline is not None:
            try:
                blob = serialization.pack(StoredError(err, entry.get("desc", "")))
                if len(blob) <= INLINE_MAX:
                    for h in entry["return_ids"]:
                        inline[h] = blob
                    return
            except Exception:  # lint: swallow-ok(inline pack failed; store path below is the fallback)
                pass
        for h in entry["return_ids"]:
            rid = ObjectID.from_hex(h)
            try:
                # Pressure-tolerant: a dropped error object turns a clean
                # task failure into an apparent object loss at the caller.
                store.put_with_pressure(
                    rid,
                    StoredError(err, entry.get("desc", "")),
                    raylet,
                    deadline_s=5.0,
                    pre_pressure=runtime.flush_local_frees,
                )
                sealed.append(rid.hex())
            except Exception as store_err:
                # A return slot with no error object hangs the caller's
                # get(); the loss must be loud in the worker log.
                _wlog.warning("failed to store error object %s: %r",
                              rid.hex()[:8], store_err)

    def bind_method(inst, name: str):
        """User method, or a framework builtin for reserved names — the
        compiled-DAG entry points ride the normal actor-task path under
        `__ray_dag_*__` names (reference: do_exec_tasks being a framework
        function executed as an actor task, compiled_dag_node.py:133)."""
        if name.startswith("__ray_dag_"):
            from .dag_exec import bind_builtin

            return bind_builtin(inst, name)
        if name == "__ray_tpu_collective_init__":
            from ..collective import init_collective_group

            def _collective_init(ws, rank, gname):
                init_collective_group(ws, rank, gname)
                return True

            return _collective_init
        if name == "__ray_tpu_collective_destroy__":
            # Gang teardown entry used by cgraph communicators (and any
            # driver-side group manager): drops this process's membership
            # and deregisters its rank from the GCS rendezvous.
            from ..collective import destroy_collective_group

            def _collective_destroy(gname):
                destroy_collective_group(gname)
                return True

            return _collective_destroy
        return getattr(inst, name)

    def run_body(entry: dict, sealed: List[str]) -> bool:
        """Executes one entry body synchronously (any thread)."""
        from .runtime_context import reset_task_context, set_task_context

        from .. import tracing as _tracing
        from ..observability.flight_recorder import record as _fr

        kind = entry["type"]
        # Always-on black box: the last events before a hang/crash name
        # the task being executed (complements the opt-in spans).
        _fr("task.exec", (kind, (entry.get("task_id") or "")[:16]))
        token = set_task_context(entry.get("task_id"), entry.get("actor_id"))
        try:
            # Chaos hook: kill this worker mid-task (SIGKILL — the
            # monitor loop sees an unexplained death, exactly like an
            # OOM/preemption), fail the task, or stall it. The detail is
            # "<desc>@<attempt>" so a rule can target one function
            # (match "flaky") or one attempt (match "flaky@0" — kills
            # the first execution everywhere while every retry, which
            # may land in a fresh worker process with fresh per-process
            # rule counters, survives deterministically).
            rule = _chaos_inject(
                "task.exec",
                f"{entry.get('desc') or kind}@{entry.get('attempt', 0)}",
            )
            if rule is not None:
                if rule.action == "kill":
                    _chaos_kill("task.exec", entry.get("desc", ""))
                elif rule.action == "delay":
                    import time as _t

                    _t.sleep(rule.delay_s)
                elif rule.action == "raise":
                    raise RuntimeError(
                        f"chaos: injected task failure in {entry.get('desc', kind)}"
                    )
            # Execution span parented to the submitter's span via the
            # propagated context (reference: tracing_helper.py:92 —
            # _span_wrapper around task execution).
            with _tracing.continue_context(
                entry.get("trace_ctx"),
                f"run {entry.get('desc') or kind}",
                {"task_id": entry.get("task_id", "")},
            ):
                if kind == "task":
                    fn = GLOBAL_FUNCTION_TABLE.loads(entry["func_blob"], entry["func_hash"])
                    args, kwargs = _resolve_args(store, entry["args_blob"], raylet)
                    result = fn(*args, **kwargs)
                    if inspect.iscoroutine(result):
                        import asyncio

                        result = asyncio.run(result)
                    store_returns(entry, result, sealed)
                    return True
                if kind == "actor_task":
                    inst = actor_instance.get(entry["actor_id"])
                    if inst is None:
                        raise RuntimeError("actor instance missing in worker")
                    method = bind_method(inst, entry["method_name"])
                    args, kwargs = _resolve_args(store, entry["args_blob"], raylet)
                    result = method(*args, **kwargs)
                    if inspect.iscoroutine(result):
                        import asyncio

                        result = asyncio.run(result)
                    store_returns(entry, result, sealed)
                    return True
                return True
        except SystemExit:
            store_returns(entry, None, sealed)
            raise
        except KeyboardInterrupt:
            store_error(
                entry,
                exc.TaskCancelledError(f"{entry.get('desc','task')} was cancelled"),
                sealed,
            )
            return False
        except BaseException as e:  # noqa: BLE001
            store_error(entry, e, sealed)
            return False
        finally:
            reset_task_context(token)
            # A worker is killed, not stopped: what this task buffered is
            # written now (off: one test of the flag).
            _tracing.flush()

    def done(entry: dict, ok: bool, sealed: List[str]) -> None:
        raylet.notify("worker_done", worker_id, ok, sealed, entry.get("task_id"))

    # ----- direct (leased / fast-path) service ----------------------------
    # Every worker serves a UDS next to the raylet socket; owners holding a
    # lease (or an actor handle) push task frames here directly, skipping
    # the raylet on the hot path (reference: CoreWorker's PushTask server,
    # core_worker.cc HandlePushTask). Completion acks ride the same socket;
    # seal locations + task events flow to the raylet in coalesced one-way
    # batches so the GCS directory and waiters still learn of results.
    direct_sock_path = os.path.join(
        os.path.dirname(raylet_sock) or ".", f"wkr_{worker_id}.sock"
    )
    direct_inbox: "queue.Queue" = queue.Queue()
    direct_conns: set = set()
    accept_count = [0]
    exec_lock = threading.Lock()  # serializes serial-lane execution across
    # the main loop and direct connection threads (an actor with
    # max_concurrency=1 must never run two methods at once).
    notify_q: "queue.Queue" = queue.Queue()

    def _notify_loop() -> None:
        cli = RpcClient(raylet_sock)
        while True:
            first = notify_q.get()
            time.sleep(0.001)  # coalesce a burst into one raylet message
            batch = [first]
            while True:
                try:
                    batch.append(notify_q.get_nowait())
                except queue.Empty:
                    break
            sealed = [h for s, _ in batch for h in s]
            events = [e for _, e in batch if e is not None]
            try:
                cli.notify("fastpath_done", worker_id, sealed, events)
            except Exception:
                return  # raylet gone; the worker is about to die anyway

    threading.Thread(target=_notify_loop, daemon=True, name="fp-notify").start()

    def fp_report(sealed: List[str], event) -> None:
        notify_q.put((sealed, event))

    _dbg = os.environ.get("RAY_TPU_DEBUG_DIRECT") == "1"

    def _dlog(msg: str) -> None:
        if _dbg:
            _wlog.info("[direct %s] %s", worker_id[:6], msg)

    # ----- concurrent actor executors -------------------------------------
    pool: Optional[Any] = None  # ThreadPoolExecutor for threaded actors
    group_pools: Dict[str, Any] = {}  # named concurrency groups
    aio: Optional[_AsyncLoop] = None

    def create_actor(entry: dict, sealed: List[str]) -> bool:
        nonlocal pool, aio
        from .. import tracing as _tracing
        from .runtime_context import set_task_context

        set_task_context(entry.get("task_id"), entry.get("actor_id"))
        try:
            cls = GLOBAL_FUNCTION_TABLE.loads(entry["func_blob"], entry["func_hash"])
            args, kwargs = _resolve_args(store, entry["args_blob"], raylet)
            # The final actor-launch phase: constructor execution in the
            # (possibly freshly forked) worker, parented to the driver's
            # actor_launch span via the propagated context.
            with _tracing.continue_context(
                entry.get("trace_ctx"),
                "actor_launch.init",
                {"actor_id": entry.get("actor_id", "")},
            ):
                inst = cls(*args, **kwargs)
            _tracing.flush()
            actor_instance[entry["actor_id"]] = inst
            mc = int(entry.get("max_concurrency", 1) or 1)
            cgroups = entry.get("concurrency_groups") or {}
            # Scan the CLASS, not the instance: getattr on the instance
            # would execute @property getters during creation.
            has_async = any(
                inspect.iscoroutinefunction(getattr(type(inst), m, None))
                for m in dir(type(inst))
                if not m.startswith("_")
            )
            if has_async:
                aio = _AsyncLoop(max(1, mc), groups=cgroups)
            elif mc > 1 or cgroups:
                import concurrent.futures

                # Default pool runs ungrouped methods at max_concurrency;
                # each named group gets its own executor of its declared
                # width (reference: concurrency_group_manager.h:34).
                pool = concurrent.futures.ThreadPoolExecutor(
                    max_workers=max(1, mc), thread_name_prefix="actor"
                )
                for gname, width in cgroups.items():
                    group_pools[gname] = concurrent.futures.ThreadPoolExecutor(
                        max_workers=max(1, int(width)),
                        thread_name_prefix=f"cg-{gname}",
                    )
            store_returns(entry, None, sealed)
            return True
        except SystemExit:
            store_returns(entry, None, sealed)
            raise
        except BaseException as e:  # noqa: BLE001
            store_error(entry, e, sealed)
            return False

    def exec_actor_task_async(entry: dict, report=None) -> None:
        """Runs an async actor method on the event loop."""
        if report is None:
            report = done
        inst = actor_instance.get(entry["actor_id"])

        async def coro():
            import asyncio

            from .runtime_context import set_task_context

            # Scoped to this asyncio task's context copy; no reset needed.
            set_task_context(entry.get("task_id"), entry.get("actor_id"))
            # Arg resolution can block (remote/spilled deps): keep it off
            # the event loop thread or all concurrent coroutines stall.
            args, kwargs = await asyncio.get_running_loop().run_in_executor(
                None, _resolve_args, store, entry["args_blob"], raylet
            )
            method = bind_method(inst, entry["method_name"])
            result = method(*args, **kwargs)
            if inspect.iscoroutine(result):
                result = await result
            return result

        def finish(fut):
            sealed: List[str] = []
            try:
                result = fut.result()
                store_returns(entry, result, sealed)
                report(entry, True, sealed)
            except SystemExit:
                store_returns(entry, None, sealed)
                report(entry, True, sealed)
                os._exit(0)
            except BaseException as e:  # noqa: BLE001
                store_error(entry, e, sealed)
                report(entry, False, sealed)

        def on_done(fut):
            # Completion does shm writes + a raylet RPC: run it OFF the
            # event loop thread or concurrent coroutines stall behind it.
            threading.Thread(target=finish, args=(fut,), daemon=True).start()

        aio.submit(coro, on_done, _group_for(entry))

    def _group_for(entry: dict):
        g = entry.get("concurrency_group")
        if g:
            return g
        # Fallback to the method's decorator-declared group: handles from
        # get_actor() (dynamic, no method metadata) must still route.
        inst = actor_instance.get(entry.get("actor_id") or "")
        if inst is None or not entry.get("method_name"):
            return None
        m = getattr(type(inst), entry["method_name"], None)
        return getattr(m, "__ray_tpu_method_options__", {}).get("concurrency_group")

    def exec_threaded(entry: dict, report=None) -> None:
        if report is None:
            report = done
        target_pool = group_pools.get(_group_for(entry)) or pool
        def run():
            sealed: List[str] = []
            try:
                ok = run_body(entry, sealed)
            except SystemExit:
                report(entry, True, sealed)
                os._exit(0)
                return
            report(entry, ok, sealed)

        target_pool.submit(run)

    # ----- direct server --------------------------------------------------
    def _exec_direct_actor(entry: dict, send_done) -> None:
        """An actor call arriving on the direct socket. Serial actors run
        inline on the connection thread (strict per-connection FIFO, which
        IS the per-caller order); concurrent actors dispatch to their pool
        or event loop exactly like the raylet path."""

        def report(e: dict, ok: bool, sealed: List[str]) -> None:
            send_done(e["task_id"], ok, sealed, e.get("_inline"))
            fp_report(sealed, (e["task_id"], "FINISHED" if ok else "FAILED"))

        if aio is not None:
            exec_actor_task_async(entry, report)
            return
        if pool is not None:
            exec_threaded(entry, report)
            return
        sealed: List[str] = []
        with exec_lock:
            ok = run_body(entry, sealed)
        report(entry, ok, sealed)

    def _make_stream_report(send_raw):
        def report(sealed: List[str], inline) -> None:
            try:
                send_raw(("si", sealed, inline))
            except OSError:
                pass  # consumer gone; items are in shm/dropped regardless

        return report

    conn_senders: Dict[Any, Any] = {}
    lease_revoked = [False]  # sticky until the lease is returned: a revoke
    # can land before the owner's connect (worker-boot race) and must
    # still reach that owner when it arrives

    def _conn_loop(conn) -> None:
        wlock = threading.Lock()

        def send_raw(frame: tuple) -> None:
            with wlock:
                _send_msg(conn, pickle.dumps(frame))

        conn_senders[conn] = send_raw
        if lease_revoked[0]:
            try:
                send_raw(("r",))
            except OSError:
                pass

        def send_done(tid: str, ok: bool, sealed: List[str], inline=None) -> None:
            try:
                send_raw(("d", tid, ok, sealed, inline or None))
            except OSError:
                pass  # owner gone; results are sealed regardless

        try:
            while True:
                try:
                    frame = pickle.loads(_recv_msg(conn))
                except (ConnectionError, OSError, EOFError):
                    _dlog("conn EOF")
                    break
                kind = frame[0]
                if _dbg and kind != "t":
                    _dlog(f"frame {kind!r}")
                if kind == "t":
                    # Leased normal task: the main thread executes it (keeps
                    # SIGINT cancellation + serial semantics).
                    _, tid, fh, fb, ab, rids, desc, streaming = frame[:8]
                    entry = {
                        "type": "task",
                        "task_id": tid,
                        "trace_ctx": frame[8] if len(frame) > 8 else None,
                        "func_hash": fh,
                        "func_blob": fb,
                        "args_blob": ab,
                        "return_ids": rids,
                        "desc": desc,
                        "streaming": streaming,
                        "_inline": {},
                    }
                    if streaming:
                        entry["_stream_report"] = _make_stream_report(send_raw)
                    direct_inbox.put((entry, send_done))
                elif kind == "a":
                    _, tid, aid, method, ab, rids, desc, streaming, cgroup = frame[:9]
                    entry = {
                        "type": "actor_task",
                        "task_id": tid,
                        "trace_ctx": frame[9] if len(frame) > 9 else None,
                        "actor_id": aid,
                        "method_name": method,
                        "args_blob": ab,
                        "return_ids": rids,
                        "desc": desc,
                        "streaming": streaming,
                        "concurrency_group": cgroup,
                        "_inline": {},
                    }
                    if streaming:
                        entry["_stream_report"] = _make_stream_report(send_raw)
                    _exec_direct_actor(entry, send_done)
                elif kind == "rv":
                    _dlog(f"revoke received; relaying to {len(conn_senders)} conns")
                    lease_revoked[0] = True
                    # Raylet revoked this worker's lease: relay a drain
                    # request to every connected owner; they stop pushing,
                    # outstanding work completes, sockets close, and the
                    # main loop hands the worker back to the pool.
                    for sender in list(conn_senders.values()):
                        try:
                            sender(("r",))
                        except OSError:
                            pass
                elif kind == "p":
                    send_raw(("p",))
        finally:
            conn_senders.pop(conn, None)
            direct_conns.discard(conn)
            try:
                conn.close()
            except OSError:
                pass

    def _serve_direct() -> None:
        try:
            srv = socketlib.socket(socketlib.AF_UNIX, socketlib.SOCK_STREAM)
            try:
                os.unlink(direct_sock_path)
            except OSError:
                pass
            srv.bind(direct_sock_path)
            srv.listen(128)
        except BaseException as e:  # noqa: BLE001
            _dlog(f"direct server failed to bind: {e!r}")
            raise
        _dlog(f"direct server listening at {direct_sock_path}")
        while True:
            try:
                conn, _ = srv.accept()
            except OSError as e:
                _dlog(f"accept failed: {e!r}")
                return
            direct_conns.add(conn)
            accept_count[0] += 1
            _dlog(f"accepted conn #{accept_count[0]}")
            threading.Thread(
                target=_conn_loop, args=(conn,), daemon=True, name="direct-conn"
            ).start()

    threading.Thread(target=_serve_direct, daemon=True, name="direct-srv").start()

    def _run_direct_mode(lease_token=None) -> None:
        """Lease mode: drain direct-pushed tasks on the main thread until
        the lease owner disconnects, then hand the worker back to the
        raylet pool (reference: the leased worker returning to the raylet
        after lease_expiration, normal_task_submitter.cc ReturnWorker).
        `lease_token` is echoed on the return so the raylet can tell THIS
        lease epoch's return from a stale one (None on the lost-control-
        message belt re-entry, which releases nothing)."""
        entered = time.monotonic()
        epoch_accepts = accept_count[0]
        last_lease_check = time.monotonic()
        cancel_scan = False  # an interrupt arrived for a task further down
        # the queue: verify each task against the raylet until it is found
        _dlog("enter direct mode")
        while True:
            try:
                entry, send_done = direct_inbox.get(timeout=0.25)
            except queue.Empty:
                if not direct_conns and (
                    # a conn came and went (accept counter moved — conns
                    # can live shorter than this poll period), or the
                    # lease is known-revoked, or nobody ever showed up
                    accept_count[0] > epoch_accepts
                    or lease_revoked[0]
                    or time.monotonic() - entered > 10.0
                ):
                    # Final drain: pushes that raced the revoke/close are
                    # still valid work — execute them before handing back
                    # (their acks are the owner's only completion signal).
                    while True:
                        try:
                            entry, send_done = direct_inbox.get_nowait()
                        except queue.Empty:
                            break
                        sealed: List[str] = []
                        ok = run_body(entry, sealed)
                        send_done(entry["task_id"], ok, sealed, entry.get("_inline"))
                        fp_report(
                            sealed,
                            (entry["task_id"], "FINISHED" if ok else "FAILED"),
                        )
                    _dlog("exit direct mode")
                    lease_revoked[0] = False  # next lease: fresh epoch
                    return
                now = time.monotonic()
                if now - last_lease_check > 5.0:
                    # Belt for a lost revoke: if the raylet no longer holds
                    # our lease, drain the owners and hand ourselves back.
                    last_lease_check = now
                    try:
                        if not raylet.call("lease_active", worker_id, timeout=5.0):
                            _dlog("lease gone; draining owners")
                            lease_revoked[0] = True
                            for sender in list(conn_senders.values()):
                                try:
                                    sender(("r",))
                                except OSError:
                                    pass
                    except Exception:  # lint: swallow-ok(lease-poll hiccup; next poll retries)
                        pass
                continue
            _dlog(f"exec {entry.get('task_id','?')[:8]}")
            sealed: List[str] = []
            ok = False
            executing_main.set()
            try:
                if pending_interrupt.is_set() or cancel_scan:
                    pending_interrupt.clear()
                    if raylet.call("is_cancelled", entry["task_id"]):
                        cancel_scan = False
                        raise KeyboardInterrupt
                with exec_lock:
                    ok = run_body(entry, sealed)
            except KeyboardInterrupt:
                # The SIGINT cancel protocol names no task: confirm THIS
                # task was the target; if not, the victim is retried and
                # later tasks are scanned until the real target surfaces.
                try:
                    was_target = raylet.call("is_cancelled", entry["task_id"])
                except Exception:
                    was_target = True
                if was_target or sealed:
                    store_error(
                        entry,
                        exc.TaskCancelledError(
                            f"{entry.get('desc','task')} was cancelled"
                        ),
                        sealed,
                    )
                else:
                    cancel_scan = True
                    try:
                        with exec_lock:
                            ok = run_body(entry, sealed)
                    except KeyboardInterrupt:
                        store_error(
                            entry,
                            exc.TaskCancelledError(
                                f"{entry.get('desc','task')} was cancelled"
                            ),
                            sealed,
                        )
            except SystemExit:
                executing_main.clear()
                send_done(entry["task_id"], True, sealed, entry.get("_inline"))
                fp_report(sealed, (entry["task_id"], "FINISHED"))
                raylet.notify("return_worker_lease", worker_id, lease_token)
                os._exit(0)
            finally:
                executing_main.clear()
            send_done(entry["task_id"], ok, sealed, entry.get("_inline"))
            fp_report(sealed, (entry["task_id"], "FINISHED" if ok else "FAILED"))

    # Serial-path completions piggyback on the next poll (worker_step):
    # one RPC per task instead of done-notify + poll. Threaded/async actor
    # paths still report via worker_done from their own threads.
    step_done: Optional[dict] = None
    while True:
        try:
            msg = raylet.call("worker_step", worker_id, step_done, timeout=60.0)
        except Exception:
            return  # raylet gone
        step_done = None
        kind = msg.get("type")
        if kind == "stop":
            return
        if kind == "direct" or (kind == "noop" and not direct_inbox.empty()):
            # Leased to an owner for direct pushes (the inbox check is the
            # belt for a lost control message: direct frames queued while
            # we idled in worker_step still get served).
            token = msg.get("token")
            _run_direct_mode(token)
            raylet.notify("return_worker_lease", worker_id, token)
            continue
        if kind == "noop":
            continue
        if kind == "task":
            entry = msg["entry"]
            if entry["type"] == "actor_creation":
                sealed: List[str] = []
                try:
                    ok = create_actor(entry, sealed)
                except SystemExit:
                    done(entry, True, sealed)
                    return
                done(entry, ok, sealed)
                continue
            if entry["type"] == "actor_task" and aio is not None:
                exec_actor_task_async(entry)
                continue
            if entry["type"] == "actor_task" and pool is not None:
                exec_threaded(entry)
                continue
            # Serial path (normal tasks + max_concurrency=1 actors): runs in
            # the main thread so cancel-via-SIGINT can interrupt it.
            sealed = []
            executing_main.set()
            try:
                if pending_interrupt.is_set():
                    # A SIGINT landed before execution started: honor it
                    # only if OUR task is the cancel target (a late signal
                    # for an already-finished task must not kill this one).
                    pending_interrupt.clear()
                    if raylet.call("is_cancelled", entry["task_id"]):
                        raise KeyboardInterrupt
                with exec_lock:
                    ok = run_body(entry, sealed)
            except KeyboardInterrupt:
                store_error(
                    entry,
                    exc.TaskCancelledError(
                        f"{entry.get('desc','task')} was cancelled"
                    ),
                    sealed,
                )
                ok = False
            except SystemExit:
                executing_main.clear()
                done(entry, True, sealed)
                return
            finally:
                executing_main.clear()
            step_done = {"ok": ok, "sealed": sealed, "task_id": entry.get("task_id")}


if __name__ == "__main__":
    main(sys.argv[1:])
