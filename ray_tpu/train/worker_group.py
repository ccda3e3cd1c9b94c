"""WorkerGroup: N training-worker actors, gang-placed.

Mirrors the reference's WorkerGroup (reference:
python/ray/train/_internal/worker_group.py:102, execute at :260): a generic
"run this function on every worker" pool of actors. TPU-native difference:
one worker == one HOST of a pod slice (SPMD: every host runs the same
program over the shared mesh), so the group also owns the rank table handed
to `jax.distributed.initialize`-style setup.
"""

from __future__ import annotations

import threading
from typing import Any, Callable, Dict, List, Optional

from .. import api
from ..core.placement_group import PlacementGroupSchedulingStrategy
from .session import TrainSession, get_session, init_session, shutdown_session


class _TrainWorker:
    """Actor body hosting one training worker (one host's SPMD process)."""

    def __init__(self, rank: int, world_size: int, target_world_size: int = 0):
        self.rank = rank
        self.world_size = world_size
        self.target_world_size = target_world_size or world_size
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None
        self._mesh = None
        self._session = None
        self._drain_flag = False

    # generic execute (reference: worker_group.py execute)
    def execute(self, fn_blob: bytes, *args, **kwargs):
        import cloudpickle

        fn = cloudpickle.loads(fn_blob)
        return fn(*args, **kwargs)

    def setup_mesh(self, mesh_spec=None):
        """Backend hook: build the device mesh this worker participates in.
        The spec resolves against THIS process's devices — the worker owns
        the accelerator; the driver never queries a backend to size it."""
        from ..parallel.mesh import build_mesh

        self._mesh = build_mesh(mesh_spec)
        return {"devices": int(self._mesh.devices.size)}

    def setup_distributed(
        self,
        coordinator: str,
        mesh_spec,
        platform=None,
        devices_per_worker=None,
        init_timeout_s: float = 60.0,
    ):
        """Multi-host backend setup: jax.distributed rendezvous, then the
        GLOBAL mesh over all hosts' devices (the analogue of
        _setup_torch_process_group, reference: train/torch/config.py:66).
        The mesh spec resolves against the global device count, which only
        this worker (post-rendezvous) knows."""
        from ..parallel.mesh import build_mesh
        from .backend import setup_jax_distributed

        info = setup_jax_distributed(
            self.rank,
            self.world_size,
            coordinator,
            platform=platform,
            devices_per_worker=devices_per_worker,
            init_timeout_s=init_timeout_s,
        )
        self._mesh = build_mesh(mesh_spec)
        info["mesh_devices"] = int(self._mesh.devices.size)
        return info

    def start_training(
        self,
        fn_blob: bytes,
        config: Dict[str, Any],
        trial_name: str,
        checkpoint_path: Optional[str],
        setup_mesh_spec="__unset__",
    ):
        import cloudpickle

        from .checkpoint import Checkpoint

        try:
            if setup_mesh_spec != "__unset__":
                # Folded-in mesh setup: a concurrent actor
                # (max_concurrency>1) gives no cross-method ordering, so
                # callers that must not block on a separate setup_mesh ack
                # pass the MeshSpec (None = default) here.
                self.setup_mesh(setup_mesh_spec)
            fn = cloudpickle.loads(fn_blob)
            ckpt = Checkpoint(checkpoint_path) if checkpoint_path else None
            session = init_session(
                world_rank=self.rank,
                world_size=self.world_size,
                trial_name=trial_name,
                checkpoint=ckpt,
                target_world_size=self.target_world_size,
            )
        except BaseException as e:  # noqa: BLE001
            # Fire-and-forget launches discard this call's ref: record the
            # failure where next_result() re-raises it, or a bad trial
            # would stall 60 s and end as a silent empty success.
            self._error = e
            raise
        session.mesh = self._mesh
        # Resolve this rank's dataset shards: a ChannelFeed handle becomes
        # a live ChannelDataIterator HERE (the reader ring must be hosted
        # by the consuming process), plain split iterators pass through.
        # Copy-not-pop: in the thread-based local runtime every worker
        # receives the SAME config dict object, so a pop by rank 0 would
        # starve the other ranks.
        shard_lists = config.get("__dataset_shards__") or {}
        for ds_name, shards in shard_lists.items():
            shard = shards[self.rank]
            session.dataset_shards[ds_name] = (
                shard.iterator() if hasattr(shard, "iterator") else shard
            )
        if shard_lists:
            config = {k: v for k, v in config.items() if k != "__dataset_shards__"}
        if self._drain_flag:
            # A drain notice landed before the session existed (restart
            # races): the new session starts pre-drained.
            session.request_drain()
        self._session = session

        def run():
            from .session import TrialAborted

            session.attach_to_current_thread()
            try:
                if _takes_config(fn):
                    fn(config)
                else:
                    fn()
            except TrialAborted:
                pass  # controller-initiated stop; not an error
            except BaseException as e:  # noqa: BLE001
                self._error = e
            finally:
                session.detach_from_current_thread()
                session.mark_finished()

        self._thread = threading.Thread(target=run, name=f"train-rank{self.rank}", daemon=True)
        self._thread.start()
        return True

    def next_result(self, timeout_s=None):
        """One reported result, None once training finished, or the
        `{"__pending__": True}` sentinel when `timeout_s` elapsed with
        nothing reported — the bounded form keeps the trainer's
        supervision loop responsive (it must notice a drain notice even
        while every worker is mid-step in a long compute)."""
        import time as _time

        # The launch is fire-and-forget and this actor runs methods on a
        # thread pool: next_result can land before start_training has
        # initialized the session — wait for it (bounded) instead of
        # reporting a phantom end-of-training. The bound must comfortably
        # exceed worst-case setup (multi-host mesh init + unpickling a
        # large closure), or a slow start reads as an empty success.
        deadline = _time.monotonic() + 600.0
        while self._session is None:
            if self._error is not None:
                raise self._error
            if _time.monotonic() > deadline:
                return None
            _time.sleep(0.02)
        session = self._session
        try:
            out = session.next_result(timeout=timeout_s)
        except TimeoutError:
            return {"__pending__": True}
        if out is None and self._error is not None:
            raise self._error
        if out is not None and out.get("checkpoint") is not None:
            out = dict(out)
            out["checkpoint"] = out["checkpoint"].path
        return out

    def stop_training(self):
        """Cancels the running training thread: the next report() inside the
        user function raises TrialAborted and the thread unwinds (no zombie
        threads blocked on the size-1 queue)."""
        if self._session is not None:
            self._session.cancel()
        return True

    def request_drain(self):
        """Relays a preemption notice into the session: the user loop's
        next `train.drain_requested()` returns True, asking for a final
        checkpoint + clean return before the node dies."""
        self._drain_flag = True
        if self._session is not None:
            self._session.request_drain()
        return True

    def join(self):
        if self._thread is not None:
            self._thread.join()
        shutdown_session(self._session)
        if self._error is not None:
            raise self._error
        return True

    def ping(self):
        return self.rank


def _takes_config(fn) -> bool:
    import inspect

    try:
        sig = inspect.signature(fn)
    except (TypeError, ValueError):
        return True
    return len(sig.parameters) >= 1


class WorkerGroup:
    """Driver-side handle to the gang of training workers."""

    def __init__(
        self,
        num_workers: int,
        resources_per_worker: Optional[Dict[str, float]] = None,
        placement_group=None,
        target_world_size: int = 0,
    ):
        self.num_workers = num_workers
        self.target_world_size = target_world_size or num_workers
        opts: Dict[str, Any] = {"max_concurrency": 4}
        res = dict(resources_per_worker or {})
        if "CPU" in res:
            opts["num_cpus"] = res.pop("CPU")
        if "TPU" in res:
            opts["num_tpus"] = res.pop("TPU")
        if res:
            opts["resources"] = res
        worker_cls = api.remote(**opts)(_TrainWorker)
        self._workers = []
        for rank in range(num_workers):
            w_opts = {}
            if placement_group is not None:
                w_opts["scheduling_strategy"] = PlacementGroupSchedulingStrategy(
                    placement_group=placement_group, placement_group_bundle_index=rank
                )
            self._workers.append(
                worker_cls.options(**w_opts).remote(
                    rank, num_workers, self.target_world_size
                )
                if w_opts
                else worker_cls.remote(rank, num_workers, self.target_world_size)
            )
        # Barrier on construction.
        api.get([w.ping.remote() for w in self._workers])

    @property
    def workers(self) -> List[Any]:
        return list(self._workers)

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Runs fn on every worker, returns all results
        (reference: worker_group.py:260)."""
        from ..core.task_spec import FunctionTable

        blob, _ = FunctionTable.dumps(fn)
        return api.get([w.execute.remote(blob, *args, **kwargs) for w in self._workers])

    def execute_single(self, rank: int, fn: Callable, *args, **kwargs) -> Any:
        from ..core.task_spec import FunctionTable

        blob, _ = FunctionTable.dumps(fn)
        return api.get(self._workers[rank].execute.remote(blob, *args, **kwargs))

    def shutdown(self):
        for w in self._workers:
            try:
                api.kill(w)
            except Exception:  # lint: swallow-ok(worker may already be dead)
                pass
        self._workers = []
