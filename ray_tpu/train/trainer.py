"""JaxTrainer: the DataParallelTrainer equivalent, TPU-native.

Reference call stack being re-designed (SURVEY.md §3.3):
BaseTrainer.fit (python/ray/train/base_trainer.py:567) ->
DataParallelTrainer.training_loop (data_parallel_trainer.py:428) ->
BackendExecutor.start (train/_internal/backend_executor.py:135) ->
WorkerGroup actors + NCCL process group (torch/config.py:66).

TPU-native shape: the trainer creates a gang of worker actors (one per
host), each worker builds its shard of a `jax.sharding.Mesh` from the
ScalingConfig's MeshSpec, and the user's `train_loop_per_worker` runs the
same jitted SPMD program on every host — collectives compile into the
program over ICI; there is no out-of-band process group to bootstrap.
Results flow back through the size-1 session queue exactly as in the
reference (TrainingIterator, train/trainer.py:124).
"""

from __future__ import annotations

import dataclasses
import time
import uuid
from typing import Any, Callable, Dict, List, Optional, Set

from .. import api
from .. import exceptions as exc
from .. import tracing as _tracing
from ..core import runtime_base
from ..core.placement_group import placement_group as create_pg
from ..observability import goodput as _goodput
from ..observability.flight_recorder import record as _flight_record
from ..utils import internal_metrics as imet
from ..utils import node_events
from ..utils.node_events import NodeEventWatcher
from .checkpoint import Checkpoint, CheckpointManager, StorageContext
from .config import CheckpointConfig, FailureConfig, RunConfig, ScalingConfig
from .worker_group import WorkerGroup

# Preemptions are capacity events, not training failures: they retry on
# their own (bounded) budget instead of burning FailureConfig.max_failures.
MAX_PREEMPTION_RETRIES = 16
# How long fit() waits for replacement capacity after a preemption before
# downsizing (elastic) or failing fast with CapacityTimeoutError
# (ScalingConfig.capacity_wait_s overrides; the autoscaler's replace loop
# normally lands a slice well inside this).
CAPACITY_WAIT_S = 120.0


class _ElasticGrow(Exception):
    """Internal control flow: capacity for the full target gang returned
    and a checkpoint just landed — re-form the gang at target size."""


@dataclasses.dataclass
class Result:
    """(reference: python/ray/air/result.py Result)"""

    metrics: Dict[str, Any]
    checkpoint: Optional[Checkpoint]
    path: str
    metrics_dataframe: Optional[Any] = None
    error: Optional[BaseException] = None

    @property
    def best_checkpoints(self) -> List[Checkpoint]:
        return [self.checkpoint] if self.checkpoint else []


class JaxTrainer:
    """Distributed SPMD training over a worker gang.

    Usage (mirrors the reference's TorchTrainer surface so call sites port
    mechanically):

        def train_loop(config):
            mesh = train.get_mesh()
            ... jitted step over the mesh ...
            train.report({"loss": ...}, checkpoint=...)

        trainer = JaxTrainer(
            train_loop,
            train_loop_config={"lr": 1e-3},
            scaling_config=ScalingConfig(num_workers=1, mesh=MeshSpec(data=-1)),
            run_config=RunConfig(name="exp"),
        )
        result = trainer.fit()
    """

    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        dataset_config: str = "object_store",
        resume_from_checkpoint: Optional[Checkpoint] = None,
    ):
        self._train_loop = train_loop_per_worker
        self._config = dict(train_loop_config or {})
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self._datasets = dict(datasets or {})
        # "object_store": each rank pulls its shard's blocks by ref;
        # "channel": each rank ingests over a persistent channel feed
        # (data/feed.py — a BlockFeeder actor pushes blocks through a
        # shared-memory ring, overlapping the object-plane fetch with the
        # consumer's step so data_wait collapses).
        if dataset_config not in ("object_store", "channel"):
            raise ValueError(
                f"dataset_config must be 'object_store' or 'channel', got {dataset_config!r}"
            )
        self._dataset_config = dataset_config
        self._resume_from = resume_from_checkpoint

    # ------------------------------------------------------------------ fit
    def fit(self) -> Result:
        name = self.run_config.name or f"JaxTrainer_{uuid.uuid4().hex[:8]}"
        storage = StorageContext(self.run_config.resolved_storage_path(), name)
        ckpt_cfg: CheckpointConfig = self.run_config.checkpoint_config
        manager = CheckpointManager(
            num_to_keep=ckpt_cfg.num_to_keep,
            score_attribute=ckpt_cfg.checkpoint_score_attribute,
            score_order=ckpt_cfg.checkpoint_score_order,
        )
        max_failures = self.run_config.failure_config.max_failures
        attempt = 0
        preemptions = 0
        resume_ckpt = self._resume_from
        last_error: Optional[BaseException] = None
        metrics: Dict[str, Any] = {}
        # Goodput ledger: fit() is the one supervisor that sees every
        # lifecycle transition, so it owns the category switches
        # (observability/goodput.py). Public for inspection/tests.
        self.goodput = _goodput.GoodputAccountant()
        restored = False  # next attempt recomputes lost steps first
        sc = self.scaling_config
        # Elastic world size: the gang the NEXT attempt launches with.
        # Starts at target; _renegotiate_capacity moves it down when
        # replacement capacity misses the wait budget, _ElasticGrow moves
        # it back to target at a checkpoint boundary.
        self._world_size = sc.num_workers
        wait_budget = (
            sc.capacity_wait_s if sc.capacity_wait_s is not None else CAPACITY_WAIT_S
        )

        while True:
            try:
                metrics = self._run_attempt(
                    storage, manager, resume_ckpt, rework=restored,
                    world_size=self._world_size,
                )
                last_error = None
                break
            except (KeyboardInterrupt, SystemExit):
                raise  # user abort is not a training failure
            except _ElasticGrow:
                # Full-target capacity returned and a checkpoint just
                # landed: re-form the gang at target size, resume
                # same-step. Not a failure and not a preemption — it
                # consumes neither retry budget.
                metrics = getattr(self, "_last_metrics", {})
                resume_ckpt = manager.latest_checkpoint or resume_ckpt
                self._world_size = sc.num_workers
                restored = True
                imet.TRAIN_ELASTIC_RESIZES.inc(direction="growback")
                _flight_record("train.elastic_growback", (sc.num_workers,))
                if resume_ckpt is not None:
                    imet.CHECKPOINTS_RESTORED.inc()
            except exc.PreemptionError as e:
                # A preemption notice drained the gang: this is a
                # capacity event, not a training failure — restore on the
                # replacement slice without burning max_failures
                # (bounded by its own budget so a flapping cluster still
                # terminates).
                last_error = e
                metrics = getattr(self, "_last_metrics", {})
                preemptions += 1
                resume_ckpt = manager.latest_checkpoint or resume_ckpt
                if preemptions > MAX_PREEMPTION_RETRIES:
                    break
                if resume_ckpt is not None:
                    imet.CHECKPOINTS_RESTORED.inc()
                restored = True
                _flight_record(
                    "train.restore",
                    (resume_ckpt.path if resume_ckpt else None, preemptions),
                )
                # Waiting out replacement capacity is drain-wait time.
                self.goodput.begin(_goodput.DRAIN_WAIT)
                if not self._renegotiate_capacity(wait_budget):
                    # No feasible gang inside the budget: fail fast with
                    # the typed capacity error instead of launching a
                    # doomed attempt against an empty cluster.
                    err = self._capacity_error
                    if err is not None:
                        err.__cause__ = e
                        last_error = err
                    break
            except Exception as e:  # noqa: BLE001
                last_error = e
                metrics = getattr(self, "_last_metrics", {})
                attempt += 1
                # Elastic restart from the latest checkpoint (reference:
                # FailureConfig via Tune, base_trainer.py:577 resume path).
                resume_ckpt = manager.latest_checkpoint or resume_ckpt
                if max_failures >= 0 and attempt > max_failures:
                    break
                if resume_ckpt is not None:
                    imet.CHECKPOINTS_RESTORED.inc()
                    restored = True
                    _flight_record("train.restore", (resume_ckpt.path, attempt))

        self.goodput.finish()
        snap = self.goodput.snapshot()
        metrics = dict(metrics)
        metrics["goodput"] = snap["goodput"]
        metrics["goodput_seconds"] = snap["seconds"]
        # once=True: the terminal value ships on one flush and then stops
        # re-reporting — a finished run's low goodput must not pin the
        # goodput_floor alert for the life of the driver process.
        imet.TRAIN_GOODPUT.set(snap["goodput"], once=True, trial=name)
        storage.write_json(
            "result.json",
            {"metrics": metrics, "error": repr(last_error) if last_error else None},
        )
        return Result(
            metrics=metrics,
            checkpoint=manager.best_checkpoint or manager.latest_checkpoint,
            path=storage.trial_dir,
            error=last_error,
        )

    def _feasible_workers(self) -> int:
        """How many gang workers the cluster could EVER host right now:
        sum over alive, non-draining nodes of total-capacity fits (total,
        not currently-available — the restore attempt frees its own
        resources). Local mode reports the configured target (nothing to
        negotiate against)."""
        sc = self.scaling_config
        need = dict(sc.resources_per_worker or {"CPU": 1.0})
        rt = runtime_base.current_runtime()
        if getattr(rt, "_gcs", None) is None:
            return sc.num_workers
        try:
            nodes = rt.nodes()
        except Exception:
            return 0
        # STRICT_SPREAD places at most one bundle per node: feasibility is
        # the number of fitting NODES, not the sum of per-node fits —
        # otherwise the renegotiation green-lights a world the placement
        # group can never form and the attempt burns max_failures instead
        # of downsizing.
        one_per_node = sc.placement_strategy == "STRICT_SPREAD"
        total = 0
        for n in nodes:
            if not n.get("Alive") or n.get("Draining"):
                continue
            res = n.get("Resources") or {}
            fits = [int(res.get(k, 0.0) // v) for k, v in need.items() if v > 0]
            per_node = max(0, min(fits)) if fits else 1
            total += min(per_node, 1) if one_per_node else per_node
        return total

    def _wait_for_capacity(
        self, n_workers: Optional[int] = None, timeout_s: float = CAPACITY_WAIT_S
    ) -> bool:
        """Blocks until the cluster can host an `n_workers` gang. Wakes on
        node_events (node_added / node_draining / node_dead published by
        the GCS) with a 1 s re-check as fallback — not a 4 Hz node-table
        poll."""
        need = n_workers if n_workers is not None else self.scaling_config.num_workers
        rt = runtime_base.current_runtime()
        gcs = getattr(rt, "_gcs", None)
        if gcs is None:
            return True  # local mode: nothing to wait for
        watcher: Optional[NodeEventWatcher] = None
        try:
            try:
                watcher = NodeEventWatcher(gcs)
            except Exception:
                watcher = None
            deadline = time.monotonic() + timeout_s
            while True:
                if self._feasible_workers() >= need:
                    return True
                remaining = deadline - time.monotonic()
                if remaining <= 0:
                    return False
                if watcher is not None:
                    watcher.wait_for_event(min(1.0, remaining))
                else:
                    time.sleep(min(0.5, remaining))
        finally:
            if watcher is not None:
                watcher.stop()

    def _renegotiate_capacity(self, timeout_s: float) -> bool:
        """After a preemption: wait for FULL target capacity; on timeout
        either enter the elastic downsize path (largest feasible world
        >= min_workers) or record a CapacityTimeoutError. Returns True
        when fit() should launch the next attempt (self._world_size is
        set), False to fail fast (self._capacity_error is set)."""
        sc = self.scaling_config
        target = sc.num_workers
        self._capacity_error: Optional[exc.CapacityTimeoutError] = None
        if self._wait_for_capacity(target, timeout_s=timeout_s):
            self._world_size = target
            return True
        feasible = self._feasible_workers()
        if sc.elastic and feasible >= sc.elastic_floor:
            new_world = min(feasible, target)
            _flight_record(
                "train.elastic_downsize", (self._world_size, new_world, target)
            )
            imet.TRAIN_ELASTIC_RESIZES.inc(direction="downsize")
            self._world_size = new_world
            return True
        self._capacity_error = exc.CapacityTimeoutError(
            target, feasible, timeout_s, sc.elastic_floor if sc.elastic else 0
        )
        _flight_record("train.capacity_timeout", (target, feasible, timeout_s))
        return False

    @staticmethod
    def _gang_nodes(gcs, group: WorkerGroup) -> Set[str]:
        """The node ids currently hosting the gang's worker actors."""
        ids = {w._actor_id.hex() for w in group.workers}
        locations = node_events.actor_locations(gcs)
        return {
            nid
            for aid, nid in locations.items()
            if aid in ids and nid
        }

    def _split_shards(self, ds: Any, ws: int) -> List[Any]:
        """One coordinated equal split of `ds` into ws per-rank handles:
        ChannelFeed handles (dataset_config="channel") or plain shard
        iterators (pre-shipped coordinator, so every rank shares ONE
        SplitCoordinator actor)."""
        split = ds.streaming_split(ws)
        if self._dataset_config == "channel":
            return split.to_channel()
        split.prepare_shipping()
        return list(split)

    def _use_distributed(self, world_size: Optional[int] = None) -> bool:
        """Multi-host rendezvous requires process-isolated workers (one jax
        runtime per worker); the thread-based local runtime shares one
        process, so it keeps the local-mesh path."""
        sc = self.scaling_config
        n = world_size if world_size is not None else sc.num_workers
        if sc.backend is None and n <= 1:
            return False
        from ..core import runtime_base
        from ..core.local_runtime import LocalRuntime

        return not isinstance(runtime_base.current_runtime(), LocalRuntime)

    # ---------------------------------------------------------------- inner
    def _run_attempt(
        self,
        storage: StorageContext,
        manager: CheckpointManager,
        resume_ckpt: Optional[Checkpoint],
        rework: bool = False,
        world_size: Optional[int] = None,
    ) -> Dict[str, Any]:
        import cloudpickle

        # Until the first fresh result lands, this attempt's wall time is
        # either setup (first attempt) or restart-rework (re-reaching the
        # restored step after a failure/preemption — work the cluster
        # already did once).
        acct = getattr(self, "goodput", None)
        if acct is None:  # direct _run_attempt callers (tests)
            acct = self.goodput = _goodput.GoodputAccountant()
        acct.begin(_goodput.RESTART_REWORK if rework else _goodput.SETUP)

        sc = self.scaling_config
        ws = world_size if world_size is not None else sc.num_workers
        trial = storage.trial_name or storage.experiment_name
        # Elastic visibility: the live world-size gauge plus degraded-mode
        # accounting — an attempt below target runs in the DEGRADED
        # goodput category, credited at world/target (half the chips
        # productive is half the goodput).
        imet.TRAIN_WORLD_SIZE.set(float(ws), trial=trial)
        productive_cat = _goodput.PRODUCTIVE
        if ws < sc.num_workers:
            productive_cat = _goodput.DEGRADED
            acct.set_weight(_goodput.DEGRADED, ws / max(1, sc.num_workers))
        pg = None
        if ws > 1:
            bundles = [dict(sc.resources_per_worker or {"CPU": 1}) for _ in range(ws)]
            pg = create_pg(bundles, strategy=sc.placement_strategy)
            # Gang re-forms (restore, grow-back) race the PREVIOUS gang's
            # async teardown: the old workers' resources free a beat after
            # kill(). Wait for the bundles instead of scheduling workers
            # against a pending group ("bundle not available").
            if not pg.ready(timeout=60.0):
                raise RuntimeError(
                    f"placement group for {ws}-worker gang not ready in 60s"
                )

        # One-off set-up spans (the workers' actor_launch.* spans nest in
        # the first; the second is each worker's first touch of its devices).
        with _tracing.span("train.worker_group.start", {"world_size": ws}):
            group = WorkerGroup(
                ws,
                resources_per_worker=sc.resources_per_worker,
                placement_group=pg,
                target_world_size=sc.num_workers,
            )
        self._last_metrics: Dict[str, Any] = {}
        # Preemption awareness: subscribe to node_draining notices and
        # resolve which nodes host this gang — the supervisor half of
        # drain -> checkpoint -> restore (cluster mode only; the local
        # runtime has no nodes to lose).
        watcher: Optional[NodeEventWatcher] = None
        gang_nodes: Set[str] = set()
        gcs = getattr(runtime_base.current_runtime(), "_gcs", None)
        if gcs is not None and ws >= 1:
            try:
                watcher = NodeEventWatcher(gcs)
                gang_nodes = self._gang_nodes(gcs, group)
            except Exception:
                watcher = None
        try:
            # Backend setup (the analogue of _setup_torch_process_group,
            # reference: train/_internal/backend_executor.py:135 start ->
            # Backend.on_start, torch/config.py:66). Two paths:
            #  - multi-host (cluster runtime, num_workers>1 or an explicit
            #    backend config): every worker-process rendezvouses via
            #    jax.distributed.initialize and builds the GLOBAL mesh;
            #  - single host: each worker builds the local-device mesh.
            # Either way the MeshSpec resolves IN the worker: an
            # accelerator belongs to one process, so the driver must never
            # initialize a jax backend to count devices.
            with _tracing.span("train.setup_mesh", {"world_size": ws}):
                if self._use_distributed(ws):
                    from .backend import JaxBackendConfig, coordinator_address

                    cfg = sc.backend or JaxBackendConfig()
                    coord = coordinator_address(cfg)
                    api.get(
                        [
                            w.setup_distributed.remote(
                                coord,
                                sc.mesh,
                                cfg.platform,
                                cfg.devices_per_worker,
                                cfg.init_timeout_s,
                            )
                            for w in group.workers
                        ]
                    )
                else:
                    api.get([w.setup_mesh.remote(sc.mesh) for w in group.workers])

            blob = cloudpickle.dumps(self._train_loop)
            config = dict(self._config)
            if self._datasets:
                config["__datasets__"] = self._datasets
                # Per-rank shards (train.get_dataset_shard resolves them
                # worker-side): one coordinated streaming_split per
                # dataset per attempt, so an elastic restart re-splits at
                # the new world size.
                config["__dataset_shards__"] = {
                    ds_name: self._split_shards(ds, ws)
                    for ds_name, ds in self._datasets.items()
                }
            api.get(
                [
                    w.start_training.remote(
                        blob,
                        config,
                        storage.trial_name or storage.experiment_name,
                        resume_ckpt.path if resume_ckpt else None,
                    )
                    for w in group.workers
                ]
            )

            ckpt_index = 0
            drained: Set[str] = set()
            while True:
                if watcher is not None and not drained:
                    # drain_noticed, NOT affected: only a real preemption
                    # notice earns the preemption retry budget — an
                    # un-noticed node death must keep taking the blunt
                    # max_failures path.
                    drained = watcher.drain_noticed(gang_nodes)
                    if drained:
                        # Preemption notice for a gang host: ask every
                        # worker for a final checkpoint + clean return
                        # (cooperative loops see train.drain_requested();
                        # others fall back to their last periodic
                        # checkpoint). Results keep flowing below so the
                        # final checkpoint is captured before the raise.
                        _flight_record("train.drain", tuple(sorted(drained)))
                        # From the notice on, wall time serves the
                        # preemption (final checkpoint, teardown), not
                        # fresh steps.
                        acct.begin(_goodput.DRAIN_WAIT)
                        for w in group.workers:
                            try:
                                w.request_drain.remote()
                            except Exception:  # lint: swallow-ok(worker already dead; drain moot)
                                pass
                # Bounded rounds (in cluster mode): a worker mid-step in a
                # long compute answers with the __pending__ sentinel after
                # 2 s, so the drain check above re-runs even when nothing
                # is being reported — an unbounded wait here would let the
                # preemption grace expire before request_drain ever went
                # out. Local mode keeps the unbounded wait (no watcher, and
                # the shared-process runtime is latency-sensitive in tests).
                round_timeout = 2.0 if watcher is not None else None
                try:
                    results = api.get(
                        [w.next_result.remote(round_timeout) for w in group.workers]
                    )
                except Exception:
                    if drained:
                        # A gang worker died INSIDE the drain grace (the
                        # node's deadline beat its final checkpoint): this
                        # is still the preemption, not a training failure —
                        # surface it as such so fit() restores on the
                        # preemption retry budget instead of burning
                        # max_failures on a capacity event.
                        raise exc.PreemptionError(sorted(drained))
                    raise
                if all(r is None for r in results):
                    break
                live = [
                    r
                    for r in results
                    if r is not None and not r.get("__pending__")
                ]
                if not live:
                    continue  # every worker is mid-step; poll again
                if not drained and acct.category != productive_cat:
                    # First fresh result of this attempt: steps are
                    # advancing — setup/rework ends here (DEGRADED when
                    # the gang is below target).
                    acct.begin(productive_cat)
                rank0 = (
                    results[0]
                    if results[0] is not None and not results[0].get("__pending__")
                    else live[0]
                )
                self._last_metrics = dict(rank0["metrics"])
                ckpt_path = rank0.get("checkpoint")
                if ckpt_path:
                    if not drained:
                        acct.begin(_goodput.CHECKPOINT)
                    persisted = storage.persist_checkpoint(Checkpoint(ckpt_path), ckpt_index)
                    manager.register(persisted, self._last_metrics)
                    ckpt_index += 1
                    if not drained:
                        acct.begin(productive_cat)
                    # Live goodput gauge each checkpoint: the
                    # goodput_floor watchdog is about runs IN PROGRESS
                    # (fit()'s terminal set is one-shot).
                    imet.TRAIN_GOODPUT.set(acct.fraction(), trial=trial)
                    if (
                        ws < sc.num_workers
                        and not drained
                        and self._feasible_workers() >= sc.num_workers
                    ):
                        # Grow-back at the checkpoint boundary: the
                        # autoscaler delivered target capacity while this
                        # degraded gang was running, and the checkpoint
                        # that just persisted is the same-step resume
                        # point for the full-size gang.
                        raise _ElasticGrow()

            try:
                api.get([w.join.remote() for w in group.workers])
            except Exception:
                if drained:
                    raise exc.PreemptionError(sorted(drained))
                raise
            if drained:
                # The gang stopped because its node(s) are going away, not
                # because training finished: surface it as a preemption so
                # fit() restores from the final checkpoint on replacement
                # capacity.
                raise exc.PreemptionError(sorted(drained))
            return self._last_metrics
        finally:
            if watcher is not None:
                watcher.stop()
            group.shutdown()
            if pg is not None:
                from ..core.placement_group import remove_placement_group

                remove_placement_group(pg)
