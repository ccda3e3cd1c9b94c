"""JaxLearner + LearnerGroup: the gradient-update side of the RL stack.

Re-design of the reference's Learner/LearnerGroup (reference:
rllib/core/learner/learner.py:109, update_from_batch :948, _update :1170;
learner_group.py:81, which bootstraps a NCCL process group by reusing
ray.train's BackendExecutor, learner_group.py:55-68; TorchLearner
torch_learner.py:67 with the DDP wrap at :576). This is exactly the spot
SURVEY.md §1 marks for the TPU swap: the jitted update shards the batch
over the mesh's data axes and XLA inserts the gradient psum — no process
group, no DDP wrapper.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import numpy as np
import optax

from .module import RLModule

PyTree = Any


class JaxLearner:
    """One learner: owns params + optimizer state and a jitted update.

    `loss_fn(module, params, batch) -> (loss, metrics)` is supplied by the
    algorithm (PPO/IMPALA); the learner is algorithm-agnostic
    (reference: Learner.compute_loss_for_module)."""

    def __init__(
        self,
        module: RLModule,
        loss_fn: Callable,
        *,
        lr: float = 3e-4,
        optimizer: Optional[optax.GradientTransformation] = None,
        grad_clip: Optional[float] = 0.5,
        seed: int = 0,
        mesh=None,
    ):
        self.module = module
        self.loss_fn = loss_fn
        self.mesh = mesh
        tx = optimizer or optax.adam(lr)
        if grad_clip is not None:
            tx = optax.chain(optax.clip_by_global_norm(grad_clip), tx)
        self.tx = tx
        self.params = module.init_params(jax.random.PRNGKey(seed))
        self.opt_state = tx.init(self.params)
        if mesh is not None:
            # Commit params/opt-state as (replicated) global arrays on the
            # mesh — required for multi-process SPMD, harmless single-host
            # (init is seed-deterministic, so every process places the same
            # values).
            from ..parallel.sharding import replicate_tree

            self.params = replicate_tree(self.params, mesh)
            self.opt_state = replicate_tree(self.opt_state, mesh)

        def _update(params, opt_state, batch):
            (loss, metrics), grads = jax.value_and_grad(
                lambda p: self.loss_fn(self.module, p, batch), has_aux=True
            )(params)
            updates, opt_state = self.tx.update(grads, opt_state, params)
            params = optax.apply_updates(params, updates)
            metrics = dict(metrics)
            metrics["total_loss"] = loss
            metrics["grad_norm"] = optax.global_norm(grads)
            return params, opt_state, metrics

        self._update = jax.jit(_update)

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        """One gradient step on a [B, ...] batch. If a mesh is set, the
        batch is sharded over its data axes so the grads psum over ICI."""
        if self.mesh is not None:
            from ..parallel.sharding import shard_batch

            batch = shard_batch(batch, self.mesh)
        self.params, self.opt_state, metrics = self._update(self.params, self.opt_state, batch)
        if self.mesh is not None and jax.process_count() > 1:
            # Gloo flake root cause (tier-1 "gloo reset"): float(metrics)
            # below syncs only the LOSS value; the param/opt-state update's
            # grad all-reduce may still be in flight when this rank starts
            # the next step. Gloo pair slots are reused across executions,
            # so rank A's step-N+1 scalar loss psum (4 bytes) can meet rank
            # B's step-N grad all-reduce (16+ bytes) on one slot:
            # `gloo::EnforceNotMet pair.cc:446 op.preamble.length <=
            # op.nbytes. 16 vs 4`, killing the process. Serialize steps on
            # the multi-process mesh before returning.
            jax.block_until_ready((self.params, self.opt_state))
        return {k: float(v) for k, v in metrics.items()}

    def get_weights(self) -> PyTree:
        return jax.device_get(self.params)

    def set_weights(self, params: PyTree) -> bool:
        if self.mesh is not None:
            from ..parallel.sharding import replicate_tree

            params = replicate_tree(params, self.mesh)
        self.params = params
        return True

    # Checkpointable (reference: rllib/utils/checkpoints.py Checkpointable)
    def save_state(self, directory: str) -> None:
        from ..train.checkpoint import save_aux_state, save_pytree

        save_pytree({"params": jax.device_get(self.params)}, directory)
        save_aux_state(directory, jax.device_get(self.opt_state))

    def load_state(self, directory: str) -> None:
        from ..train.checkpoint import load_aux_state, load_pytree

        params = load_pytree(directory)["params"]
        if self.mesh is not None:
            # Re-place on the mesh like set_weights: host-local numpy params
            # would hand the jitted update inputs committed to no mesh.
            from ..parallel.sharding import replicate_tree

            params = replicate_tree(params, self.mesh)
        self.params = params
        opt_state = load_aux_state(directory)
        if opt_state is not None:
            if self.mesh is not None:
                from ..parallel.sharding import replicate_tree

                opt_state = replicate_tree(opt_state, self.mesh)
            self.opt_state = opt_state
        else:  # old checkpoint: fresh moments
            self.opt_state = self.tx.init(self.params)


class _DistributedLearner:
    """Actor body: one process of a multi-host learner gang. Each actor
    rendezvouses via jax.distributed and runs the SAME jitted update over
    the shared global mesh — the gradient psum rides the mesh's data axis
    (the TPU inversion of the reference's BackendExecutor-bootstrapped
    NCCL DDP, learner_group.py:55-68)."""

    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self._learner: Optional[JaxLearner] = None

    def setup(
        self,
        coordinator: str,
        platform: Optional[str],
        devices_per_learner: Optional[int],
        module_blob: bytes,
        loss_blob: bytes,
        lr: float,
        grad_clip: Optional[float],
        seed: int,
        init_timeout_s: float = 60.0,
    ):
        import cloudpickle

        from ..train.backend import setup_jax_distributed

        info = setup_jax_distributed(
            self.rank,
            self.world_size,
            coordinator,
            platform=platform,
            devices_per_worker=devices_per_learner,
            init_timeout_s=init_timeout_s,
        )
        from ..parallel.mesh import MeshSpec, build_mesh

        mesh = build_mesh(MeshSpec(data=-1))
        self._learner = JaxLearner(
            cloudpickle.loads(module_blob),
            cloudpickle.loads(loss_blob),
            lr=lr,
            grad_clip=grad_clip,
            seed=seed,
            mesh=mesh,
        )
        return info

    def update(self, shard: Dict[str, np.ndarray]) -> Dict[str, float]:
        return self._learner.update(shard)

    def get_weights(self) -> PyTree:
        return self._learner.get_weights()

    def set_weights(self, params: PyTree) -> bool:
        return self._learner.set_weights(params)

    def save_state(self, directory: str) -> bool:
        self._learner.save_state(directory)
        return True

    def load_state(self, directory: str) -> bool:
        self._learner.load_state(directory)
        return True


class LearnerGroup:
    """Learner actors behind one update() call (reference:
    learner_group.py:81). With num_learners=1 the learner runs in-process
    and still spans all local devices through its mesh (DP/FSDP inside the
    program). num_learners>1 spawns one actor PROCESS per learner; the gang
    rendezvouses into one jax.distributed world and every update is one
    SPMD program over the global mesh."""

    def __init__(
        self,
        module: RLModule,
        loss_fn: Callable,
        *,
        num_learners: int = 1,
        lr: float = 3e-4,
        grad_clip: Optional[float] = 0.5,
        seed: int = 0,
        use_mesh: bool = False,
        devices_per_learner: Optional[int] = None,
        platform: Optional[str] = None,
        coordinator_host: Optional[str] = None,
    ):
        self.num_learners = num_learners
        self._actors = None
        self._learner = None
        if num_learners <= 1:
            mesh = None
            if use_mesh:
                from ..parallel.mesh import MeshSpec, build_mesh

                mesh = build_mesh(MeshSpec(data=-1))
            self._learner = JaxLearner(
                module, loss_fn, lr=lr, grad_clip=grad_clip, seed=seed, mesh=mesh
            )
            return

        import cloudpickle

        from .. import api
        from ..core import runtime_base
        from ..core.local_runtime import LocalRuntime
        from ..train.backend import free_port

        if isinstance(runtime_base.current_runtime(), LocalRuntime):
            raise RuntimeError(
                "num_learners>1 needs process-isolated learner actors; "
                "initialize the cluster runtime (ray_tpu.init()) instead of "
                "local_mode=True"
            )
        host = coordinator_host or "127.0.0.1"
        coord = f"{host}:{free_port()}"
        actor_cls = api.remote(num_cpus=1)(_DistributedLearner)
        self._actors = [actor_cls.remote(i, num_learners) for i in range(num_learners)]
        infos = api.get(
            [
                a.setup.remote(
                    coord,
                    platform,
                    devices_per_learner,
                    cloudpickle.dumps(module),
                    cloudpickle.dumps(loss_fn),
                    lr,
                    grad_clip,
                    seed,
                )
                for a in self._actors
            ]
        )
        self._global_devices = int(infos[0]["global_devices"])

    def update(self, batch: Dict[str, np.ndarray]) -> Dict[str, float]:
        if self._actors is None:
            return self._learner.update(batch)
        from .. import api

        n = self.num_learners
        B = len(next(iter(batch.values())))
        # Every process must contribute an equal, device-divisible shard
        # (gloo/ICI collectives are gang-wide); trim the ragged tail.
        usable = B - (B % self._global_devices)
        if usable == 0:
            raise ValueError(
                f"batch of {B} rows is smaller than the {self._global_devices}"
                "-device gang; enlarge the batch or reduce learners"
            )
        per = usable // n
        refs = [
            a.update.remote({k: v[i * per : (i + 1) * per] for k, v in batch.items()})
            for i, a in enumerate(self._actors)
        ]
        out = api.get(refs)
        return out[0]

    def get_weights(self) -> PyTree:
        if self._actors is None:
            return self._learner.get_weights()
        from .. import api

        return api.get(self._actors[0].get_weights.remote())

    def set_weights(self, params: PyTree) -> None:
        if self._actors is None:
            self._learner.set_weights(params)
            return
        from .. import api

        api.get([a.set_weights.remote(params) for a in self._actors])

    def save_state(self, directory: str) -> None:
        if self._actors is None:
            self._learner.save_state(directory)
        else:
            from .. import api

            api.get(self._actors[0].save_state.remote(directory))

    def load_state(self, directory: str) -> None:
        if self._actors is None:
            self._learner.load_state(directory)
        else:
            from .. import api

            api.get([a.load_state.remote(directory) for a in self._actors])

    def shutdown(self) -> None:
        if self._actors:
            from .. import api

            for a in self._actors:
                try:
                    api.kill(a)
                except Exception:  # lint: swallow-ok(learner actor may already be dead)
                    pass
            self._actors = None
