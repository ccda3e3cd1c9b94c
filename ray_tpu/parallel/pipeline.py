"""Pipeline parallelism: GPipe microbatch scheduling as an SPMD program.

The reference builds pipeline parallelism on compiled-graph channels —
actor DAGs pushing activations through shared-memory/NCCL channels
(reference: python/ray/dag/compiled_dag_node.py:664,
experimental/channel/shared_memory_channel.py:159, gpu_communicator.py:19).
The TPU-native inversion: the pipeline IS the compiled program. Stages are
a mesh axis ("stage"); activation hand-off is `lax.ppermute` on ICI/DCN
inside `shard_map`; the schedule is a `lax.scan` over pipeline steps, so
XLA sees one fused step graph (transfer overlapped with compute) and
autodiff derives the backward pipeline for free — no channel runtime, no
inter-actor serialization on the critical path.

Schedule: plain GPipe. M microbatches flow through S stages in M + S - 1
steps; bubbles compute on zero inputs and are masked at collection (the
standard simple-schedule FLOP overhead of S-1 wasted stage-steps).
`jax.checkpoint` the stage function to keep the scan's saved activations
to one per (stage, step).
"""

from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .collectives import shard_map

PyTree = Any


def stack_stage_params(stage_trees: List[PyTree]) -> PyTree:
    """Stacks per-stage param pytrees into one tree with a leading [S, ...]
    stage dim (shard it over the "stage" axis with stage_param_sharding)."""
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *stage_trees)


def stage_param_sharding(mesh: Mesh, tree: PyTree, axis: str = "stage") -> PyTree:
    """NamedShardings placing each leaf's leading stage dim on `axis`."""
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axis)), tree
    )


def shard_stage_params(params: PyTree, mesh: Mesh, axis: str = "stage") -> PyTree:
    return jax.tree_util.tree_map(
        jax.device_put, params, stage_param_sharding(mesh, params, axis)
    )


def pipeline_apply(
    stage_fn: Callable[[PyTree, jax.Array], jax.Array],
    stage_params: PyTree,
    microbatches: jax.Array,
    mesh: Mesh,
    *,
    axis: str = "stage",
    remat: bool = True,
) -> jax.Array:
    """Runs `microbatches` [M, mb, ...] through S pipeline stages.

    `stage_params` leaves carry a leading [S, ...] stage dim (sharded over
    `axis`); `stage_fn(params_s, x) -> y` must be shape-preserving (the
    activation layout is identical between stages, as with stacked
    transformer blocks). Returns [M, mb, ...] outputs, replicated over the
    stage axis. Differentiable end-to-end: grad through this function IS
    the backward pipeline.
    """
    S = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    M = microbatches.shape[0]
    fn = jax.checkpoint(stage_fn) if remat else stage_fn

    def per_stage(params_block, x):
        # shard_map hands each stage its [1, ...] param slice; drop the dim.
        params_local = jax.tree_util.tree_map(lambda p: p[0], params_block)
        sid = lax.axis_index(axis)
        perm = [(i, (i + 1) % S) for i in range(S)]

        def step(carry, t):
            act = carry  # this stage's previous output [mb, ...]
            recv = lax.ppermute(act, axis, perm) if S > 1 else act
            micro_t = lax.dynamic_index_in_dim(
                x, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            x_in = jnp.where(sid == 0, micro_t, recv)
            y = fn(params_local, x_in)
            # Emit row t-(S-1) when this is the last stage and it's valid;
            # invalid (bubble) steps emit zeros that the caller's psum mask
            # already excludes via the where() below.
            emit_idx = t - (S - 1)
            valid = (sid == S - 1) & (emit_idx >= 0)
            out_row = jnp.where(valid, y, jnp.zeros_like(y))
            return y, (out_row, emit_idx)

        _, (rows, idxs) = lax.scan(
            step, jnp.zeros(x.shape[1:], x.dtype), jnp.arange(M + S - 1)
        )
        # Scatter emitted rows into [M, ...]: bubble rows are already zero
        # (out_row masking), so their clipped-to-0 adds are no-ops.
        outputs = jnp.zeros_like(x).at[jnp.clip(idxs, 0, M - 1)].add(rows)
        # Only the last stage holds real outputs; psum replicates them.
        outputs = jnp.where(sid == S - 1, outputs, jnp.zeros_like(outputs))
        return lax.psum(outputs, axis)

    spec_params = jax.tree_util.tree_map(lambda _: P(axis), stage_params)
    return shard_map(
        per_stage, mesh, in_specs=(spec_params, P()), out_specs=P()
    )(stage_params, microbatches)


def split_stacked_layers(stacked: PyTree, num_stages: int) -> PyTree:
    """Reshapes scan-stacked layer params [L, ...] into [S, L/S, ...] so a
    stage_fn can scan its local layers (the transformer integration)."""

    def one(p):
        L = p.shape[0]
        if L % num_stages:
            raise ValueError(f"{L} layers not divisible into {num_stages} stages")
        return p.reshape((num_stages, L // num_stages) + p.shape[1:])

    return jax.tree_util.tree_map(one, stacked)
