"""In-program collectives over mesh axes (the NCCL replacement).

The reference's collectives are out-of-band process-group calls: NCCL
(reference: python/ray/util/collective/collective_group/nccl_collective_group.py:128)
or Gloo (gloo_collective_group.py:184), invoked eagerly between torch
tensors. On TPU the idiomatic form is an *in-program* collective: the op is
traced into the XLA computation, the SPMD partitioner schedules it on ICI,
and it overlaps with compute. These helpers are thin, typed wrappers meant
for use inside `jax.shard_map`-decorated functions; outside shard_map, use
sharding constraints and let XLA insert collectives (GSPMD).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable, Optional, Sequence, Union

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec

AxisName = Union[str, Sequence[str]]


def psum(x: Any, axis: AxisName):
    return lax.psum(x, axis)


def pmean(x: Any, axis: AxisName):
    return lax.pmean(x, axis)


def pmax(x: Any, axis: AxisName):
    return lax.pmax(x, axis)


def all_gather(x: Any, axis: AxisName, *, tiled: bool = True, gather_dim: int = 0):
    return lax.all_gather(x, axis, axis=gather_dim, tiled=tiled)


def reduce_scatter(x: Any, axis: AxisName, *, scatter_dim: int = 0):
    return lax.psum_scatter(x, axis, scatter_dimension=scatter_dim, tiled=True)


def all_to_all(x: Any, axis: AxisName, *, split_dim: int, concat_dim: int):
    return lax.all_to_all(x, axis, split_axis=split_dim, concat_axis=concat_dim, tiled=True)


def axis_index(axis: str):
    return lax.axis_index(axis)


def axis_size(axis: str):
    return lax.axis_size(axis)


def ring_permute(x: Any, axis: str, *, shift: int = 1):
    """Sends x to the neighbour `shift` steps around the ring of `axis`.

    On TPU a unit-shift ppermute is a single-hop ICI transfer — the building
    block of ring attention and pipeline microbatch rotation.
    """
    n = axis_size(axis)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis, perm)


def one_hot_rank(axis: str, n: Optional[int] = None, dtype=jnp.float32):
    n = n if n is not None else axis_size(axis)
    return jax.nn.one_hot(lax.axis_index(axis), n, dtype=dtype)


def pbroadcast(x: Any, axis: str, root: int = 0):
    """Broadcast from `root` along axis (select + psum formulation, which the
    partitioner pattern-matches to an ICI broadcast)."""
    idx = lax.axis_index(axis)
    masked = jax.tree_util.tree_map(lambda v: jnp.where(idx == root, v, jnp.zeros_like(v)), x)
    return jax.tree_util.tree_map(lambda v: lax.psum(v, axis), masked)


def shard_map(
    fn: Callable,
    mesh: Mesh,
    in_specs: Any,
    out_specs: Any,
    *,
    check_vma: bool = False,
):
    """`jax.shard_map` with the framework mesh (per-shard programming model
    for kernels that need explicit collectives — ring attention, Ulysses,
    expert dispatch)."""
    return jax.shard_map(
        fn, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check_vma
    )
