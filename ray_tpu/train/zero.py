"""ZeRO-style cross-replica sharded optimizer update (arXiv:2004.13336).

Plain data parallelism keeps a FULL copy of the optimizer state on every
chip — for adamw that is 2x the params in fp32-equivalent bytes, the
single biggest slab of HBM after the params themselves. The
ZeRO-1 fix: shard the optimizer state over the data axis, so each chip
updates only its 1/N slice of the flattened parameter vector:

    local grads --reduce_scatter--> grad shard
    grad shard + opt-state shard --tx.update--> param-delta shard
    updated param shard --all_gather--> full params

One reduce_scatter + one all_gather move exactly the same bytes as the
allreduce they replace (an allreduce IS reduce_scatter + all_gather),
so the collective cost is unchanged while per-chip optimizer state
drops to ~1/N. The update itself is elementwise for the adam family,
so shard-local tx.update is numerically identical to the unsharded
update (tests/test_elastic.py pins this step-for-step).

Representation: every param leaf is flattened and zero-padded to a
multiple of the axis size so shards are SPMD-uniform. The pad region
provably stays zero through adam-family updates (zero grad, zero m/v,
zero weight-decay on a zero param), which is what makes `to_logical` /
`from_logical` — the unpadded, param-shaped view used by the elastic
checkpoint format — exact at ANY world size: save the logical tree via
`elastic_checkpoint.save_state`, restore and `from_logical` onto a mesh
of a different size, and the trajectory continues bit-for-bit.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..parallel.collectives import shard_map

PyTree = Any


def _axis_size(mesh: Mesh, axis: str) -> int:
    return dict(zip(mesh.axis_names, mesh.devices.shape))[axis]


class ZeroSharder:
    """The flatten/pad/shard mapping between a logical param tree and the
    dict-of-flat-vectors representation the sharded update runs on.

    The sharded tree is `{str(i): padded_flat_vector}` keyed by leaf
    index — a dict so optimizer states built over it carry the leaf index
    in their tree paths, which is what lets `to_logical`/`from_logical`
    map optimizer moments back to param shapes without knowing the
    optimizer's structure.
    """

    def __init__(self, params_like: PyTree, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.n = _axis_size(mesh, axis)
        leaves, self.treedef = jax.tree_util.tree_flatten(
            jax.eval_shape(lambda: params_like)
        )
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(math.prod(s)) if s else 1 for s in self.shapes]
        self.padded = [-(-s // self.n) * self.n for s in self.sizes]

    # ------------------------------------------------------------ params
    def flatten(self, tree: PyTree) -> Dict[str, jax.Array]:
        """Logical tree -> padded flat dict (global arrays)."""
        leaves = jax.tree_util.tree_leaves(tree)
        out = {}
        for i, leaf in enumerate(leaves):
            flat = jnp.reshape(leaf, (-1,))
            pad = self.padded[i] - self.sizes[i]
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
            out[str(i)] = flat
        return out

    def unflatten(self, flats: Dict[str, jax.Array]) -> PyTree:
        leaves = [
            jnp.reshape(flats[str(i)][: self.sizes[i]], self.shapes[i])
            for i in range(len(self.shapes))
        ]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    def shard_struct(self) -> Dict[str, jax.ShapeDtypeStruct]:
        """Per-device shard shapes (what tx.init sees inside shard_map)."""
        return {
            str(i): jax.ShapeDtypeStruct((self.padded[i] // self.n,), self.dtypes[i])
            for i in range(len(self.shapes))
        }

    def _leaf_index(self, path) -> Optional[int]:
        for part in reversed(path):
            key = getattr(part, "key", None)
            if isinstance(key, str) and key.isdigit():
                return int(key)
        return None

    # --------------------------------------------------------- opt state
    def opt_specs(self, opt_state: PyTree) -> PyTree:
        """PartitionSpec tree for an optimizer state built over the shard
        dict: vector leaves that mirror a param shard are sharded over the
        axis, scalars (adam count etc.) stay replicated."""

        def one(path, leaf):
            i = self._leaf_index(path)
            if i is not None and getattr(leaf, "ndim", 0) == 1:
                return P(self.axis)
            return P()

        return jax.tree_util.tree_map_with_path(one, opt_state)

    def to_logical(self, opt_state: PyTree) -> PyTree:
        """Sharded (padded flat) optimizer state -> world-size-independent
        logical tree: moment leaves reshaped to their param's shape, pad
        dropped. This is the form `elastic_checkpoint` stores."""

        def one(path, leaf):
            i = self._leaf_index(path)
            if (
                i is not None
                and getattr(leaf, "ndim", 0) == 1
                and leaf.shape[0] == self.padded[i]
            ):
                arr = jax.device_get(leaf)
                return arr[: self.sizes[i]].reshape(self.shapes[i])
            return jax.device_get(leaf)

        return jax.tree_util.tree_map_with_path(one, opt_state)

    def from_logical(self, logical: PyTree) -> PyTree:
        """Inverse of to_logical at THIS sharder's world size: re-pad with
        zeros (exact — the pad region of a fresh or restored run is zero by
        construction) and place each moment sharded over the axis."""

        def one(path, leaf):
            i = self._leaf_index(path)
            arr = jnp.asarray(leaf)
            if (
                i is not None
                and tuple(arr.shape) == self.shapes[i]
                and self.padded[i] // self.n >= 1
            ):
                flat = jnp.reshape(arr, (-1,))
                pad = self.padded[i] - self.sizes[i]
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                return jax.device_put(
                    flat, NamedSharding(self.mesh, P(self.axis))
                )
            return jax.device_put(arr, NamedSharding(self.mesh, P()))

        return jax.tree_util.tree_map_with_path(one, logical)

    def place_opt(self, opt_state: PyTree) -> PyTree:
        """Device-places a (host) padded-flat optimizer state under its
        sharding specs (restore path at the SAME representation)."""
        specs = self.opt_specs(opt_state)
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(self.mesh, spec)),
            opt_state,
            specs,
        )


def init_opt_state(tx, params: PyTree, mesh: Mesh, axis: str = "data") -> PyTree:
    """Optimizer state sharded over `axis`: each device initializes state
    for only ITS slice of the flattened params (~1/N bytes per chip)."""
    sharder = ZeroSharder(params, mesh, axis)
    struct = jax.eval_shape(tx.init, sharder.shard_struct())
    specs = sharder.opt_specs(struct)

    def inner(flats):
        local = {k: v for k, v in flats.items()}
        return tx.init(local)

    fn = shard_map(
        inner,
        mesh,
        in_specs=({str(i): P(axis) for i in range(len(sharder.shapes))},),
        out_specs=specs,
    )
    return jax.jit(fn)(sharder.flatten(params))


def build_zero_step(
    loss_fn: Callable[[PyTree, Any], jax.Array],
    tx,
    params_like: PyTree,
    mesh: Mesh,
    *,
    axis: str = "data",
    donate: bool = True,
) -> Tuple[Callable, ZeroSharder]:
    """The fused ZeRO-1 train step: returns (step, sharder) where
    `step(params, opt_state, batch) -> (params, opt_state, loss)`.

    `loss_fn(params, local_batch)` computes the MEAN loss of its local
    batch shard; `batch` is sharded over `axis` on dim 0. Per-device
    grads go through ONE reduce_scatter (grad shard), the shard-local
    tx.update, and ONE all_gather (updated params) — allreduce-equivalent
    bytes, 1/N optimizer state.
    """
    sharder = ZeroSharder(params_like, mesh, axis)
    n = sharder.n
    idx_keys = [str(i) for i in range(len(sharder.shapes))]
    opt_struct = jax.eval_shape(tx.init, sharder.shard_struct())
    opt_specs = sharder.opt_specs(opt_struct)

    def inner(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        import optax

        # The three scopes are rows of models/transformer.SCOPES: a device
        # trace splits the step's time by them.
        g_leaves = jax.tree_util.tree_leaves(grads)
        g_shards = {}
        with jax.named_scope("zero.grad_scatter"):
            for i, g in enumerate(g_leaves):
                flat = jnp.reshape(g, (-1,))
                pad = sharder.padded[i] - sharder.sizes[i]
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                # reduce_scatter: sum of per-device grads, sliced to this
                # device's shard; /n turns sum-of-local-means into the global
                # mean (equal local batch sizes by construction of the spec).
                g_shards[str(i)] = (
                    lax.psum_scatter(flat, axis, scatter_dimension=0, tiled=True) / n
                )
        with jax.named_scope("zero.update"):
            p_leaves = jax.tree_util.tree_leaves(params)
            r = lax.axis_index(axis)
            p_shards = {}
            for i, p in enumerate(p_leaves):
                flat = jnp.reshape(p, (-1,))
                pad = sharder.padded[i] - sharder.sizes[i]
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                p_shards[str(i)] = lax.dynamic_slice(
                    flat, (r * (sharder.padded[i] // n),), (sharder.padded[i] // n,)
                )
            updates, new_opt = tx.update(g_shards, opt_state, p_shards)
            new_p_shards = optax.apply_updates(p_shards, updates)
        with jax.named_scope("zero.param_gather"):
            new_flats = {
                k: lax.all_gather(new_p_shards[k], axis, axis=0, tiled=True)
                for k in idx_keys
            }
            new_params = sharder.unflatten(new_flats)
        with jax.named_scope("loss"):
            return new_params, new_opt, lax.pmean(loss, axis)

    batch_spec = P(axis)
    stepped = shard_map(
        inner,
        mesh,
        in_specs=(P(), opt_specs, batch_spec),
        out_specs=(P(), opt_specs, P()),
    )
    step = jax.jit(stepped, donate_argnums=(0, 1) if donate else ())
    return step, sharder


def build_zero_update(
    tx,
    params_like: PyTree,
    mesh: Mesh,
    *,
    axis: str = "data",
) -> Tuple[Callable, ZeroSharder]:
    """Update-only variant: `(params, opt_state, grads) -> (params, opt)`
    for callers that already hold globally-reduced grads (the numerics
    test pins THIS against a plain tx.update — identical elementwise
    math, just sliced)."""
    sharder = ZeroSharder(params_like, mesh, axis)
    n = sharder.n
    opt_struct = jax.eval_shape(tx.init, sharder.shard_struct())
    opt_specs = sharder.opt_specs(opt_struct)

    def inner(params, opt_state, grads):
        r = lax.axis_index(axis)

        def shard_of(tree):
            out = {}
            for i, leaf in enumerate(jax.tree_util.tree_leaves(tree)):
                flat = jnp.reshape(leaf, (-1,))
                pad = sharder.padded[i] - sharder.sizes[i]
                if pad:
                    flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
                out[str(i)] = lax.dynamic_slice(
                    flat, (r * (sharder.padded[i] // n),), (sharder.padded[i] // n,)
                )
            return out

        import optax

        with jax.named_scope("zero.grad_scatter"):  # a slice here: the gradients come reduced
            g_shards = shard_of(grads)
        with jax.named_scope("zero.update"):
            p_shards = shard_of(params)
            updates, new_opt = tx.update(g_shards, opt_state, p_shards)
            new_p = optax.apply_updates(p_shards, updates)
        with jax.named_scope("zero.param_gather"):
            flats = {
                k: lax.all_gather(v, axis, axis=0, tiled=True) for k, v in new_p.items()
            }
            return sharder.unflatten(flats), new_opt

    fn = shard_map(
        inner, mesh, in_specs=(P(), opt_specs, P()), out_specs=(P(), opt_specs)
    )
    return jax.jit(fn), sharder


def per_device_bytes(tree: PyTree, device=None) -> int:
    """Bytes of `tree` resident on ONE device (first addressable device by
    default) — the number the ZeRO sharding shrinks ~1/N; bench_elastic
    records it at N in {1, 4}."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        shards = getattr(leaf, "addressable_shards", None)
        if shards is None:
            total += getattr(leaf, "nbytes", 0)
            continue
        if device is None:
            device = shards[0].device
        for s in shards:
            if s.device == device:
                total += s.data.nbytes
    return total
