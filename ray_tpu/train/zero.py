"""ZeRO-style cross-replica sharded optimizer update (arXiv:2004.13336).

Plain data parallelism keeps a FULL copy of the optimizer state on every
chip — for adamw that is 2x the params in fp32-equivalent bytes, the
single biggest slab of HBM after the params themselves. The
ZeRO-1 fix: shard the optimizer state over the data axis, so each chip
updates only its 1/N of every parameter leaf:

    local grads --reduce_scatter--> grad shard
    grad shard + opt-state shard --tx.update--> param-delta shard
    param-delta shard --all_gather--> full delta, added to the full params

One reduce_scatter + one all_gather move the bytes of the allreduce they
replace (an allreduce IS reduce_scatter + all_gather), and per-chip
optimizer state drops to ~1/N. The update itself is elementwise for the
adam family, so shard-local tx.update is numerically identical to the
unsharded update (tests/test_elastic.py pins this step-for-step).

What a chip's shard is, and why. A leaf with an inner dimension (any but
the first) divisible by N is cut along the last such dimension: chip r owns
`leaf[..., r*k:(r+1)*k, ...]`, its gradient goes to `psum_scatter` in the
shape the backward pass left it in, and the updates' slices come back from
`all_gather` in the leaf's own shape (`ZeroSharder.all_gather_tree` says why
the updates and not the updated slices); only the SHARD is flattened, for
the optimizer. The form is the TPU compiler's: it keeps a reduce-scatter whose
scattered dimension is an inner one of the array as it lies (v5e 2x2,
`bf16[4, 4096, 14336]`: 1 reduce-scatter, 470 MB of temporaries) and
rewrites one of a flattened vector, or along the major-most dimension, to an
all-reduce of the WHOLE gradient plus a slice (0 reduce-scatters, 940 MB).
Flattened, the four-chip Mistral step paid 8 all-reduces (40 ms), 14 ms of
copies of their results and an all-gather on top, a tenth of the step
(PERF.md §6, PR 56 and PR 57): the collective cost was not unchanged. A leaf with
no such dimension (a `[d]` scale, a scalar, an odd size: kilobytes) is
flattened, zero-padded to a multiple of N and cut into N runs.

Representation: the optimizer state is built over `{str(i): vector}`, leaf
i's shard flattened, `padded[i] // N` elements a chip; the global vector is
the chips' shards one after another (for a cut leaf a permutation of the
flattened leaf, `ZeroSharder._to_flat`). A flat-cut leaf's pad region
provably stays zero through adam-family updates (zero grad, zero m/v,
zero weight-decay on a zero param), and a permutation loses nothing, which
is what makes `to_logical` / `from_logical` — the unpadded, param-shaped
view used by the elastic checkpoint format — exact at ANY world size: save
the logical tree via `elastic_checkpoint.save_state`, restore and
`from_logical` onto a mesh of a different size, and the trajectory
continues bit-for-bit.
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
    """Which elements of each param leaf a chip owns, and the mapping between
    a logical param tree and the dict-of-flat-vectors representation the
    sharded update runs on.

    The partition is decided here, once, from a leaf's SHAPE and the axis
    size alone (`dims`): a leaf with an inner dimension (any but the first)
    divisible by n is cut along the last such dimension, chip r owning
    `leaf[..., r*k:(r+1)*k, ...]`; a leaf with none (a `[d]` scale, a scalar,
    an odd size) is flattened, zero-padded to a multiple of n and cut into n
    runs, as every leaf once was.

    The sharded tree is `{str(i): flat_vector}` keyed by leaf index — a dict
    so optimizer states built over it carry the leaf index in their tree
    paths, which is what lets `to_logical`/`from_logical` map optimizer
    moments back to param shapes without knowing the optimizer's structure.
    A global vector is the concatenation of the chips' flattened shards
    (`padded[i]` elements): for a cut leaf a permutation of the flattened
    leaf (`_to_flat` / `_from_flat`, the one reshape-transpose), else the
    padded flattening itself.
    """

    def __init__(self, params_like: PyTree, mesh: Mesh, axis: str = "data"):
        self.mesh = mesh
        self.axis = axis
        self.n = n = _axis_size(mesh, axis)
        leaves, self.treedef = jax.tree_util.tree_flatten(
            jax.eval_shape(lambda: params_like)
        )
        self.shapes = [tuple(l.shape) for l in leaves]
        self.dtypes = [l.dtype for l in leaves]
        self.sizes = [int(math.prod(s)) if s else 1 for s in self.shapes]
        self.padded = [-(-s // n) * n for s in self.sizes]
        # The dimension a leaf is cut along, None where it is cut flat.
        self.dims: List[Optional[int]] = [
            next((d for d in range(len(s) - 1, 0, -1) if s[d] and s[d] % n == 0), None)
            for s in self.shapes
        ]

    # ---------------------------------------------------- the partition
    def _shard_shape(self, i: int) -> Tuple[int, ...]:
        """A cut leaf's slice on one chip, before it is flattened."""
        s, d = self.shapes[i], self.dims[i]
        return s[:d] + (s[d] // self.n,) + s[d + 1 :]

    def _to_flat(self, i: int, leaf):
        """A whole leaf (numpy or jax) -> its global vector: chip r's shard
        at [r*m, (r+1)*m), m = padded[i] // n. A flat-cut leaf's pad is left
        to the caller."""
        d = self.dims[i]
        if d is None:
            return leaf.reshape((-1,))
        shard = self._shard_shape(i)
        chips_first = (d,) + tuple(range(d)) + tuple(range(d + 1, len(shard) + 1))
        return leaf.reshape(shard[:d] + (self.n,) + shard[d:]).transpose(chips_first).reshape((-1,))

    def _from_flat(self, i: int, flat):
        """Inverse of `_to_flat` (pad dropped)."""
        d = self.dims[i]
        if d is None:
            return flat[: self.sizes[i]].reshape(self.shapes[i])
        shard = self._shard_shape(i)
        chips_at_d = tuple(range(1, d + 1)) + (0,) + tuple(range(d + 1, len(shard) + 1))
        return flat.reshape((self.n,) + shard).transpose(chips_at_d).reshape(self.shapes[i])

    def _padded_flat(self, i: int, leaf: jax.Array) -> jax.Array:
        flat = self._to_flat(i, leaf)
        pad = self.padded[i] - self.sizes[i]
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
        return flat

    # Inside a shard_map over `axis`, on a leaf every chip holds whole:
    def local_shard(self, i: int, leaf: jax.Array, r) -> jax.Array:
        """Chip r's shard of the leaf, flattened for the optimizer."""
        m = self.padded[i] // self.n
        d = self.dims[i]
        if d is None:
            return lax.dynamic_slice_in_dim(self._padded_flat(i, leaf), r * m, m)
        k = self.shapes[i][d] // self.n
        return lax.dynamic_slice_in_dim(leaf, r * k, k, d).reshape((-1,))

    def local_shards(self, tree: PyTree, r) -> Dict[str, jax.Array]:
        return {str(i): self.local_shard(i, leaf, r) for i, leaf in enumerate(jax.tree_util.tree_leaves(tree))}

    def reduce_scatter(self, i: int, g: jax.Array) -> jax.Array:
        """The chips' sum of a per-chip gradient, this chip's shard of it,
        flattened. A cut leaf goes to the collective in the shape the
        backward pass left it in (see the module docstring)."""
        d = self.dims[i]
        if d is None:
            return lax.psum_scatter(self._padded_flat(i, g), self.axis, scatter_dimension=0, tiled=True)
        return lax.psum_scatter(g, self.axis, scatter_dimension=d, tiled=True).reshape((-1,))

    def all_gather(self, i: int, shard: jax.Array) -> jax.Array:
        """The chips' flattened shards -> the whole leaf in its own shape."""
        d = self.dims[i]
        if d is None:
            return self._from_flat(i, lax.all_gather(shard, self.axis, axis=0, tiled=True))
        return lax.all_gather(shard.reshape(self._shard_shape(i)), self.axis, axis=d, tiled=True)

    def all_gather_tree(self, shards: Dict[str, jax.Array]) -> PyTree:
        """The chips' shard dicts -> the logical tree, every leaf whole.

        The step gathers the UPDATES and adds them to the whole parameters
        rather than gathering updated shards: the TPU compiler never lets an
        all-gather's result be the step's output buffer. A bare gather is
        copied into it, and with the parameters donated they are also copied
        OUT of it at the step's start: 3.2 GiB more live through a Mistral
        step, enough to make XLA rematerialise the head's logits (PERF.md §6,
        PR 57: +19 ms). An elementwise op behind the gather writes the output
        in place, and the sum is the same numbers on the same elements."""
        leaves = [self.all_gather(i, shards[str(i)]) for i in range(len(self.shapes))]
        return jax.tree_util.tree_unflatten(self.treedef, leaves)

    # ------------------------------------------------------------ params
    def flatten(self, tree: PyTree) -> Dict[str, jax.Array]:
        """Logical tree -> dict of global vectors (global arrays)."""
        leaves = jax.tree_util.tree_leaves(tree)
        return {str(i): self._padded_flat(i, jnp.asarray(leaf)) for i, leaf in enumerate(leaves)}

    def unflatten(self, flats: Dict[str, jax.Array]) -> PyTree:
        leaves = [self._from_flat(i, flats[str(i)]) for i in range(len(self.shapes))]
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
        """Sharded optimizer state -> world-size-independent logical tree:
        moment leaves in their param's shape and element order, pad dropped.
        This is the form `elastic_checkpoint` stores."""

        def one(path, leaf):
            i = self._leaf_index(path)
            arr = jax.device_get(leaf)
            if (
                i is not None
                and getattr(leaf, "ndim", 0) == 1
                and leaf.shape[0] == self.padded[i]
            ):
                return self._from_flat(i, arr)
            return arr

        return jax.tree_util.tree_map_with_path(one, opt_state)

    def from_logical(self, logical: PyTree) -> PyTree:
        """Inverse of to_logical at THIS sharder's world size: re-cut (and
        re-pad with zeros: exact — the pad region of a fresh or restored run
        is zero by construction) and place each moment sharded over the
        axis."""

        def one(path, leaf):
            i = self._leaf_index(path)
            arr = jnp.asarray(leaf)
            if i is not None and tuple(arr.shape) == self.shapes[i]:
                return jax.device_put(
                    self._padded_flat(i, arr), NamedSharding(self.mesh, P(self.axis))
                )
            return jax.device_put(arr, NamedSharding(self.mesh, P()))

        return jax.tree_util.tree_map_with_path(one, logical)

    def place_opt(self, opt_state: PyTree) -> PyTree:
        """Device-places a (host) flat-vector optimizer state under its
        sharding specs (restore path at the SAME representation)."""
        specs = self.opt_specs(opt_state)
        return jax.tree_util.tree_map(
            lambda leaf, spec: jax.device_put(leaf, NamedSharding(self.mesh, spec)),
            opt_state,
            specs,
        )


def init_opt_state(tx, params: PyTree, mesh: Mesh, axis: str = "data") -> PyTree:
    """Optimizer state sharded over `axis`: each device initializes state
    for only ITS shard of the params (~1/N bytes per chip)."""
    sharder = ZeroSharder(params, mesh, axis)
    struct = jax.eval_shape(tx.init, sharder.shard_struct())
    specs = sharder.opt_specs(struct)
    fn = shard_map(
        tx.init,
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
    grads go through ONE reduce_scatter a leaf (grad shard), the
    shard-local tx.update, and ONE all_gather a leaf (the updates, added
    to the whole params) — allreduce-equivalent bytes, 1/N optimizer state.
    """
    sharder = ZeroSharder(params_like, mesh, axis)
    n = sharder.n
    opt_struct = jax.eval_shape(tx.init, sharder.shard_struct())
    opt_specs = sharder.opt_specs(opt_struct)

    def inner(params, opt_state, batch):
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        import optax

        # The three scopes are rows of models/transformer.SCOPES: a device
        # trace splits the step's time by them.
        with jax.named_scope("zero.grad_scatter"):
            # /n turns sum-of-local-means into the global mean (equal local
            # batch sizes by construction of the spec).
            g_shards = {
                str(i): sharder.reduce_scatter(i, g) / n
                for i, g in enumerate(jax.tree_util.tree_leaves(grads))
            }
        with jax.named_scope("zero.update"):
            p_shards = sharder.local_shards(params, lax.axis_index(axis))
            updates, new_opt = tx.update(g_shards, opt_state, p_shards)
        with jax.named_scope("zero.param_gather"):
            new_params = optax.apply_updates(params, sharder.all_gather_tree(updates))
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
    opt_struct = jax.eval_shape(tx.init, sharder.shard_struct())
    opt_specs = sharder.opt_specs(opt_struct)

    def inner(params, opt_state, grads):
        import optax

        r = lax.axis_index(axis)
        with jax.named_scope("zero.grad_scatter"):  # a slice here: the gradients come reduced
            g_shards = sharder.local_shards(grads, r)
        with jax.named_scope("zero.update"):
            p_shards = sharder.local_shards(params, r)
            updates, new_opt = tx.update(g_shards, opt_state, p_shards)
        with jax.named_scope("zero.param_gather"):
            return optax.apply_updates(params, sharder.all_gather_tree(updates)), new_opt

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
