"""The routed FFN's two row movements (models/transformer.py `_routed_ffn`),
each with its transpose written by hand.

Dispatch repeats every token k times and sorts the (token, expert) pairs by
expert; combine brings the sorted rows back to token order and sums a
token's k rows under the router's weights. Both move rows by a permutation
(`order` and `inverse` are each other's inverse), so the transpose of each
is again a gather, followed by a sum over the k copies. Autodiff through
`jnp.take` cannot know that and emits a scatter-add of [n * k, d] rows,
which on a TPU costs several times the gather of the same rows. The
forward of both functions is the plain expression, op for op.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def dispatch_rows(x, order, inverse, k: int):
    """x [n, d] -> [n * k, d]: sorted row r is the token of pair order[r]."""
    return jnp.take(x, order // k, axis=0)


def _dispatch_rows_fwd(x, order, inverse, k):
    return dispatch_rows(x, order, inverse, k), inverse


def _dispatch_rows_bwd(k, inverse, dxs):
    # Pair p sits in sorted row inverse[p] and a token's k pairs are
    # neighbours: one gather, then a float32 sum over k, rounded once.
    rows = jnp.take(dxs, inverse, axis=0).reshape(-1, k, dxs.shape[-1])
    return jnp.sum(rows, axis=1, dtype=jnp.float32).astype(dxs.dtype), None, None


dispatch_rows.defvjp(_dispatch_rows_fwd, _dispatch_rows_bwd)


@jax.custom_vjp
def combine_rows(ys, top_p, order, inverse):
    """Sorted rows ys [n * k, d] back to token order, weighted by top_p
    [n, k] and summed over each token's k rows in float32 -> [n, d]."""
    n, k = top_p.shape
    rows = jnp.take(ys, inverse, axis=0).reshape(n, k, ys.shape[-1])
    return jnp.sum(rows.astype(jnp.float32) * top_p[..., None], axis=1).astype(ys.dtype)


def _combine_rows_fwd(ys, top_p, order, inverse):
    return combine_rows(ys, top_p, order, inverse), (ys, top_p, order, inverse)


def _combine_rows_bwd(res, dout):
    # Sorted row r is pair order[r] of token order[r] // k: its cotangent is
    # that token's row of the [n, d] dout times the pair's weight, gathered
    # and scaled in one pass, so no un-sorted [n * k, d] cotangent exists.
    # The weights' gradient is the row-wise float32 dot with ys AS SORTED,
    # un-sorted as a vector: the backward needs no un-sort of ys either.
    ys, top_p, order, inverse = res
    g = jnp.take(dout, order // top_p.shape[1], axis=0).astype(jnp.float32)
    p = jnp.take(top_p.reshape(-1), order)
    dp = jnp.sum(ys.astype(jnp.float32) * g, axis=-1)
    return (g * p[:, None]).astype(ys.dtype), jnp.take(dp, inverse).reshape(top_p.shape), None, None


combine_rows.defvjp(_combine_rows_fwd, _combine_rows_bwd)
