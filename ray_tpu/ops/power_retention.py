"""Power retention's decode step as one pallas TPU kernel a layer.

Under power retention (models/transformer.py, `retention_degree`) a served
sequence keeps no K/V: its past in a layer is one state a K/V head, `S`
[head_dim, D] and `z` [D] float32 (34 MB a layer at 8 K/V heads of 128), and
a decode step decays all of it by the token's gate, adds the token and reads
it with the K/V head's query heads (`transformer.retention_step`, this
kernel's parity reference and the path for shapes it cannot tile). That plain
expression gathers the rows' states out of the pool, writes the updated copy,
reads it again for the queries and scatters it back. This kernel moves a
state once in and once out:

- the pool stays in HBM as it lies, `s` [layers, slots, n_kv_heads, head_dim,
  D] and `z` [layers, slots, n_kv_heads, D]; the layer, each row's slot and
  whether it is live are scalar-prefetched, and the block a grid step takes
  is named by them: (row, K/V head, a group of `ROWS` of S's head_dim rows),
  [ROWS, D] float32, pipelined in and out by the grid. The pool is aliased to
  the outputs (`input_output_aliases`): what a step does not name is not
  touched. A row that is not live names the trash slot's first block at every
  step, so it is fetched once, and computes nothing;
- a block holds whole rows of phi's axis, so nothing is carried between grid
  steps: `S' = g S + v phi(k)^T` and the query heads' `phi(q)^T S'` come from
  the values in hand, `z' = g z + phi(k)` and `phi(q)^T z'` at the K/V head's
  first group, all on the vector unit in float32 (a [5, D] x [D, ROWS] float32
  product on the MXU would be bound by loading S as its stationary operand, at
  under the HBM's rate);
- phi(x) in the state's layout is x times x rotated by d lanes, d = 0 ..
  head_dim / 2 (`transformer.retention_phi`): k, the query heads (scaled by
  1 / sqrt(head_dim): the scale inside the power), the gate and v ride in as
  the eight rows of one [8, head_dim] tile a (row, K/V head), and one lane
  rotation of that tile makes row d of phi for k and every query head at once.

`interpret=True` (selected when this process's backend is not a TPU) runs the
same kernel on the CPU for tests.
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "power_retention_decode"
LANES = 128
TILE_ROWS = 8  # the input tile's rows: k, the query heads, the gate, v
ROWS = 32  # rows of S (v's index) a grid step takes: a block of 32 x 8 320 x 4 B = 1 MB


def _auto_interpret() -> bool:
    """The flash kernel's rule (its module is patched where a program is
    compiled for a described TPU from a CPU process: benchmarks/rehearse.py)."""
    return importlib.import_module("ray_tpu.ops.flash_attention")._auto_interpret()


def can_tile(n_heads: int, n_kv_heads: int, head_dim: int) -> bool:
    """Whether the kernel takes these shapes: a head is one vector register
    wide, and k, the K/V head's query heads, the gate and v fit the eight rows
    of one tile. Shapes decide, nothing else does."""
    return head_dim == LANES and n_heads % n_kv_heads == 0 and n_heads // n_kv_heads + 3 <= TILE_ROWS


def _kernel(slots_ref, live_ref, layer_ref, x_ref, s_ref, z_ref, y_ref, den_ref, s_out, z_out, *, r: int, hd: int):
    del slots_ref, layer_ref  # the index maps read them
    b, j, group = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    half = hd // 2

    def phi_row(x, d):
        """Row d of phi for every row of the tile x [8, hd] at once."""
        rotated = x if d == 0 else pltpu.roll(x, hd - d, 1)  # lane a holds x[(a + d) % hd]
        return x * rotated * (1.0 if d in (0, half) else math.sqrt(2.0))

    @pl.when(live_ref[b] > 0)
    def _():
        x = x_ref[...]
        g = x[TILE_ROWS - 2 : TILE_ROWS - 1, :]  # the gate, on every lane
        g_rows = jnp.broadcast_to(g, (ROWS, hd))
        # v's entries of this group as a column: v lies along the tile's lanes.
        v_row = x[TILE_ROWS - 1 : TILE_ROWS, :]
        row = lax.broadcasted_iota(jnp.int32, (ROWS, hd), 0) + group * ROWS
        lane = lax.broadcasted_iota(jnp.int32, (ROWS, hd), 1)
        v_rows = jnp.broadcast_to(jnp.sum(jnp.where(lane == row, v_row, 0.0), axis=1, keepdims=True), (ROWS, hd))
        acc = [jnp.zeros((ROWS, hd), jnp.float32) for _ in range(r)]
        for d in range(half + 1):
            p = phi_row(x, d)
            lanes = slice(d * hd, (d + 1) * hd)
            s_new = g_rows * s_ref[:, lanes] + v_rows * p[0:1, :]
            s_out[:, lanes] = s_new
            for i in range(r):
                acc[i] = acc[i] + s_new * p[1 + i : 2 + i, :]
        # Query head i's result for this group's entries, on lane i.
        y = jnp.zeros((ROWS, hd), jnp.float32)
        for i in range(r):
            y = jnp.where(lane == i, jnp.sum(acc[i], axis=1, keepdims=True), y)
        y_ref[...] = y

        @pl.when(group == 0)
        def _():
            # The block holds z of all the row's K/V heads and stays in place
            # over them: this step's head is row j of it, the heads before it
            # are already in the output block, the ones after still the input's.
            head = lax.broadcasted_iota(jnp.int32, (z_ref.shape[0], hd), 0)
            den = jnp.zeros((TILE_ROWS, hd), jnp.float32)
            for d in range(half + 1):
                p = phi_row(x, d)
                lanes = slice(d * hd, (d + 1) * hd)
                z_old = z_ref[:, lanes]
                z_new = jnp.sum(jnp.where(head == j, g * z_old + p[0:1, :], 0.0), axis=0, keepdims=True)  # [1, hd]
                z_out[:, lanes] = jnp.where(head == j, z_new, jnp.where(head < j, z_out[:, lanes], z_old))
                den = den + z_new * p
            den_ref[...] = den  # row 1 + i: query head i's phi(q) . z', lane by lane


def power_retention_decode(q, k, v, log_g, s, z, layer, slots, live, *, eps: float, interpret: Optional[bool] = None):
    """One token a row against the states of a pool, in place.

    q [B, n_heads, hd], k / v [B, n_kv_heads, hd] (any float type), log_g
    [B, n_kv_heads]; s [layers, slots, n_kv_heads, hd, D], z [layers, slots,
    n_kv_heads, D] float32, the pool; layer: scalar; slots [B] int32, each
    row's state (distinct among live rows); live [B] bool; eps: the
    normaliser's. Returns (y [B, n_heads, hd] float32, s, z): the pool with
    the live rows' states of that layer advanced by their token. A row that
    is not live leaves every slot but the trash slot (0) as it was; its y is
    arbitrary."""
    B, H, hd = q.shape
    KV = k.shape[1]
    r = H // KV
    D = s.shape[-1]
    if not can_tile(H, KV, hd) or D != (hd // 2 + 1) * hd:
        raise ValueError(f"power_retention_decode does not tile {H}:{KV} heads of {hd} over a state of {D}")
    if interpret is None:
        interpret = _auto_interpret()
    f32 = jnp.float32
    qs = q.astype(f32).reshape(B, KV, r, hd) / math.sqrt(hd)
    gate = jnp.broadcast_to(jnp.exp(log_g.astype(f32))[:, :, None, None], (B, KV, 1, hd))
    pad = jnp.zeros((B, KV, TILE_ROWS - 3 - r, hd), f32)
    x = jnp.concatenate([k.astype(f32)[:, :, None], qs, pad, gate, v.astype(f32)[:, :, None]], axis=2)  # [B, KV, 8, hd]
    live = live.astype(jnp.int32)
    slots = jnp.where(live > 0, slots.astype(jnp.int32), 0)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def per_row(b, j, group, slots, live, layer):
        return (b, j, 0, 0)

    def s_block(b, j, group, slots, live, layer):
        return (layer[0], slots[b], j * live[b], group * live[b], 0)

    def z_block(b, j, group, slots, live, layer):
        return (layer[0], slots[b], 0, 0)

    y, den, s, z = pl.pallas_call(
        functools.partial(_kernel, r=r, hd=hd),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, KV, hd // ROWS),
            in_specs=[
                pl.BlockSpec((None, None, TILE_ROWS, hd), per_row),
                pl.BlockSpec((None, None, None, ROWS, D), s_block),
                pl.BlockSpec((None, None, KV, D), z_block),
            ],
            out_specs=[
                pl.BlockSpec((None, None, ROWS, hd), lambda b, j, group, *_: (b, j, group, 0)),
                pl.BlockSpec((None, None, TILE_ROWS, hd), per_row),
                pl.BlockSpec((None, None, None, ROWS, D), s_block),
                pl.BlockSpec((None, None, KV, D), z_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, KV, hd, hd), f32),
            jax.ShapeDtypeStruct((B, KV, TILE_ROWS, hd), f32),
            jax.ShapeDtypeStruct(s.shape, s.dtype),
            jax.ShapeDtypeStruct(z.shape, z.dtype),
        ],
        input_output_aliases={4: 2, 5: 3},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots, live, layer, x, s, z)
    num = jnp.swapaxes(y[..., :r], -1, -2)  # [B, KV, r, hd]
    den = jnp.sum(den[:, :, 1 : 1 + r, :], axis=-1)  # [B, KV, r]
    return (num / (den[..., None] + eps)).reshape(B, H, hd), s, z
