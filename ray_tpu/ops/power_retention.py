"""Power retention's decode step and its prefill chunk, each one pallas TPU
kernel a layer (`power_retention_decode`, `power_retention_prefill`). The
decode step first; the chunk's kernel has its own notes further down.

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
same kernels on the CPU for tests.
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

from .paged_attention import largest_divisor

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


# ------------------------------------------------------- a prefill chunk
#
# `transformer.retention_chunk` (this kernel's parity reference, and the path
# for shapes it cannot tile) takes a chunk of c rows of ONE sequence from the
# state before its first row to the state after its last. Written plainly it
# materialises phi(q) [c, n_heads, D] and phi(k) [c, n_kv_heads, D] in HBM
# (341 MB and 68 MB at 256 rows of 40:8 heads of 128) for three products over
# D. Here phi never leaves VMEM:
#
# - the grid is (K/V head, a group of `PHI_ROWS` of phi's head_dim / 2 + 1
#   rows); a step takes the [head_dim, PHI_ROWS * head_dim] slab of the head's
#   S, named by the scalar-prefetched (layer, slot), and for each phi row d of
#   it forms phi(k)'s and phi(q)'s row d from a lane rotation of k and of the
#   head's query rows (all c positions x r query heads at once), as the
#   decode kernel does;
# - against that [head_dim, head_dim] piece of S it accumulates the carried
#   numerator phi(q) S_in^T and, lane by lane, the carried denominator phi(q) .
#   z_in (both stay in VMEM over the head's groups), and writes the piece of
#   S_out = exp(L_last) S_in + (v left)^T phi(k) back IN PLACE (the pool is
#   aliased to the output; what a step does not name is not touched), with
#   left^T phi(k), z's update, as eight more rows of the same product. The
#   state makes one trip each way a chunk;
# - a chunk at position 0 (`carried` false) is the same kernel with the slab
#   read as zeros, whatever the page's last owner left there;
# - the products are float32 at the MXU's float32 contraction
#   (`lax.Precision.HIGHEST`: what RETENTION_PRECISION gives the plain
#   expression's einsums), accumulated in float32.
#
# The chunk's own pairs (`a = (q k^T)^2 decay`, [c, c] a head, 1 % of the
# work) stay in XLA beside the kernel, and the division follows where both
# parts meet; z (1 / 128 of the state) is finished there too.

PREFILL_KERNEL_NAME = "power_retention_prefill"
PHI_ROWS = 13  # rows of phi a grid step takes: a slab of 128 x 13 x 128 x 4 B = 852 KB, five steps a K/V head
CHUNK_ROWS = 256  # the longest chunk: its rows x query heads of q, numerator and denominator stay in VMEM over a K/V head's steps
PRECISION = lax.Precision.HIGHEST


def can_tile_prefill(rows: int, n_heads: int, n_kv_heads: int, head_dim: int) -> bool:
    """Whether the prefill kernel takes a chunk of `rows` positions of these
    heads: the decode kernel's shapes, in whole sublane tiles of rows, no more
    of them than VMEM holds of q, numerator and denominator at once."""
    return can_tile(n_heads, n_kv_heads, head_dim) and rows % 8 == 0 and rows <= CHUNK_ROWS


def _prefill_kernel(meta_ref, q_ref, k_ref, vl_ref, gl_ref, z_ref, s_ref, num_ref, den_ref, zup_ref, s_out, *, hd: int, phi_rows: int):
    j, group = pl.program_id(0), pl.program_id(1)
    half = hd // 2
    carried = meta_ref[2] > 0

    @pl.when(group == 0)
    def _():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    q, k, vl, gl = q_ref[...], k_ref[...], vl_ref[...], gl_ref[...]
    head = lax.broadcasted_iota(jnp.int32, (z_ref.shape[0], hd), 0)
    for i in range(phi_rows):
        d = group * phi_rows + i
        w = jnp.where((d == 0) | (d == half), 1.0, math.sqrt(2.0)).astype(jnp.float32)
        shift = jnp.where(d == 0, 0, hd - d)  # lane a of the rotated x holds x[(a + d) % hd]
        lanes = slice(i * hd, (i + 1) * hd)
        pk = k * pltpu.roll(k, shift, 1) * w  # [c, hd]: row d of phi(k), every position
        grown = jnp.dot(vl, pk, precision=PRECISION, preferred_element_type=jnp.float32)  # [hd + 8, hd]
        s_old = jnp.where(carried, s_ref[:, lanes], 0.0)
        s_out[:, lanes] = gl * s_old + grown[:hd]
        zup_ref[:, lanes] = grown[hd:]
        z_old = jnp.sum(jnp.where(head == j, z_ref[:, lanes], 0.0), axis=0, keepdims=True)  # [1, hd]: this head's row of the block
        s_w, z_w = s_old * w, z_old * w  # phi(q)'s weight rides on the state's side: 17 registers, not 160
        pq = q * pltpu.roll(q, shift, 1)  # [c * r, hd]: row d of phi(q), every position and query head
        num_ref[...] += lax.dot_general(pq, s_w, (((1,), (1,)), ((), ())), precision=PRECISION, preferred_element_type=jnp.float32)
        den_ref[...] += pq * z_w


def power_retention_prefill(q, k, v, log_g, s, z, layer, slot, carried, valid, *, eps: float, interpret: Optional[bool] = None):
    """One chunk of ONE sequence against its state in a pool, in place.

    q [c, n_heads, hd], k / v [c, n_kv_heads, hd] (any float type), log_g
    [c, n_kv_heads]; s [layers, slots, n_kv_heads, hd, D], z [layers, slots,
    n_kv_heads, D] float32, the pool; layer, slot: scalars, the sequence's
    state; carried: scalar bool, whether that state holds the rows before the
    chunk (false: the chunk starts from nothing, whatever the slot holds);
    valid [c] bool: rows past a prompt's length (they follow every valid row)
    neither decay the state nor enter it; eps: the normaliser's. Returns
    (y [c, n_heads, hd] float32, s, z): `transformer.retention_chunk`'s
    result, the pool with that one state advanced over the chunk."""
    c, H, hd = q.shape
    KV = k.shape[1]
    r = H // KV
    D = s.shape[-1]
    if not can_tile_prefill(c, H, KV, hd) or D != (hd // 2 + 1) * hd:
        raise ValueError(f"power_retention_prefill does not tile {c} rows of {H}:{KV} heads of {hd} over a state of {D}")
    if interpret is None:
        interpret = _auto_interpret()
    f32 = jnp.float32
    dot = functools.partial(jnp.einsum, precision=PRECISION)
    q = q.astype(f32).reshape(c, KV, r, hd) / math.sqrt(hd)
    k, v = k.astype(f32), v.astype(f32)
    log_g = jnp.where(valid[:, None], log_g.astype(f32), 0.0)
    L = jnp.cumsum(log_g, axis=0)  # [c, kv]: the decay from the chunk's start to each row, that row's gate included
    # The chunk's own pairs, by the quadratic expression.
    back = jnp.arange(c)[:, None] - jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(back >= 0, L.T[:, :, None] - L.T[:, None, :], -jnp.inf))  # [kv, t, s]
    a = jnp.square(dot("tjrd,sjd->jrts", q, k)) * decay[:, None]
    num = dot("jrts,sje->tjre", a, v)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), -1, 0)
    # The earlier ones through the state, and what each row leaves in it at the chunk's end.
    left = jnp.where(valid[:, None], jnp.exp(L[-1][None, :] - L), 0.0)  # [c, kv]
    grow = jnp.concatenate([jnp.moveaxis(v * left[..., None], 0, -1), jnp.broadcast_to(left.T[:, None], (KV, 8, c))], axis=1)  # [kv, hd + 8, c]
    gl = jnp.exp(L[-1])  # [kv]
    z_in = jnp.where(carried, z[layer, slot], 0.0)
    meta = jnp.stack([jnp.asarray(layer, jnp.int32), jnp.asarray(slot, jnp.int32), jnp.asarray(carried, jnp.int32)])
    phi_rows = largest_divisor(hd // 2 + 1, PHI_ROWS)
    width = phi_rows * hd

    def per_head(j, group, meta):
        return (j, 0, 0)

    def per_group(j, group, meta):
        return (j, 0, group)

    def z_block(j, group, meta):  # z as it lies in the pool, the K/V heads on the sublanes: a step reads its head's row
        return (0, group)

    def s_block(j, group, meta):
        return (meta[0], meta[1], j, 0, group)

    num_c, den_c, z_up, s = pl.pallas_call(
        functools.partial(_prefill_kernel, hd=hd, phi_rows=phi_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(KV, (hd // 2 + 1) // phi_rows),
            in_specs=[
                pl.BlockSpec((None, c * r, hd), per_head),
                pl.BlockSpec((None, c, hd), per_head),
                pl.BlockSpec((None, hd + 8, c), per_head),
                pl.BlockSpec((None, 1, hd), per_head),
                pl.BlockSpec((KV, width), z_block),
                pl.BlockSpec((None, None, None, hd, width), s_block),
            ],
            out_specs=[
                pl.BlockSpec((None, c * r, hd), per_head),
                pl.BlockSpec((None, c * r, hd), per_head),
                pl.BlockSpec((None, 8, width), per_group),
                pl.BlockSpec((None, None, None, hd, width), s_block),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((KV, c * r, hd), f32),
            jax.ShapeDtypeStruct((KV, c * r, hd), f32),
            jax.ShapeDtypeStruct((KV, 8, D), f32),
            jax.ShapeDtypeStruct(s.shape, s.dtype),
        ],
        input_output_aliases={6: 3},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=PREFILL_KERNEL_NAME,
    )(
        meta,
        jnp.moveaxis(q, 0, 1).reshape(KV, c * r, hd),
        jnp.moveaxis(k, 0, 1),
        grow,
        jnp.broadcast_to(gl[:, None, None], (KV, 1, hd)),
        z_in,
        s,
    )
    z = z.at[layer, slot].set(gl[:, None] * z_in + z_up[:, 0])
    reached = jnp.exp(L)[:, :, None]  # [c, kv, 1]
    num = num + reached[..., None] * jnp.moveaxis(num_c.reshape(KV, c, r, hd), 0, 1)
    den = den + reached * jnp.moveaxis(jnp.sum(den_c, axis=-1).reshape(KV, c, r), 0, 1)
    return (num / (den[..., None] + eps)).reshape(c, H, hd), s, z
