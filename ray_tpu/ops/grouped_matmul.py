"""The routed FFN's expert products at a prefill chunk's rows, as pallas TPU
kernels that read each touched expert's matrix once and multiply it by that
expert's own rows only.

A serving step holds a group's expert matrices as one stack `[layers, E, K,
N]` (models/transformer.py `_experts_in_place`) and a chunk's (row, chosen
expert) pairs sorted by expert, `xs [m, K]` with `group_sizes [E]`
(`_routed_ffn`'s dispatch). The two products the repo had both pay for pairs
the router did not make: the every-expert product multiplies all E experts by
every row (E / k times the FLOPs), `lax.ragged_dot` pads every group to 512
rows. At a chunk's 256 rows x 8 choices over 128 experts a group is 16 rows,
every expert is touched, and the least a product can cost is the experts'
bytes. These kernels cost that:

- the stack stays in HBM as it lies; the layer's index and each grid step's
  expert are scalar-prefetched and the weight block a step takes is named by
  them, `(layer, expert, all of K, a block of N)`: nothing slices a layer out
  of the stack (a slice handed to a custom call is a copy of it, every step);
- the rows are walked in tiles of `TILE_ROWS`, and a tile that holds rows of
  several experts is visited once for each of them, in order (`visits`): the
  visit multiplies the whole tile by its expert and keeps the rows that are
  the expert's own. The row tile and the output tile stay in VMEM over a
  tile's visits, and an expert's block stays over an expert's visits, so an
  expert is read once a block of N whatever the tile, an expert with no row
  is never read, and a tile's rows are read once a block of N. The MXU's work
  is `visits x TILE_ROWS` rows, `(m / TILE_ROWS + E - 1)` visits at most:
  under the weights' transfer while a visit's rows are few;
- the grid is (blocks of N, visits), the visits inner: a block of N of every
  touched expert, then the next. float32 accumulation, results in the
  parameters' type, as `lax.ragged_dot` gives them in `_routed_ffn`;
- rows behind the last group (one chip's share of the experts: the picks of
  experts it does not hold) are no expert's: a tile of them alone is never
  visited, and what the result holds there is not a number.

`grouped_swiglu` is the gate and up products and `silu(gate) * up` in one
kernel (one read of the row tile, one result written), with gate and up
rounded to the parameters' type before the activation as the separate
products round them; `grouped_matmul` is the down product.

`interpret=True` (selected when this process's backend is not a TPU) runs the
same kernels on the CPU for tests.
"""

from __future__ import annotations

import functools
import importlib
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import largest_divisor

MATMUL_KERNEL_NAME = "grouped_matmul"
SWIGLU_KERNEL_NAME = "grouped_swiglu"
LANES = 128
TILE_ROWS = 64  # rows of xs a visit multiplies: a multiple of bf16's 16-row packing
# Weight blocks [K, a block of N] in VMEM a matrix: the one being multiplied and the next touched expert's on its way.
# A third changed nothing on the chip (PERF.md §6, PR 47).
BUFFERS = 2
# The most a visit's weight blocks hold together, gate's and up's or down's alone: BUFFERS of them, the row tile and
# the result tile stay inside the 16 MiB of VMEM a kernel has unasked. Smaller blocks read slower (more visits).
BLOCK_BYTES = 4 << 20


def _auto_interpret() -> bool:
    """The flash kernel's rule (its module is patched where a program is
    compiled for a described TPU from a CPU process: benchmarks/rehearse.py)."""
    return importlib.import_module("ray_tpu.ops.flash_attention")._auto_interpret()


class Visits(NamedTuple):
    """The grid's inner axis, from `visits`: visit v multiplies row tile
    `tile[v]` by the `run[v]`-th touched expert, `expert[run[v]]`, and keeps
    rows [lo[v], hi[v]); only the first `total[0]` visits are real, the rest
    repeat the last. `runs[0]` experts are touched."""

    tile: jax.Array  # [V] int32
    lo: jax.Array  # [V] int32
    hi: jax.Array  # [V] int32
    run: jax.Array  # [V] int32
    expert: jax.Array  # [E] int32, the touched experts in order; behind them the last
    total: jax.Array  # [1] int32
    runs: jax.Array  # [1] int32


def visits(group_sizes, m: int, tile_rows: int = TILE_ROWS) -> Visits:
    """The (row tile, expert) pairs to multiply, in order of expert, for m
    rows sorted by expert with `group_sizes` [E] rows each (their sum at
    most m): a group visits every tile one of its rows lies in, an empty
    group none. At most m / tile_rows + E - 1 of them, a static bound."""
    sizes = group_sizes.astype(jnp.int32)
    n_tiles, E = pl.cdiv(m, tile_rows), sizes.shape[0]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first_tile = starts // tile_rows
    tiles_of = jnp.where(sizes > 0, (ends - 1) // tile_rows - first_tile + 1, 0)
    visit_ends = jnp.cumsum(tiles_of)
    total = visit_ends[-1]
    v = jnp.minimum(jnp.arange(n_tiles + E - 1, dtype=jnp.int32), total - 1)
    group = jnp.sum(visit_ends[None, :] <= v[:, None], axis=1, dtype=jnp.int32)  # below E: v < total
    tile = jnp.clip(first_tile[group] + v - (visit_ends - tiles_of)[group], 0, n_tiles - 1)
    rank = jnp.cumsum(sizes > 0, dtype=jnp.int32) - 1  # a touched expert's place among the touched
    runs = rank[-1] + 1
    expert = jnp.sum(rank[None, :] < jnp.minimum(jnp.arange(E), runs - 1)[:, None], axis=1, dtype=jnp.int32)
    return Visits(tile, starts[group], ends[group], rank[group], jnp.minimum(expert, E - 1), total.reshape(1), runs.reshape(1))


def _kernel(layer_ref, tile_ref, lo_ref, hi_ref, run_ref, expert_ref, total_ref, runs_ref, x_ref, *refs, product):
    """One visit of one block of N. refs: the stacks in HBM, the result tile,
    a ring of BUFFERS weight blocks a stack, the ring's DMA semaphores."""
    n = (len(refs) - 2) // 2
    stacks, o_ref, rings, sems = refs[:n], refs[n], refs[n + 1 : 2 * n + 1], refs[-1]
    j, v = pl.program_id(0), pl.program_id(1)
    cols = o_ref.shape[1]
    runs = runs_ref[0]
    fetches = runs * pl.num_programs(0)  # fetch q is block q // runs of N of touched expert q % runs

    def fetch(q, act):
        block = pl.ds(pl.multiple_of(lax.div(q, runs) * cols, cols), cols)
        for i in range(n):
            src = stacks[i].at[layer_ref[0], expert_ref[lax.rem(q, runs)], :, block]
            act(pltpu.make_async_copy(src, rings[i].at[lax.rem(q, BUFFERS)], sems.at[i, lax.rem(q, BUFFERS)]))

    @pl.when(v < total_ref[0])
    def _():
        q = j * runs + run_ref[v]

        @pl.when((v == 0) | (run_ref[jnp.maximum(v - 1, 0)] != run_ref[v]))
        def _():  # the expert's first visit: its block is awaited here, and the slot the last one left is filled
            for ahead in range(BUFFERS - 1):  # the very first visit starts the ring

                @pl.when((q == 0) & (ahead < fetches))
                def _():
                    fetch(jnp.int32(ahead), lambda c: c.start())

            @pl.when(q + BUFFERS - 1 < fetches)
            def _():
                fetch(q + BUFFERS - 1, lambda c: c.start())

            fetch(q, lambda c: c.wait())

        new = product(x_ref[...], *(ring[lax.rem(q, BUFFERS)] for ring in rings)).astype(o_ref.dtype)
        # The expert's own rows from `new`; the rows before them as the tile's earlier visits left them (rows below
        # a group belong to the groups before it, which came first); 0 behind them (a later visit's, or no expert's).
        rows = tile_ref[v] * o_ref.shape[0] + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
        o_ref[...] = jnp.where(rows < lo_ref[v], o_ref[...], jnp.where(rows < hi_ref[v], new, jnp.zeros_like(new)))


def _down(x, w):
    return jnp.dot(x, w, preferred_element_type=jnp.float32)


def _swiglu(x, w_gate, w_up, limit: float = 0.0):
    # Each rounded to the weights' type first, as two products that hand over gate and up would.
    gate = jnp.dot(x, w_gate, preferred_element_type=jnp.float32).astype(w_gate.dtype).astype(jnp.float32)
    up = jnp.dot(x, w_up, preferred_element_type=jnp.float32).astype(w_up.dtype).astype(jnp.float32)
    if limit:  # a clamped SwiGLU: the gate cut from above, up on both sides
        gate, up = jnp.minimum(gate, limit), jnp.clip(up, -limit, limit)
    return jax.nn.silu(gate) * up


def block_cols(K: int, N: int, itemsize: int, matrices: int = 1) -> int:
    """Columns of a weight block [K, .] of each of `matrices` stacks: the most
    whole lanes that divide N with the blocks inside BLOCK_BYTES together, or
    all of N where N is not made of whole lanes."""
    if N % LANES:
        return N
    return LANES * largest_divisor(N // LANES, BLOCK_BYTES // (matrices * K * LANES * itemsize))


def _call(product, name: str, xs, stacks, layer, plan: Visits, tile_rows: int, interpret: Optional[bool]):
    m, K = xs.shape
    N = stacks[0].shape[-1]
    for stack in stacks:
        if stack.ndim != 4 or stack.shape[2:] != (K, N):
            raise ValueError(f"{name}: an expert stack is [layers, E, {K}, {N}] beside rows of {K}, not {stack.shape}")
    if interpret is None:
        interpret = _auto_interpret()
    dtype = stacks[0].dtype
    cols = block_cols(K, N, dtype.itemsize, len(stacks))
    layer = jnp.asarray(layer, jnp.int32).reshape(1)
    ring = BUFFERS * len(stacks) * K * cols * dtype.itemsize
    tiles = 2 * tile_rows * (K * xs.dtype.itemsize + cols * dtype.itemsize)  # the pipeline's two of each
    return pl.pallas_call(
        functools.partial(_kernel, product=product),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(plan),
            grid=(N // cols, plan.tile.shape[0]),
            in_specs=[pl.BlockSpec((tile_rows, K), lambda j, v, layer, tile, *_: (tile[v], 0))] + [pl.BlockSpec(memory_space=pl.ANY)] * len(stacks),
            out_specs=pl.BlockSpec((tile_rows, cols), lambda j, v, layer, tile, *_: (tile[v], j)),
            scratch_shapes=[pltpu.VMEM((BUFFERS, K, cols), dtype)] * len(stacks) + [pltpu.SemaphoreType.DMA((len(stacks), BUFFERS))],
        ),
        out_shape=jax.ShapeDtypeStruct((m, N), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=ring + tiles + 4 * tile_rows * cols * 4 + (2 << 20),  # and a visit's float32 products
        ),
        interpret=interpret,
        name=name,
    )(layer, *plan, xs, *stacks)


def grouped_matmul(xs, stack, layer, plan: Visits, *, tile_rows: int = TILE_ROWS, interpret: Optional[bool] = None):
    """xs [m, K] rows sorted by expert times each row's own expert of layer
    `layer` (a scalar) of `stack` [layers, E, K, N] -> [m, N] in the stack's
    type; `plan` = visits(group_sizes, m, tile_rows). Rows behind the last
    group are left out: what the result holds there is arbitrary."""
    return _call(_down, MATMUL_KERNEL_NAME, xs, (stack,), layer, plan, tile_rows, interpret)


def grouped_swiglu(xs, gate_stack, up_stack, layer, plan: Visits, *, tile_rows: int = TILE_ROWS, interpret: Optional[bool] = None,
                   limit: float = 0.0):
    """silu(xs @ gate) * (xs @ up) with each row's own expert of layer
    `layer` of the two stacks [layers, E, K, N] -> [m, N]: grouped_matmul
    twice and the activation, in one pass over the rows. `limit` a, if not 0:
    silu(min(gate, a)) * clip(up, -a, a)."""
    product = functools.partial(_swiglu, limit=limit) if limit else _swiglu
    return _call(product, SWIGLU_KERNEL_NAME, xs, (gate_stack, up_stack), layer, plan, tile_rows, interpret)
