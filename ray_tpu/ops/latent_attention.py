"""Paged latent attention (MLA in its absorbed form) as pallas TPU kernels:
one new token a slot (decode), and a chunk of a prompt's rows over the
prompt's own latent pages (prefill).

What a latent-attention layer keeps of a position is ONE row shared by all
heads: `[c_kv | k_r]`, the normed latent (`kv_lora_rank` lanes) and the
rotated key part (`qk_rope_dim` lanes), padded with zeros to whole 128-lane
tiles (`row_width`): the pool is `[layers, pages, page_tokens, row_width]`.
Absorbed, a head's query is `[q_nope W_UK | q_rope]` (the same width, the
same zero padding), its scores are one product with that row, and its output
in latent space is the softmax times the row's first `v_width` =
`kv_lora_rank` lanes: the value is a prefix of the key. So every head of
every query row is one more ROW of a multi-query attention over a single
key/value head, which is how both kernels multiply: a block of query rows
`[positions x n_heads, row_width]` against a block of latent rows, the walk,
the DMAs (one a page: a page is contiguous), the online softmax and the
bounds of ops/paged_attention.py, whose kernels these are siblings of. What
expands `c_kv` into a head's keys and values never runs here: `W_UK` is
applied to the query before and `W_UV` to the output after
(models/transformer.py, the "latent" row of `KINDS`).

- `paged_latent_attention` (decode): grid over the slots; slot b's `n_heads`
  rows walk its `ceil(lengths[b] / page_tokens)` live pages. A cached position
  costs `2 x row bytes` read once and `2 x n_heads x (row_width + v_width)`
  FLOPs: at 128 heads 242 FLOP a byte, the v5e's ridge, so neither bound hides
  the other.
- `paged_latent_prefill_attention` (a chunk of ONE prompt): grid over blocks
  of `positions_per_block` positions (x n_heads rows), each walking the
  prompt's pages up to its own last position under the causal mask. Many rows
  share a block of latent rows, so the MXU bounds it.

The plain XLA expressions `latent_attention_gather` and
`latent_prefill_attention_gather` are their parity references and the path
for shapes the kernels cannot tile (the tiny CPU widths). `interpret=True`
(selected when this process's backend is not a TPU) runs the kernels on the
CPU for tests.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import NEG_INF, _auto_interpret, _sublanes, largest_divisor

KERNEL_NAME = "paged_latent_attention_decode"
PREFILL_KERNEL_NAME = "paged_latent_attention_prefill"
# Latent rows of one VMEM block (double-buffered): 1 024 x 640 lanes of bfloat16
# are 1.3 MB, and a block's scores for 1 024 query rows 4 MB of float32. Swept on
# the v5e at 128 heads (tools/paged_attention_bench.py --latent, PERF.md section 6,
# PR 50): 256 / 512 / 1 024 / 2 048 rows a block read 37 / 49 / 58 / 62 % of the
# decode kernel's roofline at 24k positions, and the prefill kernel peaks at 1 024.
BLOCK_TOKENS = 1024
# Positions of a chunk one grid step holds, each n_heads rows of q: every
# block of latent rows is read once a grid step. Swept the same way: 2 / 4 / 8 /
# 16 positions do 71 / 78 / 82 / 82 % of the MXU's peak at 24k positions.
PREFILL_POSITIONS = 8


def row_width(kv_lora_rank: int, rope_dim: int) -> int:
    """Lanes of one cached position's row: `[c_kv | k_r]` padded to whole 128-lane tiles."""
    return -(-(kv_lora_rank + rope_dim) // 128) * 128


def can_tile(page_tokens: int, n_heads: int, kv_lora_rank: int, dtype) -> bool:
    """Whether the kernels can tile a latent pool: the value part whole
    128-lane tiles, a page and a position's heads whole sublane tiles of the
    pool's dtype (the tiny CPU widths are neither)."""
    sub = _sublanes(dtype)
    return kv_lora_rank % 128 == 0 and page_tokens % sub == 0 and n_heads % sub == 0


def latent_attention_gather(q, pages, block_tables, lengths, *, scale: float, v_width: int):
    """The plain XLA expression of decode's latent attention: gathers every
    slot's WHOLE block table out of one layer's pages [pages, page_tokens,
    row_width], casts it to float32 and softmaxes the `P * T`-wide row under
    the length mask. q [B, n_heads, row_width] (absorbed queries), lengths [B]
    (>= 1) -> [B, n_heads, v_width] in q's dtype."""
    B, _, W = q.shape
    P, T = block_tables.shape[1], pages.shape[1]
    rows = pages[block_tables].reshape(B, P * T, W).astype(jnp.float32)
    scores = jnp.einsum("bhw,bsw->bhs", q.astype(jnp.float32), rows) * scale
    live = jnp.arange(P * T)[None, :] < lengths[:, None]
    attn = jax.nn.softmax(jnp.where(live[:, None, :], scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhs,bsc->bhc", attn, rows[..., :v_width]).astype(q.dtype)


def latent_prefill_attention_gather(q, pages, block_table, start, *, scale: float, v_width: int):
    """The plain XLA expression of a prefill chunk's latent attention: row i
    of q [C, n_heads, row_width] is position `start + i` and attends causally
    over the whole table [P] of one layer's pages -> [C, n_heads, v_width]."""
    C, _, W = q.shape
    P, T = block_table.shape[0], pages.shape[1]
    rows = pages[block_table].reshape(P * T, W).astype(jnp.float32)
    scores = jnp.einsum("qhw,sw->hqs", q.astype(jnp.float32), rows) * scale
    seen = jnp.arange(P * T)[None, :] <= start + jnp.arange(C)[:, None]
    attn = jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqs,sc->qhc", attn, rows[:, :v_width]).astype(q.dtype)


def _kernel(
    layer_ref, first_ref, length_ref, tables_ref,  # scalar prefetch (SMEM)
    q_ref, pool_hbm,  # [rows, W] VMEM; [L, N, T, W] HBM
    o_ref,  # [rows, v_width]
    buf, sems, m_scr, l_scr, acc_scr,
    *, scale, n_heads, v_width, page_tokens, pages_per_block, max_pages, table_a_block,
):
    """One block of query rows: `rows // n_heads` positions from `first_ref[g]`
    on, n_heads rows each, over positions [0, min(its last position + 1,
    length_ref[g])) of the pages its table names."""
    g = pl.program_id(0)
    rows = q_ref.shape[0]
    T, ppb = page_tokens, pages_per_block
    bk = ppb * T
    layer = layer_ref[0]
    q_first = first_ref[g]
    # Indices are clamped as an XLA gather clamps them: a length or a page
    # index out of range must not become a DMA outside the pool.
    length = jnp.minimum(length_ref[g], max_pages * T)
    # A block wholly past the prompt, or an inactive slot (length 0), sees nothing and returns zeros.
    kv_end = jnp.where((q_first >= 0) & (q_first < length), jnp.minimum(q_first + rows // n_heads, length), 0)
    n_pages = (kv_end + T - 1) // T
    n_blocks = (n_pages + ppb - 1) // ppb
    last_page = pool_hbm.shape[1] - 1
    table = g * max_pages if table_a_block else 0

    def copies(blk, slot, act):
        """Starts or awaits the DMAs of block `blk`'s live pages."""
        def page_copy(j, _):
            pg = blk * ppb + j

            @pl.when(pg < n_pages)
            def _():
                page = jnp.clip(tables_ref[table + pg], 0, last_page)
                act(pltpu.make_async_copy(pool_hbm.at[layer, page], buf.at[slot, pl.ds(pl.multiple_of(j * T, T), T)], sems.at[slot]))

        lax.fori_loop(0, ppb, page_copy, None)

    copies(0, 0, lambda c: c.start())
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    q = q_ref[:]
    exact = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    def body(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copies(blk + 1, 1 - slot, lambda c: c.start())

        copies(blk, slot, lambda c: c.wait())
        first = blk * bk

        # Only the last block holds rows past kv_end (stale VMEM or the rest of
        # a page). p is 0 there, but 0 * NaN is NaN: zero them (key and value
        # are one row; a zeroed key's score is selected away below).
        @pl.when(first + bk > kv_end)
        def _():
            rows_ = buf[slot]
            live = first + lax.broadcasted_iota(jnp.int32, rows_.shape, 0) < kv_end
            buf[slot] = jnp.where(live, rows_, jnp.zeros_like(rows_))

        kv = buf[slot]
        s = lax.dot_general(q, kv, (((1,), (1,)), ((), ())), precision=exact, preferred_element_type=jnp.float32) * scale  # [rows, bk]
        q_pos = q_first + lax.broadcasted_iota(jnp.int32, s.shape, 0) // n_heads
        k_pos = first + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where((k_pos <= q_pos) & (k_pos < kv_end), s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p.astype(kv.dtype), kv[:, :v_width], (((1,), (0,)), ((), ())), precision=exact, preferred_element_type=jnp.float32)
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    lax.fori_loop(0, n_blocks, body, None)
    o_ref[:] = (acc_scr[:] / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def _call(q2, pool, layer, firsts, lengths, tables, *, rows, name, scale, n_heads, v_width, max_pages, table_a_block, pages_per_block, interpret):
    """q2 [G * rows, W]: G blocks of `rows` query rows, block g's first
    position firsts[g], its keys bounded by lengths[g], its pages named by
    `tables` (flat: row g of [G, max_pages], or the one table [max_pages])."""
    _, _, T, W = pool.shape
    G = q2.shape[0] // rows
    if pages_per_block is None:
        pages_per_block = max(1, min(max_pages, BLOCK_TOKENS // T))
    if interpret is None:
        interpret = _auto_interpret()
    bk = pages_per_block * T
    item = jnp.dtype(pool.dtype).itemsize
    # q and o blocks double-buffered by the pipeline, the latent rows by hand, the running max / sum / accumulator,
    # and a block's scores and probabilities in flight.
    vmem = 2 * rows * (W + v_width) * item + 2 * bk * W * item + rows * (2 * 128 + v_width) * 4 + 3 * rows * bk * 4
    kern = functools.partial(
        _kernel, scale=scale, n_heads=n_heads, v_width=v_width, page_tokens=T, pages_per_block=pages_per_block,
        max_pages=max_pages, table_a_block=table_a_block,
    )
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1), firsts.astype(jnp.int32), lengths.astype(jnp.int32), tables.astype(jnp.int32).reshape(-1)]
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(G,),
            in_specs=[pl.BlockSpec((rows, W), lambda g, *_: (g, 0)), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((rows, v_width), lambda g, *_: (g, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, W), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, v_width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((G * rows, v_width), q2.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=min(100 << 20, max(32 << 20, vmem * 2))),
        interpret=interpret,
        name=name,
    )(*scalars, q2.astype(pool.dtype), pool)


def _check(q, pool, v_width: int, what: str):
    H, W = q.shape[-2], q.shape[-1]
    T = pool.shape[2]
    if pool.shape[3] != W or v_width > W:
        raise ValueError(f"latent pool rows of {pool.shape[3]} lanes against queries of {W} (value part {v_width})")
    if not can_tile(T, H, v_width, pool.dtype) or W % 128:
        raise ValueError(f"{what} cannot tile a value part of {v_width}, {H} heads, page_tokens {T}, {pool.dtype}: use its gather expression")


def paged_latent_attention(
    q: jax.Array, pool: jax.Array, layer: jax.Array, block_tables: jax.Array, lengths: jax.Array,
    *, scale: float, v_width: int, pages_per_block: Optional[int] = None, interpret: Optional[bool] = None,
) -> jax.Array:
    """Latent attention of one new token per slot over the slot's live pages.

    q [B, n_heads, row_width], the absorbed queries; pool [layers, pages,
    page_tokens, row_width], read at `layer` (int32 scalar) and never copied;
    block_tables [B, P] int32; lengths [B] int32: positions [0, lengths[b])
    are attended and 0 means an inactive slot (output zeros). Returns
    [B, n_heads, v_width] in q's dtype: each head's output in latent space.
    """
    B, H, W = q.shape
    _check(q, pool, v_width, "paged latent attention")
    out = _call(
        q.reshape(B * H, W), pool, layer, lengths - 1, lengths, block_tables, rows=H, name=KERNEL_NAME, scale=scale, n_heads=H,
        v_width=v_width, max_pages=block_tables.shape[1], table_a_block=True, pages_per_block=pages_per_block, interpret=interpret,
    )
    return out.reshape(B, H, v_width)


def paged_latent_prefill_attention(
    q: jax.Array, pool: jax.Array, layer: jax.Array, block_table: jax.Array, start: jax.Array, length: jax.Array,
    *, scale: float, v_width: int, positions_per_block: Optional[int] = None, pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal latent attention of one chunk of one prompt over the prompt's pages.

    q [C, n_heads, row_width]: row i is the prompt's position `start + i`
    (int32 scalar); pool as `paged_latent_attention` takes it; block_table [P]
    int32, page j holds positions [j * page_tokens, (j + 1) * page_tokens),
    the chunk's own included; `length` the prompt's length: no position past
    it is read, and blocks of rows wholly past it return zeros. Row i attends
    over positions [0, start + i]. Returns [C, n_heads, v_width] in q's dtype.
    """
    C, H, W = q.shape
    _check(q, pool, v_width, "paged latent prefill attention")
    R = largest_divisor(C, positions_per_block or PREFILL_POSITIONS)
    G = C // R
    firsts = jnp.asarray(start, jnp.int32) + jnp.arange(G, dtype=jnp.int32) * R
    out = _call(
        q.reshape(C * H, W), pool, layer, firsts, jnp.full((G,), length, jnp.int32), block_table, rows=R * H,
        name=PREFILL_KERNEL_NAME, scale=scale, n_heads=H, v_width=v_width, max_pages=block_table.shape[0], table_a_block=False,
        pages_per_block=pages_per_block, interpret=interpret,
    )
    return out.reshape(C, H, v_width)
