"""Paged attention as pallas TPU kernels: one new token a slot (decode), and a
chunk of a prompt's rows over the prompt's own pages (prefill).

One decode step attends one new token per slot over that slot's K/V, which
lies scattered over the page pool (models/transformer.py init_kv_pages). The
plain XLA expression (transformer.paged_attention_gather, this kernel's
parity reference and the path for shapes it cannot tile) gathers the WHOLE
block table of every slot out of the pool, casts it to float32 and softmaxes
a `P * T`-wide row whatever the slots hold. This kernel moves what is live:

- the pool stays in HBM as it lies, `[layers, pages, page_tokens,
  n_kv_heads * head_dim]`; `block_tables`, the live `lengths` and the layer
  index are scalar-prefetched, and slot `b` walks only its
  `ceil(lengths[b] / page_tokens)` pages, `pages_per_block` at a time: one
  DMA per page (a page is contiguous) into a double-buffered VMEM block, in
  the pool's own dtype. A slot with length 0 walks nothing and returns zeros;
- a page lands as `[page_tokens, n_kv_heads * head_dim]`: tokens on sublanes,
  (head, dim) on lanes. All heads of a block go through the MXU at once as
  `Q_bd [n_heads, n_kv_heads * head_dim] x K^T`, where row h of `Q_bd` holds
  q_h in the lanes of its KV head and zeros elsewhere: the head -> KV head
  index of GQA is that mask, K/V are never repeated. Every K/V tile passes
  the MXU once, which is what a per-head product costs too (the MXU is bound
  by loading K/V tiles, not by the rows of q), and the scores come out dense
  `[n_heads, block]` instead of one sublane a head;
- the same mathematics as the reference: K and V are read in the dtype they
  are stored in, the q.K products accumulate in float32, running max, sum
  and the output accumulator are float32, and the probabilities are rounded
  to V's dtype for the P.V product, as ops/flash_attention.py does and no
  lower. The read is bounded by the live length: scores past it are selected
  away and V rows past it are zeroed before the product, so what lies in the
  rest of a page (or in the trash page) never reaches the output;
- ONE executable serves every batch mix and length: lengths and tables are
  data, the page walk is a loop with a dynamic trip count.

`paged_prefill_attention` is its many-row sibling for forward_prefill's chunk
loop: the rows of one chunk of ONE prompt, at positions `start + i`, attend
causally over the pages the prompt's block table names, which hold the
chunk's own K/V (written just before) and everything below it, computed by an
earlier chunk or by whoever owns a shared prefix. The same page walk, DMAs
and mathematics; what differs is that many rows share every K/V block, so
each head multiplies its own `[rows, head_dim] x [head_dim, block]` (the
block-diagonal trick would cost n_kv_heads times the FLOPs once the rows,
not the K/V tiles, bound the MXU), and the mask is causal. Its parity
reference is transformer.paged_prefill_attention_gather.

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

NEG_INF = -1e30
KERNEL_NAME = "paged_attention_decode"
# Bytes of one VMEM block of K (V has its own, both double-buffered): large
# enough that the per-block softmax and accumulator update are small beside
# the DMA, small enough for four of them in the default scoped VMEM.
BLOCK_BYTES = 1 << 19
PREFILL_KERNEL_NAME = "paged_attention_prefill"
# Prefill: rows of q one grid step holds (every K/V block is read once per
# q block), the tokens of one VMEM block of K, all KV heads wide (fewer
# where that would pass PREFILL_BLOCK_BYTES: a float32 pool), and the heads
# unrolled in one turn of the loop over heads. The per-head running max, sum
# and accumulator are rescaled once a block, which costs as much as the
# block's own products at 64 tokens; past 256 a head's [rows, tokens] scores
# no longer fit the registers. Unrolled heads overlap (one's products under
# another's softmax) but each costs ~0.1 s of tracing in every bucket of
# every process that serves. On the chip, DeepSeek MHA (PERF.md, PR 31;
# tools/paged_attention_bench.py --prefill): 512 rows over 4 096 tokens,
# all heads unrolled, 64 / 128 / 256 / 512 tokens a block 1 483 / 784 / 451
# / 542 us; 256 rows, 256 tokens a block, 1 / 2 / 4 / 8 / 32 heads unrolled
# 634 / 572 / 445 / 329 / 242 us against 0.4 / - / 0.7 / 1.0 / 4.2 s for a
# bucket's first call with a warm compile cache (the parent's: 0.4).
PREFILL_BLOCK_Q = 256
PREFILL_BLOCK_BYTES = 1 << 21
PREFILL_BLOCK_TOKENS = 256
PREFILL_HEADS_UNROLLED = 4


def _auto_interpret() -> bool:
    """True off-TPU: the flash kernels' rule, asked of their module at call
    time (the package exports the function `flash_attention` under the
    module's name), so that whoever steers it for an ahead-of-time compile
    steers every kernel at once."""
    return importlib.import_module("ray_tpu.ops.flash_attention")._auto_interpret()


def _sublanes(dtype) -> int:
    """Rows of one VMEM tile: 8 for 4-byte types, 16 for 2-byte ones."""
    return 32 // jnp.dtype(dtype).itemsize


def can_tile(page_tokens: int, head_dim: int, dtype, v_head_dim: Optional[int] = None) -> bool:
    """Whether the kernel can tile a pool: a head must fill whole 128-lane
    tiles (a K head `head_dim` lanes, a V head `v_head_dim` where that is
    another width) and a page whole sublane tiles of the pool's dtype, or a
    page's DMA and a head's slice would cut through a tile (the tiny CPU
    widths: head_dim 16, 8-token pages)."""
    return head_dim % 128 == 0 and (v_head_dim or head_dim) % 128 == 0 and page_tokens % _sublanes(dtype) == 0


def pick_pages_per_block(page_tokens: int, row_width: int, max_pages: int, dtype) -> int:
    """Pages one VMEM block of K (or V) holds: BLOCK_BYTES of them."""
    page_bytes = page_tokens * row_width * jnp.dtype(dtype).itemsize
    return max(1, min(max_pages, BLOCK_BYTES // page_bytes))


def _kernel(*refs, windowed: bool, **static):
    """The decode kernel's two signatures: with a window, its scalar is the
    last of the scalar-prefetch operands."""
    (layer_ref, lengths_ref, tables_ref), rest = refs[:3], refs[3:]
    window_ref, rest = (rest[0], rest[1:]) if windowed else (None, rest)
    _decode_kernel(layer_ref, lengths_ref, tables_ref, window_ref, *rest, **static)


def _decode_kernel(
    layer_ref, lengths_ref, tables_ref, window_ref,  # scalar prefetch (SMEM); window_ref None: no window
    q_ref, k_hbm, v_hbm,  # [1, H, hd] VMEM; [L, N, T, F] HBM, twice (V's rows n_kv_heads * hv wide)
    o_ref,  # [1, H, hv]
    k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
    *, scale, n_kv_heads, page_tokens, pages_per_block, max_pages,
):
    b = pl.program_id(0)
    H, hd, hv = q_ref.shape[1], q_ref.shape[2], o_ref.shape[2]
    rep = H // n_kv_heads
    F = n_kv_heads * hd
    T, ppb = page_tokens, pages_per_block
    bk = ppb * T
    layer = layer_ref[0]
    # Indices are clamped as an XLA gather clamps them: a length or a page
    # index out of range must not become a DMA outside the pool.
    length = jnp.minimum(lengths_ref[b], max_pages * T)
    n_pages = (length + T - 1) // T
    # Under a window the walk starts at the page that holds the first visible
    # key, `seen_from`: the pages below it are neither copied nor multiplied.
    if window_ref is None:
        seen_from = first_page = None
        n_blocks = (n_pages + ppb - 1) // ppb
    else:
        seen_from = jnp.maximum(length - window_ref[0], 0)
        first_page = seen_from // T
        n_blocks = (n_pages - first_page + ppb - 1) // ppb
    last_page = k_hbm.shape[1] - 1

    def copies(blk, slot, act):
        """Starts or awaits the DMAs of block `blk`'s live pages."""
        for j in range(ppb):
            pg = blk * ppb + j if first_page is None else first_page + blk * ppb + j

            @pl.when(pg < n_pages)
            def _():
                page = jnp.clip(tables_ref[b * max_pages + pg], 0, last_page)
                rows = pl.ds(j * T, T)
                for pool, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    act(pltpu.make_async_copy(pool.at[layer, page], buf.at[slot, rows], sems.at[s, slot]))

    copies(0, 0, lambda c: c.start())
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    # Row h of q_bd: q_h in the lanes of KV head h // rep, zeros elsewhere.
    q = q_ref[0]
    q_tiled = q if n_kv_heads == 1 else jnp.concatenate([q] * n_kv_heads, axis=1)
    row_kv = lax.broadcasted_iota(jnp.int32, (H, F), 0) // rep
    lane_kv = lax.broadcasted_iota(jnp.int32, (H, F), 1) // hd
    q_bd = jnp.where(row_kv == lane_kv, q_tiled, jnp.zeros_like(q_tiled))
    exact = lax.Precision.HIGHEST if q.dtype == jnp.float32 else None

    def body(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copies(blk + 1, 1 - slot, lambda c: c.start())

        copies(blk, slot, lambda c: c.wait())
        first = blk * bk if first_page is None else first_page * T + blk * bk

        # Only a slot's last block holds rows past its length (stale VMEM or
        # the rest of a page). p is 0 there, but 0 * NaN is NaN: zero V.
        @pl.when(first + bk > length)
        def _():
            v = v_buf[slot]
            live = first + lax.broadcasted_iota(jnp.int32, v.shape, 0) < length
            v_buf[slot] = jnp.where(live, v, jnp.zeros_like(v))

        s = lax.dot_general(
            q_bd, k_buf[slot], (((1,), (1,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32,
        ) * scale  # [H, bk]
        tok = first + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(tok < length if seen_from is None else (tok < length) & (tok >= seen_from), s, NEG_INF)
        m_prev = m_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v_buf.dtype), v_buf[slot], (((1,), (0,)), ((), ())),
            precision=exact, preferred_element_type=jnp.float32,
        )  # [H, n_kv_heads * hv]; row h is wanted in the lanes of its KV head only
        acc_scr[:] = acc_scr[:] * alpha + pv
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    lax.fori_loop(0, n_blocks, body, None)

    out_kv = lax.broadcasted_iota(jnp.int32, (H, hv), 0) // rep
    out = jnp.zeros((H, hv), jnp.float32)
    for g in range(n_kv_heads):
        out = jnp.where(out_kv == g, acc_scr[:, g * hv:(g + 1) * hv], out)
    o_ref[0] = (out / jnp.maximum(l_scr[:, :1], 1e-30)).astype(o_ref.dtype)


def paged_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
    block_tables: jax.Array,
    lengths: jax.Array,
    *,
    n_kv_heads: int,
    window: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    pages_per_block: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Attention of one new token per slot over the slot's live pages.

    q [B, n_heads, head_dim]; k_pages / v_pages the pool
    [layers, pages, page_tokens, n_kv_heads * head_dim], read at `layer`
    (int32 scalar) and never copied; v_pages' heads may be of another width
    than k_pages' (the result's), and `scale` another factor of the scores than
    head_dim^-0.5 (heads padded to whole lane tiles);
    block_tables [B, P] int32 page indices;
    lengths [B] int32, positions [0, lengths[b]) are attended and 0 means an
    inactive slot (output zeros). `window` (int32 scalar, may be traced; None:
    no window, the kernel as it was): only positions [lengths[b] - window,
    lengths[b]) are attended, and the pages wholly below them are not read.
    Returns [B, n_heads, v_pages' head width] in q's dtype.
    """
    B, H, hd = q.shape
    _, _, T, F = k_pages.shape
    P = block_tables.shape[1]
    Fv = v_pages.shape[3]
    hv = Fv // n_kv_heads
    if F != n_kv_heads * hd or H % n_kv_heads or Fv % n_kv_heads:
        raise ValueError(f"pool width {F} is not n_kv_heads {n_kv_heads} x head_dim {hd} (n_heads {H}; V's {Fv})")
    if not can_tile(T, hd, k_pages.dtype, hv):
        raise ValueError(
            f"paged attention cannot tile head_dim {hd}, page_tokens {T}, {k_pages.dtype}: "
            "use transformer.paged_attention_gather"
        )
    if pages_per_block is None:
        pages_per_block = pick_pages_per_block(T, F, P, k_pages.dtype)
    if interpret is None:
        interpret = _auto_interpret()
    bk = pages_per_block * T
    kern = functools.partial(
        _kernel, windowed=window is not None, scale=1.0 / math.sqrt(hd) if scale is None else scale, n_kv_heads=n_kv_heads, page_tokens=T,
        pages_per_block=pages_per_block, max_pages=P,
    )
    scalars = [jnp.asarray(layer, jnp.int32).reshape(1), lengths.astype(jnp.int32), block_tables.astype(jnp.int32).reshape(-1)]
    if window is not None:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))
    return pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(B,),
            in_specs=[
                pl.BlockSpec((1, H, hd), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, H, hv), lambda b, *_: (b, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, F), k_pages.dtype),
                pltpu.VMEM((2, bk, Fv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, 128), jnp.float32),
                pltpu.VMEM((H, Fv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, H, hv), q.dtype),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=KERNEL_NAME,
    )(*scalars, q.astype(k_pages.dtype), k_pages, v_pages)


def largest_divisor(n: int, limit: int) -> int:
    """The largest divisor of n that is at most limit (at least 1)."""
    return max(d for d in range(1, max(1, min(n, limit)) + 1) if n % d == 0)


def pick_prefill_blocks(chunk_tokens: int, page_tokens: int, row_width: int, max_pages: int, dtype):
    """(rows of q a grid step, pages a VMEM block of K) for a chunk: whole
    pages both, the first a divisor of the chunk."""
    block_q = page_tokens * largest_divisor(chunk_tokens // page_tokens, max(1, PREFILL_BLOCK_Q // page_tokens))
    tokens = min(PREFILL_BLOCK_TOKENS, PREFILL_BLOCK_BYTES // (row_width * jnp.dtype(dtype).itemsize))
    return block_q, max(1, min(max_pages, tokens // page_tokens))


def _prefill_kernel(*refs, windowed: bool, **static):
    """The prefill kernel's two signatures, as `_kernel`."""
    head, rest = refs[:4], refs[4:]
    window_ref, rest = (rest[0], rest[1:]) if windowed else (None, rest)
    _prefill_chunk_kernel(*head, window_ref, *rest, **static)


def _prefill_chunk_kernel(
    layer_ref, start_ref, length_ref, table_ref, window_ref,  # scalar prefetch (SMEM); window_ref None: no window
    q_ref, k_hbm, v_hbm,  # [bq, H * hd] VMEM; [L, N, T, F] HBM, twice (V's rows n_kv_heads * hv wide)
    o_ref,  # [bq, H * hv]
    k_buf, v_buf, sems, m_scr, l_scr, acc_scr,
    *, scale, n_heads, n_kv_heads, page_tokens, pages_per_block, max_pages, heads_unrolled,
):
    bq = q_ref.shape[0]
    hd, hv = q_ref.shape[1] // n_heads, o_ref.shape[1] // n_heads
    rep = n_heads // n_kv_heads
    T, ppb = page_tokens, pages_per_block
    bk = ppb * T
    layer = layer_ref[0]
    q_first = start_ref[0] + pl.program_id(0) * bq
    # Keys these rows may see: up to the last row's own position (causal),
    # and no page past the prompt's last (what the table names there is the
    # trash page or someone else's). Rows wholly past the prompt see nothing.
    length = jnp.minimum(length_ref[0], max_pages * T)
    kv_end = jnp.minimum(q_first + bq, (length + T - 1) // T * T)
    kv_end = jnp.where(q_first < length, kv_end, 0)
    n_pages = (kv_end + T - 1) // T
    # Under a window the walk starts at the page that holds the first key the
    # block's FIRST row sees; each row masks what lies below its own reach.
    if window_ref is None:
        window = first_page = None
        n_blocks = (n_pages + ppb - 1) // ppb
    else:
        window = window_ref[0]
        first_page = jnp.maximum(q_first - window + 1, 0) // T
        n_blocks = jnp.maximum(n_pages - first_page + ppb - 1, 0) // ppb
    last_page = k_hbm.shape[1] - 1

    def copies(blk, slot, act):
        """Starts or awaits the DMAs of block `blk`'s live pages: a loop,
        where the decode kernel unrolls its few pages a block (16 and more
        `pl.when`s, three times over, were most of the seconds it took to
        trace this kernel, once a bucket in every process that serves)."""
        def page_copy(j, _):
            pg = blk * ppb + j if first_page is None else first_page + blk * ppb + j

            @pl.when(pg < n_pages)
            def _():
                page = jnp.clip(table_ref[pg], 0, last_page)
                rows = pl.ds(pl.multiple_of(j * T, T), T)
                for pool, buf, s in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                    act(pltpu.make_async_copy(pool.at[layer, page], buf.at[slot, rows], sems.at[s, slot]))

        lax.fori_loop(0, ppb, page_copy, None)

    copies(0, 0, lambda c: c.start())
    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)
    exact = lax.Precision.HIGHEST if q_ref.dtype == jnp.float32 else None

    @jax.jit  # traced once, inlined for each unrolled head: tracing it per head is most of a bucket's first call
    def softmax_step(q, k, v, seen, m, l, acc):
        """One head's online-softmax update over one K/V block: q [bq, hd],
        k / v [bk, hd], running max and sum [bq, 128] (every lane the same),
        accumulator [bq, hd]."""
        s = lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), precision=exact, preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]
        s = jnp.where(seen, s, NEG_INF)
        m_prev = m[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())), precision=exact, preferred_element_type=jnp.float32
        )  # [bq, hd]
        return jnp.broadcast_to(m_new, m.shape), jnp.broadcast_to(l_new, l.shape), acc * alpha + pv

    def body(blk, _):
        slot = blk % 2

        @pl.when(blk + 1 < n_blocks)
        def _():
            copies(blk + 1, 1 - slot, lambda c: c.start())

        copies(blk, slot, lambda c: c.wait())
        first = blk * bk if first_page is None else first_page * T + blk * bk

        # Rows of the last block past kv_end are stale VMEM: p is 0 there,
        # but 0 * NaN is NaN, so V is zeroed (as the decode kernel does).
        @pl.when(first + bk > kv_end)
        def _():
            v = v_buf[slot]
            live = first + lax.broadcasted_iota(jnp.int32, v.shape, 0) < kv_end
            v_buf[slot] = jnp.where(live, v, jnp.zeros_like(v))

        q_pos = q_first + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = first + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        seen = (k_pos <= q_pos) & (k_pos < kv_end)
        if window is not None:
            # A row whose keys in this block are all out of reach adds
            # garbage at weight exp(NEG_INF - NEG_INF) = 1; the first block
            # that holds a key it sees rescales that by exp(NEG_INF - max) = 0.
            seen &= k_pos > q_pos - window
        # Heads in a loop, heads_unrolled of them a turn: unrolled, the
        # compiler overlaps one head's products with another's softmax; all
        # of them unrolled are seconds of program load in every process
        # that serves.
        def head(h, _):
            lanes = pl.ds(pl.multiple_of(h * hd, 128), hd)
            kv_lanes = pl.ds(pl.multiple_of(h // rep * hd, 128), hd)
            # where a head's values are of another width than its keys: their lanes in v_buf and in the accumulator
            v_lanes = kv_lanes if hv == hd else pl.ds(pl.multiple_of(h // rep * hv, 128), hv)
            o_lanes = lanes if hv == hd else pl.ds(pl.multiple_of(h * hv, 128), hv)
            m_scr[h], l_scr[h], acc_scr[:, o_lanes] = softmax_step(
                q_ref[:, lanes], k_buf[slot, :, kv_lanes], v_buf[slot, :, v_lanes], seen,
                m_scr[h], l_scr[h], acc_scr[:, o_lanes],
            )

        def heads(i, _):
            for j in range(heads_unrolled):
                head(i * heads_unrolled + j, None)

        lax.fori_loop(0, n_heads // heads_unrolled, heads, None)

    lax.fori_loop(0, n_blocks, body, None)

    def finish(h, _):
        lanes = pl.ds(pl.multiple_of(h * hv, 128), hv)
        o_ref[:, lanes] = (acc_scr[:, lanes] / jnp.maximum(l_scr[h, :, :1], 1e-30)).astype(o_ref.dtype)

    lax.fori_loop(0, n_heads, finish, None)


def paged_prefill_attention(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
    block_table: jax.Array,
    start: jax.Array,
    length: jax.Array,
    *,
    n_kv_heads: int,
    window: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    pages_per_block: Optional[int] = None,
    heads_unrolled: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Causal attention of one chunk of one prompt over the prompt's pages.

    q [C, n_heads, head_dim]: row i is the prompt's position `start + i`
    (int32 scalar); k_pages / v_pages the pool [layers, pages, page_tokens,
    n_kv_heads * head_dim], read at `layer` and never copied; block_table
    [P] int32, page j holds positions [j * page_tokens, (j + 1) *
    page_tokens), the chunk's own included; `length` the prompt's length:
    no page past its last is read, and blocks of rows wholly past it return
    zeros. Row i attends over positions [0, start + i], under `window` (int32
    scalar, may be traced; None: no window, the kernel as it was) over the
    last `window` of them, and the pages wholly below the reach of a block's
    first row are not read. v_pages' heads and `scale` as paged_attention
    takes them. Returns [C, n_heads, v_pages' head width] in q's dtype.
    """
    C, H, hd = q.shape
    _, _, T, F = k_pages.shape
    P = block_table.shape[0]
    Fv = v_pages.shape[3]
    hv = Fv // n_kv_heads
    if F != n_kv_heads * hd or H % n_kv_heads or Fv % n_kv_heads:
        raise ValueError(f"pool width {F} is not n_kv_heads {n_kv_heads} x head_dim {hd} (n_heads {H}; V's {Fv})")
    if not can_tile(T, hd, k_pages.dtype, hv) or C % T:
        raise ValueError(
            f"paged prefill attention cannot tile head_dim {hd}, page_tokens {T}, chunk {C}, {k_pages.dtype}: "
            "use transformer.paged_prefill_attention_gather"
        )
    bq, ppb = pick_prefill_blocks(C, T, F, P, k_pages.dtype)
    block_q = block_q or bq
    pages_per_block = pages_per_block or ppb
    if C % block_q:
        raise ValueError(f"block_q {block_q} does not divide the chunk's {C} rows")
    if interpret is None:
        interpret = _auto_interpret()
    bk = pages_per_block * T
    item = jnp.dtype(k_pages.dtype).itemsize
    # q and o blocks double-buffered by the pipeline, K and V by hand, the
    # running max / sum / accumulator, and the scores of one head in flight.
    vmem = 2 * block_q * H * (hd + hv) * item + 2 * bk * (F + Fv) * item + block_q * H * (2 * 128 + hv) * 4 + 4 * block_q * bk * 4
    scalars = [jnp.asarray(x, jnp.int32).reshape(1) for x in (layer, start, length)] + [block_table.astype(jnp.int32)]
    if window is not None:
        scalars.append(jnp.asarray(window, jnp.int32).reshape(1))
    kern = functools.partial(
        _prefill_kernel, windowed=window is not None, scale=1.0 / math.sqrt(hd) if scale is None else scale, n_heads=H, n_kv_heads=n_kv_heads, page_tokens=T,
        pages_per_block=pages_per_block, max_pages=P,
        heads_unrolled=largest_divisor(H, heads_unrolled or PREFILL_HEADS_UNROLLED),
    )
    out = pl.pallas_call(
        kern,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(C // block_q,),
            in_specs=[
                pl.BlockSpec((block_q, H * hd), lambda i, *_: (i, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((block_q, H * hv), lambda i, *_: (i, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, bk, F), k_pages.dtype),
                pltpu.VMEM((2, bk, Fv), v_pages.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((H, block_q, 128), jnp.float32),
                pltpu.VMEM((H, block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, H * hv), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((C, H * hv), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",), vmem_limit_bytes=min(100 << 20, max(32 << 20, vmem * 3 // 2))
        ),
        interpret=interpret,
        name=PREFILL_KERNEL_NAME,
    )(*scalars, q.astype(k_pages.dtype).reshape(C, H * hd), k_pages, v_pages)
    return out.reshape(C, H, hv)
