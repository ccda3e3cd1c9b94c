"""Fused (flash) attention as a pallas TPU kernel.

The reference has no attention kernels at all — its training path delegates
model math to torch/DeepSpeed user code (reference:
python/ray/train/torch/train_loop_utils.py:162, release/air_examples/
gptj_deepspeed_finetuning/). A TPU-native framework must own this op: naive
attention materializes the [b, h, s, s] score matrix in HBM, which turns the
attention layers from MXU-bound into HBM-bandwidth-bound and caps whole-model
MFU. This kernel streams K/V blocks through VMEM with an online softmax
(Dao et al., FlashAttention; Rabe & Staats, blockwise attention) so the
score matrix never leaves the chip.

Design notes (TPU-first):
- layout inside the kernels is [batch*heads, seq, head_dim]; the grid walks
  (bh, q_block, k_block) with the k_block axis innermost so the running
  (max, normalizer, accumulator) live in VMEM scratch across the inner loop;
- matmuls use fp32 accumulation (`preferred_element_type`) on the MXU, with
  probabilities cast back to the input dtype for the P@V contraction;
- a causal call does the work of the causal triangle and little more:
  blocks entirely above the diagonal are skipped (predicated out, and their
  index maps name the block already in VMEM, so nothing is fetched for
  them); in square blocks a block on the diagonal is walked in sub-tiles,
  only those at or below the diagonal computed and only those on it
  masked; blocks below the diagonal build no mask (causal_work_ratio says
  how much of the square a call computes);
- a row statistic (running max, normalizer, lse, delta) holds the row's
  value in every lane of its vector registers, in VMEM and in HBM
  ([b*h, s, LANES] float32: what a [b*h, s, 1] array occupies once tiled),
  so no kernel broadcasts a column across lanes;
- backward = two kernels (dq; dk/dv) recomputing probabilities from the
  saved logsumexp, the standard flash-backward decomposition;
- `interpret=True` (selected when this process's backend is not a TPU) runs
  the same kernels on CPU for tests; the multi-chip ring/Ulysses paths compose on top of this per-shard
  kernel via shard_map.
"""

from __future__ import annotations

import functools
import operator
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The widest block a call takes, and the sub-tiles its diagonal blocks are
# walked in; shorter sequences take the largest dividing tile (_pick_blocks).
# From a sweep on a v5e (tools/flash_attention_bench.py, PR 55; ms a layer,
# forward + dq + dk/dv, causal bfloat16 at s = 4096, head_dim 128):
#   q [3*32, s, 128] over 8 kv heads (Mistral): 1024:256 14.26, 1024:512
#   14.78, 1024:128 14.08, 1024 whole 15.95, 512:128 17.48 (the parent's
#   1024 whole, masked everywhere, fetching skipped blocks: 19.57);
#   q [4*16, s, 128] (OLMoE): 9.66, 9.99, 9.54, 10.76, 11.81 (13.28).
# 2048-wide blocks ask 16.2-16.7 MB of the 16 MB of VMEM a kernel may scope
# and are refused. 128-wide sub-tiles read 1 % under 256 alone and were not
# run in a cell; the cells were measured at 1024:256.
BLOCK = 1024
SUB = 256
NEG_INF = -1e30
# The kernels' names in the compiled program and in a device trace.
FWD_KERNEL_NAME = "flash_attention_fwd"
DQ_KERNEL_NAME = "flash_attention_dq"
DKV_KERNEL_NAME = "flash_attention_dkv"
LANES = 128  # a row statistic (running max, normalizer, lse, delta) holds the row's value in every lane of a vector register


def causal_work_ratio(s: int, block_q: int, block_k: int, sub: Optional[int]) -> float:
    """Score elements a causal call computes over the s^2 / 2 it needs, from
    the tiles alone: every block the diagonal touches or that lies below it,
    and of a diagonal block (block_q == block_k) walked in sub-tiles of
    `sub` only the sub-tiles at or below the diagonal. 1.25 at s = 4096 in
    blocks of 1024 computed whole, 1.0625 with sub-tiles of 256."""
    computed = 0
    for iq in range(s // block_q):
        for ik in range(s // block_k):
            if ik * block_k > iq * block_q + block_q - 1:
                continue  # wholly above the diagonal: skipped
            if sub and block_q == block_k and ik == iq:
                n = block_q // sub
                computed += n * (n + 1) // 2 * sub * sub
            else:
                computed += block_q * block_k
    return computed / (s * s / 2)


def _dot(a, b, contract=((1,), (0,))):
    return lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())), preferred_element_type=jnp.float32
    )


def _masked(s, q0=None, k0=None):
    """s at and below the diagonal, NEG_INF above it; the tile's corner is at
    (q0, k0) of the sequence, or on the diagonal itself when none is given."""
    q_pos = lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = lax.broadcasted_iota(jnp.int32, s.shape, 1)
    if q0 is not None:
        q_pos, k_pos = q0 + q_pos, k0 + k_pos
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _masked_on_top(s):
    """A strip of keys under the queries at and below it: its top square lies
    on the diagonal and is the only part a mask touches."""
    c = s.shape[1]
    return _masked(s) if s.shape[0] == c else jnp.concatenate([_masked(s[:c]), s[c:]], axis=0)


def _wide(x, n):
    """x [r, LANES], a row's value in every lane, as [r, n]."""
    if n % LANES == 0:
        return x if n == LANES else jnp.tile(x, (1, n // LANES))
    return x[:, :n] if n < LANES else jnp.broadcast_to(x[:, :1], (x.shape[0], n))


def _cat(xs, axis):
    return xs[0] if len(xs) == 1 else jnp.concatenate(xs, axis=axis)


def _tiles(causal, block_q, block_k, sub, iq, ik, num_k, body):
    """Runs `body(tiles)` on what grid step (iq, ik) has to compute, tiles as
    [(rows, cols, mask or None)] in pl.ds slices of the block, and returns
    the last k block of q block iq (where a q-major kernel emits). A causal
    call in square blocks knows its diagonal blocks statically (ik == iq):
    each is walked a strip of `sub` keys at a time under the queries at and
    below the strip (the long axis is the one the MXU streams past the
    strip's latched keys), masked in the strip's top square only, and the
    blocks below the diagonal take no mask. Any other call computes a block
    as one tile, masked by position where the call is causal."""
    q_start, k_start = iq * block_q, ik * block_k
    whole = (pl.ds(0, block_q), pl.ds(0, block_k))
    last_k = jnp.minimum(num_k - 1, (q_start + block_q - 1) // block_k) if causal else num_k - 1
    if causal and block_q == block_k:
        sub = sub or block_q
        pl.when(ik < iq)(lambda: body([(*whole, None)]))
        pl.when(ik == iq)(lambda: body([(pl.ds(j, block_q - j), pl.ds(j, sub), _masked_on_top) for j in range(0, block_q, sub)]))
    else:
        by_position = (lambda s: _masked(s, q_start, k_start)) if causal else None
        pl.when(ik <= last_k)(lambda: body([(*whole, by_position)]))
    return last_k


def _strips(tiles):
    """The strips of rows that `tiles` (a block's key strips, each under the
    rows from its own down to the block's end) share, shortest tile's rows a
    strip: [(rows, [(tile's number, the strip's rows within that tile)])]."""
    r, end = tiles[-1][0].size, tiles[0][0].start + tiles[0][0].size
    return [
        (pl.ds(lo, r), [(n, slice(lo - t[0].start, lo - t[0].start + r)) for n, t in enumerate(tiles) if t[0].start <= lo])
        for lo in range(tiles[0][0].start, end, r)
    ]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k, num_k, sub):
    """m_scr and l_scr hold a row's running maximum and normalizer in every
    one of their LANES lanes, as lse does: a row statistic is never a column
    to broadcast across lanes."""
    iq, ik = pl.program_id(1), pl.program_id(2)
    d = acc_scr.shape[1]

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    def body(tiles):
        """One online-softmax step of the block's rows over the keys of `tiles`:
        every tile's scores, then one merge a strip of rows over the tiles
        that reach it, then every tile's P.V."""
        ss = []
        for rows, cols, mask in tiles:
            s = _dot(q_ref[0, rows], k_ref[0, cols], contract=((1,), (1,))) * scale  # [r, c] fp32
            ss.append(mask(s) if mask else s)
        strips = _strips(tiles)
        ps, alphas = [[] for _ in tiles], []
        for rows, mine in strips:
            s_mine = [ss[n][cut] for n, cut in mine]
            m_prev = m_scr[rows]
            m_new = jnp.maximum(m_prev, jnp.max(functools.reduce(jnp.maximum, s_mine), axis=1, keepdims=True))
            p = [jnp.exp(s - _wide(m_new, s.shape[1])) for s in s_mine]
            alpha = jnp.exp(m_prev - m_new)
            l_scr[rows] = l_scr[rows] * alpha + jnp.sum(functools.reduce(operator.add, p), axis=1, keepdims=True)
            m_scr[rows] = m_new
            alphas.append(_wide(alpha, d))
            for (n, _), x in zip(mine, p):
                ps[n].append(x.astype(v_ref.dtype))
        pv = [_dot(_cat(p, 0), v_ref[0, cols]) for p, (_, cols, _) in zip(ps, tiles)]
        for (rows, mine), alpha in zip(strips, alphas):
            acc_scr[rows] = functools.reduce(operator.add, [pv[n][cut] for n, cut in mine], acc_scr[rows] * alpha)

    last_k = _tiles(causal, block_q, block_k, sub, iq, ik, num_k, body)

    @pl.when(ik == last_k)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _wide(l, d)).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:] + jnp.log(l)).astype(lse_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale, causal, block_q, block_k, num_k, sub):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    def body(tiles):
        for rows, cols, mask in tiles:
            q, k, v = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols]
            s = _dot(q, k, contract=((1,), (1,))) * scale
            p = jnp.exp((mask(s) if mask else s) - _wide(lse_ref[0, rows], s.shape[1]))
            dp = _dot(do_ref[0, rows], v, contract=((1,), (1,)))  # [r, c]
            ds = p * (dp - _wide(delta_ref[0, rows], s.shape[1])) * scale
            dq_scr[rows] += _dot(ds.astype(k.dtype), k)

    last_k = _tiles(causal, block_q, block_k, sub, iq, ik, num_k, body)

    @pl.when(ik == last_k)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k, num_q, num_k, rep, sub):
    """Grid: (b*h_kv, nk, rep*num_q) — the innermost axis walks every
    (shared-q-head, q-block) pair contributing to this kv head, so GQA's
    sum over the `rep` query heads happens in VMEM scratch instead of
    materializing repeated K/V in HBM."""
    ik, t = pl.program_id(1), pl.program_id(2)
    iq = t % num_q

    @pl.when(t == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    def body(tiles):
        """P and dS a tile at a time (a strip of keys latched, the long run of
        queries under it streamed), then dV and dK a strip of queries at a
        time over the keys at and before it (the strip's dO and q latched,
        the long run of keys streamed)."""
        ps, dss = [], []
        for rows, cols, mask in tiles:
            q, k, v, do = q_ref[0, rows], k_ref[0, cols], v_ref[0, cols], do_ref[0, rows]
            s = _dot(q, k, contract=((1,), (1,))) * scale
            p = jnp.exp((mask(s) if mask else s) - _wide(lse_ref[0, rows], s.shape[1]))
            dp = _dot(do, v, contract=((1,), (1,)))
            ps.append(p)
            dss.append(p * (dp - _wide(delta_ref[0, rows], s.shape[1])) * scale)
        for rows, mine in _strips(tiles):
            cols = pl.ds(tiles[mine[0][0]][1].start, sum(tiles[n][1].size for n, _ in mine))
            q, do = q_ref[0, rows], do_ref[0, rows]
            p, ds = (_cat([x[n][cut] for n, cut in mine], 1) for x in (ps, dss))
            dv_scr[cols] += _dot(p.astype(do.dtype), do, contract=((0,), (0,)))  # [c, d]
            dk_scr[cols] += _dot(ds.astype(q.dtype), q, contract=((0,), (0,)))

    _tiles(causal, block_q, block_k, sub, iq, ik, num_k, body)

    @pl.when(t == rep * num_q - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _auto_interpret() -> bool:
    """True off-TPU: the Pallas interpreter runs the same kernels on the
    CPU backend (tests, virtual meshes). A statement about this process's
    backend only — compiling for a TPU topology from a CPU process must
    pass interpret=False explicitly."""
    return jax.default_backend() != "tpu"


def _pick_block(s: int, want: int) -> Optional[int]:
    """Largest power-of-two tile <= want dividing s; None when s has no
    8-aligned tiling."""
    for b in (want, 512, 256, 128, 64, 32, 16, 8):
        if b <= want and s % b == 0:
            return b
    return None


def _pick_blocks(s: int, head_dim: int, dtype, block_q: Optional[int], block_k: Optional[int], interpret: bool):
    """(bq, bk, sub) tiles for sequence length s, or None when s cannot be
    tiled AND the kernel is interpreted (CPU tests take the unfused reference
    for tiny shards). On the compiled TPU path an untileable shape raises: a
    run that expected the fused kernel must not silently get the reference.
    A block the caller does not give is BLOCK for rows of up to 256 bytes
    (the swept shapes: 128 bfloat16) and half of it for wider rows, whose
    blocks beside the float32 score tiles would pass the 16 MB of VMEM a
    kernel may scope. `sub` is the diagonal blocks' sub-tile: SUB of a BLOCK
    in proportion, never under 128 keys (a score tile's lanes), none where a
    block holds fewer than two: the block is then one masked tile."""
    want = BLOCK if head_dim * jnp.dtype(dtype).itemsize <= 256 else BLOCK // 2
    bq, bk = _pick_block(s, block_q or want), _pick_block(s, block_k or want)
    if bq is not None and bk is not None:
        sub = max(128, bq * SUB // BLOCK)
        return bq, bk, sub if bq == bk and bq >= 2 * sub else None
    if not interpret:
        raise ValueError(
            f"flash attention cannot tile sequence length {s} (needs a "
            f"multiple of 8; blocks q={block_q} k={block_k}); pad the "
            "sequence or use attn_impl='naive'"
        )
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, scale, blocks, interpret, heads):
    o, _ = _flash_fwd_impl(q, k, v, causal, scale, blocks, interpret, heads)
    return o


def _kv_index(h: int, h_kv: int, causal: bool, block_q: int, block_k: int):
    """Maps the q-side grid index bh = batch*h + head to the kv-side row
    batch*h_kv + head // rep — GQA head sharing resolved by the BlockSpec
    index map, so repeated K/V never materialize. A causal step past the q
    block's last k block computes nothing and names that last block again:
    it is in VMEM already, so nothing is fetched for it."""
    rep = h // h_kv

    def f(b, i, j):
        if causal:
            j = jnp.minimum(j, (i * block_q + block_q - 1) // block_k)
        return ((b // h) * h_kv + (b % h) // rep, j, 0)

    return f


def _q_index(h: int, h_kv: int, num_q: int, causal: bool, block_q: int, block_k: int):
    """The dk/dv kernel's q-side map: its inner grid axis t fans the rep query
    heads sharing kv row b out as (head, q block) pairs. A causal step before
    the k block's first q block computes nothing and names that first block,
    the one the head's next computing step wants."""
    rep = h // h_kv

    def f(b, j, t):
        i = t % num_q
        if causal:
            i = jnp.maximum(i, (j * block_k) // block_q)
        return ((b // h_kv) * h + (b % h_kv) * rep + t // num_q, i, 0)

    return f


def _flash_fwd_impl(q, k, v, causal, scale, blocks, interpret, heads):
    h, h_kv = heads
    block_q, block_k, sub = blocks
    bh, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, num_k=nk, sub=sub
    )
    kv_map = _kv_index(h, h_kv, causal, block_q, block_k)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, LANES), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, LANES), jnp.float32),
            _scratch((block_q, LANES), jnp.float32),
            _scratch((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        name=FWD_KERNEL_NAME,
    )(q, k, v)
    return o, lse


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)  # the interpreter accepts VMEM scratch too


def _flash_vjp_fwd(q, k, v, causal, scale, blocks, interpret, heads):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, blocks, interpret, heads)
    # Named for remat policies: saving o+lse (~16 MB/layer at bench shapes)
    # lets jax.checkpoint skip re-running the forward kernel during the
    # backward pass — the bwd kernels need only q,k,v (cheap projection
    # recompute), do, lse, delta. See TransformerConfig.remat_policy="attn".
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _delta(do, o):
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # [bh, s, 1]


def _flash_vjp_bwd(causal, scale, blocks, interpret, heads, res, do):
    q, k, v, o, lse = res
    delta = jnp.broadcast_to(_delta(do, o), lse.shape)
    return _flash_bwd_impl(causal, scale, blocks, interpret, heads, q, k, v, lse, do, delta)


def _flash_bwd_impl(causal, scale, blocks, interpret, heads, q, k, v, lse, do, delta):
    h, h_kv = heads
    block_q, block_k, sub = blocks
    rep = h // h_kv
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    nq, nk = s // block_q, s // block_k
    kv_map = _kv_index(h, h_kv, causal, block_q, block_k)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, num_k=nk, sub=sub
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d), jnp.float32)],
        interpret=interpret,
        name=DQ_KERNEL_NAME,
    )(q, k, v, do, lse, delta)

    # dk/dv walk the kv-side batch axis; the q/do/lse/delta index maps fan
    # the rep query heads sharing each kv head through the inner grid axis.
    q_map = _q_index(h, h_kv, nq, causal, block_q, block_k)

    def k_map(b, j, t):
        return (b, j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, num_q=nq, num_k=nk, rep=rep, sub=sub,
        ),
        grid=(bh_kv, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
            pl.BlockSpec((1, block_q, LANES), q_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_k, d), k_map),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh_kv, s, d), k.dtype),
            jax.ShapeDtypeStruct((bh_kv, s, d), v.dtype),
        ],
        scratch_shapes=[
            _scratch((block_k, d), jnp.float32),
            _scratch((block_k, d), jnp.float32),
        ],
        interpret=interpret,
        name=DKV_KERNEL_NAME,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, scale, blocks, interpret, heads):
    """Flash attention that also RETURNS the per-row logsumexp — the
    primitive ring attention composes across K/V blocks (partial outputs
    merge by lse weighting). Gradient flows through BOTH outputs: an
    upstream dlse folds into the delta term (ds = p*(dp - delta + dlse)),
    so the same backward kernels serve."""
    return _flash_fwd_impl(q, k, v, causal, scale, blocks, interpret, heads)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, blocks, interpret, heads):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, blocks, interpret, heads)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(causal, scale, blocks, interpret, heads, res, g):
    q, k, v, o, lse = res
    do, dlse = g  # dlse over lse's LANES copies of a row's value: the row's cotangent is their sum
    delta = _delta(do, o)
    if dlse is not None:
        delta = delta - jnp.sum(dlse.astype(jnp.float32), axis=-1, keepdims=True)
    return _flash_bwd_impl(causal, scale, blocks, interpret, heads, q, k, v, lse, do, jnp.broadcast_to(delta, lse.shape))


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def reference_attention_with_lse(q, k, v, *, causal: bool, scale: float):
    """Unfused differentiable (o, lse) pair for shapes the kernel cannot
    tile (tiny CPU-test shards). lse: [b, h, s_q]."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        q_pos = lax.broadcasted_iota(jnp.int32, s.shape, 2)
        k_pos = lax.broadcasted_iota(jnp.int32, s.shape, 3)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.maximum(jnp.sum(p, axis=-1), 1e-30)
    lse = m + jnp.log(l)
    o = jnp.einsum(
        "bhqk,bkhd->bqhd", (p / l[..., None]).astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return o.astype(q.dtype), lse


def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Fused attention over [b, s, h, d] returning (out, lse[b, h, s]) —
    the building block for ring attention's cross-shard online softmax."""
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads {h_kv}")
    scale = scale if scale is not None else d**-0.5
    if interpret is None:
        interpret = _auto_interpret()
    blocks = _pick_blocks(s, d, q.dtype, block_q, block_k, interpret)
    if blocks is None:
        if h_kv != h:
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return reference_attention_with_lse(q, k, v, causal=causal, scale=scale)

    def to_bh(x):
        hh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, d)

    o, lse = _flash_lse(to_bh(q), to_bh(k), to_bh(v), causal, scale, blocks, interpret, (h, h_kv))
    return (
        o.reshape(b, h, s, d).transpose(0, 2, 1, 3),
        lse[..., 0].reshape(b, h, s),
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fused attention over [batch, seq, heads, head_dim] inputs.

    Exact (not approximate) attention; O(s) memory per core. `interpret`
    defaults to True off-TPU so the same kernel runs (slowly) on CPU for
    tests; there, shapes the kernel cannot tile take the unfused reference.
    Compiled for a TPU, an untileable shape raises instead.
    """
    b, s, h, d = q.shape
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(f"n_heads {h} not divisible by n_kv_heads {h_kv}")
    scale = scale if scale is not None else d**-0.5
    if interpret is None:
        interpret = _auto_interpret()
    blocks = _pick_blocks(s, d, q.dtype, block_q, block_k, interpret)
    if blocks is None:
        from ..parallel.ring_attention import attention_reference

        if h_kv != h:  # the unfused path wants expanded kv heads
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return attention_reference(q, k, v, causal=causal, scale=scale)

    def to_bh(x):
        hh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, d)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, scale, blocks, interpret, (h, h_kv))
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
