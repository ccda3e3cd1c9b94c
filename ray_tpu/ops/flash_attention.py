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
- causal blocks entirely above the diagonal are skipped (predicated out) —
  ~2x FLOP saving at long sequence;
- backward = two kernels (dq; dk/dv) recomputing probabilities from the
  saved logsumexp, the standard flash-backward decomposition;
- `interpret=True` (selected when this process's backend is not a TPU) runs
  the same kernels on CPU for tests; the multi-chip ring/Ulysses paths compose on top of this per-shard
  kernel via shard_map.
"""

from __future__ import annotations

import functools
import os as _os
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tile sizes are tunable per chip generation (VMEM budget vs pipelining):
# RAY_TPU_FLASH_BLOCK_Q / RAY_TPU_FLASH_BLOCK_K override the defaults.
# 1024/1024 came from a v5e sweep that predates PR 1 (not re-measured on
# today's code; 2048-wide k blocks overflowed VMEM); shorter sequences take
# the largest dividing tile automatically (_pick_block).
DEFAULT_BLOCK = int(_os.environ.get("RAY_TPU_FLASH_BLOCK_Q", 1024))
DEFAULT_BLOCK_K = int(_os.environ.get("RAY_TPU_FLASH_BLOCK_K", 1024))
NEG_INF = -1e30


def _dot(a, b, contract=((1,), (0,))):
    return lax.dot_general(
        a, b, dimension_numbers=(contract, ((), ())), preferred_element_type=jnp.float32
    )


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k, num_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    # Last k block this q block attends to (causal) — also where we emit.
    last_k = jnp.minimum(num_k - 1, (q_start + block_q - 1) // block_k) if causal else num_k - 1

    @pl.when(ik <= last_k)
    def _():
        q = q_ref[0]  # [bq, d]
        k = k_ref[0]  # [bk, d]
        s = _dot(q, k, contract=((1,), (1,))) * scale  # [bq, bk] fp32
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_prev = m_scr[:, 0]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_scr[:, 0] * alpha + jnp.sum(p, axis=1)
        acc = acc_scr[:] * alpha[:, None] + _dot(p.astype(v_ref.dtype), v_ref[0])
        m_scr[:] = jnp.broadcast_to(m_new[:, None], m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new[:, None], l_scr.shape)
        acc_scr[:] = acc

    @pl.when(ik == (last_k if causal else num_k - 1))
    def _():
        l = jnp.maximum(l_scr[:, 0], 1e-30)
        o_ref[0] = (acc_scr[:] / l[:, None]).astype(o_ref.dtype)
        lse_ref[0] = (m_scr[:, 0] + jnp.log(l))[:, None].astype(lse_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dq_scr, *, scale, causal, block_q, block_k, num_k):
    iq, ik = pl.program_id(1), pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_start = iq * block_q
    k_start = ik * block_k
    last_k = jnp.minimum(num_k - 1, (q_start + block_q - 1) // block_k) if causal else num_k - 1

    @pl.when(ik <= last_k)
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        s = _dot(q, k, contract=((1,), (1,))) * scale
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # lse_ref[0]: [bq, 1] broadcasts
        dp = _dot(do_ref[0], v, contract=((1,), (1,)))  # [bq, bk]
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] += _dot(ds.astype(k.dtype), k)

    @pl.when(ik == (last_k if causal else num_k - 1))
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal, block_q, block_k, num_q, rep):
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

    q_start = iq * block_q
    k_start = ik * block_k
    # First q block at/below the diagonal for this k block.
    not_skipped = (q_start + block_q - 1) >= k_start if causal else True

    @pl.when(not_skipped)
    def _():
        q, k, v = q_ref[0], k_ref[0], v_ref[0]
        do = do_ref[0]
        s = _dot(q, k, contract=((1,), (1,))) * scale
        if causal:
            q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse_ref[0])  # lse_ref[0]: [bq, 1] broadcasts
        dv_scr[:] += _dot(p.astype(do.dtype), do, contract=((0,), (0,)))  # [bk, d]
        dp = _dot(do, v, contract=((1,), (1,)))
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] += _dot(ds.astype(q.dtype), q, contract=((0,), (0,)))

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


def _pick_blocks(s: int, block_q: int, block_k: int, interpret: bool):
    """(bq, bk) tiles for sequence length s, or None when s cannot be tiled
    AND the kernel is interpreted (CPU tests take the unfused reference for
    tiny shards). On the compiled TPU path an untileable shape raises: a
    run that expected the fused kernel must not silently get the reference."""
    bq, bk = _pick_block(s, block_q), _pick_block(s, block_k)
    if bq is not None and bk is not None:
        return bq, bk
    if not interpret:
        raise ValueError(
            f"flash attention cannot tile sequence length {s} (needs a "
            f"multiple of 8; blocks q={block_q} k={block_k}); pad the "
            "sequence or use attn_impl='naive'"
        )
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, causal, scale, block_q, block_k, interpret, heads):
    o, _ = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, heads)
    return o


def _kv_index(h: int, h_kv: int):
    """Maps the q-side grid index bh = batch*h + head to the kv-side row
    batch*h_kv + head // rep — GQA head sharing resolved by the BlockSpec
    index map, so repeated K/V never materialize."""
    rep = h // h_kv

    def f(b, i, j):
        return ((b // h) * h_kv + (b % h) // rep, j, 0)

    return f


def _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, heads):
    h, h_kv = heads
    bh, s, d = q.shape
    nq, nk = s // block_q, s // block_k
    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, num_k=nk
    )
    kv_map = _kv_index(h, h_kv)
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
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, 1), jnp.float32),
        ],
        scratch_shapes=[
            _scratch((block_q, 128), jnp.float32),
            _scratch((block_q, 128), jnp.float32),
            _scratch((block_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


def _scratch(shape, dtype):
    return pltpu.VMEM(shape, dtype)  # the interpreter accepts VMEM scratch too


def _flash_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret, heads):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, heads)
    # Named for remat policies: saving o+lse (~16 MB/layer at bench shapes)
    # lets jax.checkpoint skip re-running the forward kernel during the
    # backward pass — the bwd kernels need only q,k,v (cheap projection
    # recompute), do, lse, delta. See TransformerConfig.remat_policy="attn".
    from jax.ad_checkpoint import checkpoint_name

    o = checkpoint_name(o, "flash_o")
    lse = checkpoint_name(lse, "flash_lse")
    return o, (q, k, v, o, lse)


def _flash_vjp_bwd(causal, scale, block_q, block_k, interpret, heads, res, do):
    q, k, v, o, lse = res
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)  # [bh, s, 1]
    return _flash_bwd_impl(
        causal, scale, block_q, block_k, interpret, heads, q, k, v, o, lse, do, delta
    )


def _flash_bwd_impl(causal, scale, block_q, block_k, interpret, heads, q, k, v, o, lse, do, delta):
    h, h_kv = heads
    rep = h // h_kv
    bh, s, d = q.shape
    bh_kv = k.shape[0]
    nq, nk = s // block_q, s // block_k
    kv_map = _kv_index(h, h_kv)

    dq = pl.pallas_call(
        functools.partial(
            _dq_kernel, scale=scale, causal=causal, block_q=block_q, block_k=block_k, num_k=nk
        ),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[_scratch((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    # dk/dv walk the kv-side batch axis; the q/do/lse/delta index maps fan
    # the rep query heads sharing each kv head through the inner grid axis.
    def q_map(b, j, t):
        return ((b // h_kv) * h + (b % h_kv) * rep + t // nq, t % nq, 0)

    def k_map(b, j, t):
        return (b, j, 0)

    dk, dv = pl.pallas_call(
        functools.partial(
            _dkv_kernel, scale=scale, causal=causal, block_q=block_q,
            block_k=block_k, num_q=nq, rep=rep,
        ),
        grid=(bh_kv, nk, rep * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_k, d), k_map),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
            pl.BlockSpec((1, block_q, 1), q_map),
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
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_lse(q, k, v, causal, scale, block_q, block_k, interpret, heads):
    """Flash attention that also RETURNS the per-row logsumexp — the
    primitive ring attention composes across K/V blocks (partial outputs
    merge by lse weighting). Gradient flows through BOTH outputs: an
    upstream dlse folds into the delta term (ds = p*(dp - delta + dlse)),
    so the same backward kernels serve."""
    return _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, heads)


def _flash_lse_vjp_fwd(q, k, v, causal, scale, block_q, block_k, interpret, heads):
    o, lse = _flash_fwd_impl(q, k, v, causal, scale, block_q, block_k, interpret, heads)
    return (o, lse), (q, k, v, o, lse)


def _flash_lse_vjp_bwd(causal, scale, block_q, block_k, interpret, heads, res, g):
    q, k, v, o, lse = res
    do, dlse = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)
    if dlse is not None:
        delta = delta - dlse.astype(jnp.float32)
    return _flash_bwd_impl(
        causal, scale, block_q, block_k, interpret, heads, q, k, v, o, lse, do, delta
    )


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
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK_K,
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
    blocks = _pick_blocks(s, block_q, block_k, interpret)
    if blocks is None:
        if h_kv != h:
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return reference_attention_with_lse(q, k, v, causal=causal, scale=scale)
    bq, bk = blocks

    def to_bh(x):
        hh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, d)

    o, lse = _flash_lse(
        to_bh(q), to_bh(k), to_bh(v), causal, scale, bq, bk, interpret, (h, h_kv)
    )
    return (
        o.reshape(b, h, s, d).transpose(0, 2, 1, 3),
        lse.reshape(b, h, s),
    )


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    scale: Optional[float] = None,
    block_q: int = DEFAULT_BLOCK,
    block_k: int = DEFAULT_BLOCK_K,
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
    blocks = _pick_blocks(s, block_q, block_k, interpret)
    if blocks is None:
        from ..parallel.ring_attention import attention_reference

        if h_kv != h:  # the unfused path wants expanded kv heads
            k = jnp.repeat(k, h // h_kv, axis=2)
            v = jnp.repeat(v, h // h_kv, axis=2)
        return attention_reference(q, k, v, causal=causal, scale=scale)
    bq, bk = blocks

    def to_bh(x):
        hh = x.shape[2]
        return x.transpose(0, 2, 1, 3).reshape(b * hh, s, d)

    o = _flash(to_bh(q), to_bh(k), to_bh(v), causal, scale, bq, bk, interpret, (h, h_kv))
    return o.reshape(b, h, s, d).transpose(0, 2, 1, 3)
