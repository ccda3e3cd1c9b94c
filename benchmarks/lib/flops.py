"""Operations and bytes of the kernels that any attention of shape
[batch, seq, heads, head_dim] shares, from shapes alone. An architecture
file (benchmarks/archs/) calls these from its own table of kernels; what is
specific to one architecture (its matmul parameters, a train step's FLOPs, a
decode step's bytes) is counted there.
"""

from __future__ import annotations

from typing import Dict, Tuple


def flash_kernel_flops(heads: int, head_dim: int, batch: int, seq_len: int) -> Dict[str, float]:
    """FLOPs of ONE call of each Mosaic kernel at [batch, seq, heads, head_dim],
    causal (half of the s x s square). Each s x s x head_dim matmul is
    2 x s^2 x hd FLOPs a head. fwd: S = QK^T, O = PV (2). dq: S again, dP =
    dO V^T, dQ = dS K (3). dkv: S again, dP, dV = P^T dO, dK = dS^T Q (4).
    The recomputed S is part of what a kernel with that output must do."""
    square = 2.0 * batch * heads * seq_len * seq_len * head_dim / 2
    return {"fwd": 2 * square, "dq": 3 * square, "dkv": 4 * square}


def flash_kernel_bytes(heads: int, kv_heads: int, head_dim: int, batch: int, seq_len: int) -> Dict[str, float]:
    """HBM bytes ONE call must move (bf16 tensors once each; lse/delta in f32)."""
    q = 2.0 * batch * seq_len * heads * head_dim
    kv = 2.0 * batch * seq_len * kv_heads * head_dim
    vec = 4.0 * batch * seq_len * heads
    return {
        "fwd": q + 2 * kv + q + vec,  # q, k, v -> o, lse
        "dq": q + 2 * kv + q + 2 * vec + q,  # q, k, v, do, lse, delta -> dq
        "dkv": q + 2 * kv + q + 2 * vec + 2 * kv,  # ... -> dk, dv
    }


def flash_kernels(heads: int, kv_heads: int, head_dim: int, batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of one call of each: an entry of an architecture file's table of kernels."""
    need_f = flash_kernel_flops(heads, head_dim, batch, seq_len)
    need_b = flash_kernel_bytes(heads, kv_heads, head_dim, batch, seq_len)
    return {kind: (need_f[kind], need_b[kind]) for kind in need_f}
