"""Operations and bytes the algorithm needs, from shapes alone.

Kept with the benchmark so that no PR that claims a gain can change how a
utilization or a roofline share is counted. `m` is spec.model_dims(config).
"""

from __future__ import annotations

from typing import Dict


def matmul_params(m: Dict) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the output head (the embedding is a gather)."""
    per_layer = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + 3 * m["d"] * m["f"]
    return m["L"] * per_layer + m["d"] * m["V"]


def train_flops_per_token(m: Dict, seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x matmul parameters, plus
    causal attention (QK^T and PV: 2 matmuls x 2 FLOPs x seq/2 visible
    positions x d per layer forward, x3 with the backward)."""
    attn = 12 * m["L"] * m["h"] * m["hd"] * (seq_len / 2)
    return 6.0 * matmul_params(m) + attn


def flash_kernel_flops(m: Dict, batch: int, seq_len: int) -> Dict[str, float]:
    """FLOPs of ONE call of each Mosaic kernel at [batch, seq, heads, head_dim],
    causal (half of the s x s square). Each s x s x head_dim matmul is
    2 x s^2 x hd FLOPs a head. fwd: S = QK^T, O = PV (2). dq: S again, dP =
    dO V^T, dQ = dS K (3). dkv: S again, dP, dV = P^T dO, dK = dS^T Q (4).
    The recomputed S is part of what a kernel with that output must do."""
    square = 2.0 * batch * m["h"] * seq_len * seq_len * m["hd"] / 2
    return {"fwd": 2 * square, "dq": 3 * square, "dkv": 4 * square}


def flash_kernel_bytes(m: Dict, batch: int, seq_len: int) -> Dict[str, float]:
    """HBM bytes ONE call must move (bf16 tensors once each; lse/delta in f32)."""
    q = 2.0 * batch * seq_len * m["h"] * m["hd"]
    kv = 2.0 * batch * seq_len * m["kv"] * m["hd"]
    vec = 4.0 * batch * seq_len * m["h"]
    return {
        "fwd": q + 2 * kv + q + vec,  # q, k, v -> o, lse
        "dq": q + 2 * kv + q + 2 * vec + q,  # q, k, v, do, lse, delta -> dq
        "dkv": q + 2 * kv + q + 2 * vec + 2 * kv,  # ... -> dk, dv
    }


def weight_bytes_per_decode_step(m: Dict) -> float:
    """Every matmul weight is read once a step, whatever the batch."""
    return float(matmul_params(m) * m["bytes_per_param"])


def kv_bytes_per_token(m: Dict) -> float:
    """K and V of one cached position, all layers."""
    return float(2 * m["L"] * m["kv"] * m["hd"] * m["bytes_per_param"])


def decode_step_min_bytes(m: Dict, live_kv_tokens: int) -> float:
    """What one decode step must read: the weights once and the live K/V of
    the sequences in the batch (not the padded block tables)."""
    return weight_bytes_per_decode_step(m) + kv_bytes_per_token(m) * live_kv_tokens
