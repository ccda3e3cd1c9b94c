"""Fused TPU kernels (pallas), and moe_rows: row gathers whose backward is
written by hand. The hot single-chip ops live here; the model layer picks
them up via config (models/transformer.py attn_impl, n_experts)."""

from .flash_attention import flash_attention

__all__ = ["flash_attention"]
