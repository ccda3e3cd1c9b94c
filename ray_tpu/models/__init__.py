"""First-class TPU-native model implementations (net-new vs the reference,
which delegates models to torch user code — SURVEY.md §2d/§6)."""

from . import mlp, transformer
from .transformer import (
    TransformerConfig,
    flops_per_token,
    forward,
    gpt_j_6b,
    init_params,
    llama2_7b,
    llama2_13b,
    next_token_loss,
    param_count,
    tiny,
)

__all__ = [
    "mlp", "transformer", "TransformerConfig", "flops_per_token", "forward",
    "gpt_j_6b", "init_params", "llama2_7b", "llama2_13b", "next_token_loss",
    "param_count", "tiny",
]
