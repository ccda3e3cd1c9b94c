"""Architecture `gptj_block`, as a later PR would add it: NOT part of the
benchmark. tests/test_run.py copies this file to archs/ of a temporary copy
of the benchmark to prove that a new architecture comes with new files only.

GPT-J's block (EleutherAI/gpt-j-6b config.json and modeling_gptj.py): ONE
LayerNorm per block, attention and an ungated tanh-gelu MLP both computed
from it and both added to the residual; rotary embeddings on the first
`rotary_dim` dims of each head, on interleaved (even, odd) pairs; multi-head
attention; untied head. Departure, as in the program: no biases (the
published model has them in the MLP, the norms and the head).
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..lib import flops

PUBLISHED_KEYS = frozenset({
    "n_embd", "n_head", "n_layer", "n_inner", "n_positions", "rotary_dim", "vocab_size",
    "layer_norm_epsilon", "activation_function", "tie_word_embeddings", "torch_dtype",
})

TINY = {"n_embd": 64, "n_head": 4, "n_layer": 2, "n_inner": 128, "n_positions": 256, "rotary_dim": 8, "vocab_size": 256}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    if config["activation_function"] != "gelu_new":
        raise ValueError("only the tanh gelu is mapped")
    d, h = int(config["n_embd"]), int(config["n_head"])
    return {
        "d": d, "h": h, "hd": d // h, "f": int(config["n_inner"] or 4 * d), "L": int(config["n_layer"]),
        "V": int(config["vocab_size"]), "rd": int(config["rotary_dim"]), "eps": float(config["layer_norm_epsilon"]),
        "bytes_per_param": {"bfloat16": 2, "float32": 4}[config.get("torch_dtype", "bfloat16")],
    }


def vocab_size(config: Dict[str, Any]) -> int:
    return int(config["vocab_size"])


def model_config(config: Dict[str, Any], **overrides):
    from ray_tpu.models import transformer as tfm

    m = dims(config)
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["h"], d_ff=m["f"],
        max_seq_len=int(config["n_positions"]), rope_theta=10000.0, norm_eps=m["eps"],
        tie_embeddings=bool(config.get("tie_word_embeddings", False)),
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        mlp_act="gelu", parallel_block=True, norm_type="layer", rotary_dim=m["rd"], rope_style="interleaved",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32


def _layer_norm(x, scale, eps):
    x = x - jnp.mean(x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x**3)))


def _rope(x, rd):
    """x [s, heads, hd]: pairs (2i, 2i+1) of the first rd dims turn by pos * 10000^(-2i/rd)."""
    s = x.shape[0]
    inv = 10000.0 ** (-jnp.arange(0, rd, 2, dtype=F32) / rd)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    even, odd = x[..., 0:rd:2], x[..., 1:rd:2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1).reshape(*x.shape[:-1], rd)
    return jnp.concatenate([turned, x[..., rd:]], axis=-1)


def _attention(q, k, v):
    s, h, hd = q.shape
    scores = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(F32(hd))
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", probs, v).reshape(s, h * hd)


def hidden_states(params, tokens, m: Dict):
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        for layer in range(m["L"]):
            w = jax.tree_util.tree_map(lambda a: a[layer].astype(F32), params["blocks"])
            hn = _layer_norm(x, w["attn_norm"]["scale"], m["eps"])
            s = hn.shape[0]
            q = _rope((hn @ w["attn"]["wq"]).reshape(s, m["h"], m["hd"]), m["rd"])
            k = _rope((hn @ w["attn"]["wk"]).reshape(s, m["h"], m["hd"]), m["rd"])
            v = (hn @ w["attn"]["wv"]).reshape(s, m["h"], m["hd"])
            attn = _attention(q, k, v) @ w["attn"]["wo"]
            x = x + attn + _gelu_new(hn @ w["mlp"]["w_up"]) @ w["mlp"]["w_down"]  # the parallel block
        return _layer_norm(x, params["final_norm"]["scale"], m["eps"])


def _head(params):
    head = params.get("lm_head")
    return params["embed"]["embedding"].T if head is None else head


def sequence_nll(params, tokens, config: Dict[str, Any]):
    with jax.default_matmul_precision("highest"):
        logits = hidden_states(params, tokens, dims(config))[:-1] @ _head(params).astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def logits_at(params, tokens, positions, config: Dict[str, Any]):
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, tokens, dims(config))[positions] @ _head(params).astype(F32)


# -------------------------------------------------------------- the counts


def matmul_params(config: Dict[str, Any]) -> int:
    m = dims(config)
    return m["L"] * (4 * m["d"] * m["d"] + 2 * m["d"] * m["f"]) + m["d"] * m["V"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    m = dims(config)
    return 6.0 * matmul_params(config) + 12 * m["L"] * m["d"] * (seq_len / 2)


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    m = dims(config)
    return flops.flash_kernels(m["h"], m["h"], m["hd"], batch, seq_len)


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    m = dims(config)
    kv_token = 2 * m["L"] * m["d"] * m["bytes_per_param"]
    return float(matmul_params(config) * m["bytes_per_param"] + kv_token * kv_tokens)
