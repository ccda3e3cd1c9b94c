"""Architecture `dense_decoder`: everything the benchmark knows about one
architecture, in one file that a configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_min_bytes, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

`config` is always the configuration file as loaded (published keys). No other
file of the benchmark reads a model key; `lib/spec.py` refuses a configuration
file with a key that is neither in PUBLISHED_KEYS nor one of the harness's own.

The plain reference: jax.numpy, float32, matmul precision "highest", no
kernels, no cache, no batching, one sequence at a time. Written from the
published description (pre-norm RMSNorm, rotary embeddings on rotate-half
pairs, grouped-query causal attention, gated-SiLU MLP, untied head); it
shares no code with ray_tpu/models/transformer.py and reads only the layout
of the weights (stacked layers, [in, out] matrices). Weights are upcast one
layer at a time so that it fits beside the engine. `correct` rests on it:
`sequence_nll`, differentiated, is the reference that trains
(`lib/correct.training_reference`); `logits_at` gives the reference logit of
each served token (`lib/correct.served_margins`).

The counts are the operations and bytes the algorithm needs, from shapes
alone, kept with the benchmark so that no PR that claims a gain can change
how a utilization or a roofline share is counted.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..lib import flops

# ------------------------------------------------------------- the mapping

# What this block does not compute, read only to refuse a value that switches it on: that is another architecture.
MUST_BE_OFF = ("sliding_window", "rope_scaling", "attention_bias", "mlp_bias", "clip_qkv", "attention_dropout")
# Published keys this architecture gives a meaning to.
PUBLISHED_KEYS = frozenset(MUST_BE_OFF) | {
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_hidden_layers", "vocab_size", "max_position_embeddings", "rope_theta", "rms_norm_eps",
    "hidden_act", "tie_word_embeddings", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_hidden_layers": 2,
    "vocab_size": 256,
    "max_position_embeddings": 256,
}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k in MUST_BE_OFF:
        if config.get(k):
            raise ValueError(f"dense_decoder does not compute {k}={config[k]!r}")
    return {
        "d": int(config["hidden_size"]),
        "f": int(config["intermediate_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config.get("head_dim") or config["hidden_size"] // config["num_attention_heads"]),
        "L": int(config["num_hidden_layers"]),
        "V": int(config["vocab_size"]),
        "theta": float(config["rope_theta"]),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
        "bytes_per_param": {"bfloat16": 2, "float32": 4}[config.get("torch_dtype", "bfloat16")],
    }


def vocab_size(config: Dict[str, Any]) -> int:
    """The token ids the traffic may draw."""
    return int(config["vocab_size"])


def model_config(config: Dict[str, Any], **overrides):
    """The program's TransformerConfig for a configuration file (call it
    only in the process that owns the chip)."""
    from ray_tpu.models import transformer as tfm

    m = dims(config)
    if m["hd"] * m["h"] != m["d"]:
        raise ValueError("TransformerConfig derives head_dim as d_model // n_heads")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only the gated-silu MLP is mapped")
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"],
        d_ff=m["f"], max_seq_len=int(config["max_position_embeddings"]), rope_theta=m["theta"],
        norm_eps=m["eps"], tie_embeddings=m["tied"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        attn_impl=assumed.get("attn_impl", "full"),
    )
    if "remat_policy" in assumed:
        kw["remat_policy"] = assumed["remat_policy"]
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
Q_BLOCK = 512  # query rows per block of the causal attention (bounds the s x s scores)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _rope(x, theta):
    """x [s, heads, hd]; rotate-half pairs (i, i + hd/2), angle pos * theta^(-2i/hd)."""
    s, _h, hd = x.shape
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q [s, h, hd], k/v [s, kv, hd] -> [s, h*hd]; causal, in query blocks."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(s, q0 + Q_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) / jnp.sqrt(F32(hd))
        mask = jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :]
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", probs, v[:q1]))
    return jnp.concatenate(outs, axis=0).reshape(s, h * hd)


def _layer(x, w, m: Dict):
    """One pre-norm block on x [s, d]; `w` is the layer's weights as stored, upcast here."""
    w = jax.tree_util.tree_map(lambda a: a.astype(F32), w)
    hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
    s = hn.shape[0]
    q = _rope((hn @ w["attn"]["wq"]).reshape(s, m["h"], m["hd"]), m["theta"])
    k = _rope((hn @ w["attn"]["wk"]).reshape(s, m["kv"], m["hd"]), m["theta"])
    v = (hn @ w["attn"]["wv"]).reshape(s, m["kv"], m["hd"])
    x = x + _attention(q, k, v) @ w["attn"]["wo"]
    hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
    return x + (jax.nn.silu(hn @ w["mlp"]["w_gate"]) * (hn @ w["mlp"]["w_up"])) @ w["mlp"]["w_down"]


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32. Each
    layer is a `jax.checkpoint`: the same numbers, and where the reference
    is differentiated (lib/correct.training_reference) only one layer's
    scores are alive in the backward pass."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        for layer in range(m["L"]):
            x = jax.checkpoint(lambda x, w: _layer(x, w, m))(x, jax.tree_util.tree_map(lambda a: a[layer], params["blocks"]))
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"])


def _head(params):
    head = params.get("lm_head")
    return params["embed"]["embedding"].T if head is None else head


def sequence_nll(params, tokens, config: Dict[str, Any]):
    """Mean next-token cross-entropy of ONE sequence (positions 0..s-2)."""
    with jax.default_matmul_precision("highest"):
        logits = hidden_states(params, tokens, dims(config))[:-1] @ _head(params).astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def logits_at(params, tokens, positions, config: Dict[str, Any]):
    """Next-token logits [len(positions), V] after each of `positions` of ONE sequence."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, tokens, dims(config))[positions] @ _head(params).astype(F32)


# -------------------------------------------------------------- the counts


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections and the output head (the embedding is a gather)."""
    m = dims(config)
    per_layer = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + 3 * m["d"] * m["f"]
    return m["L"] * per_layer + m["d"] * m["V"]


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x matmul parameters, plus
    causal attention (QK^T and PV: 2 matmuls x 2 FLOPs x seq/2 visible
    positions x d per layer forward, x3 with the backward)."""
    m = dims(config)
    attn = 12 * m["L"] * m["h"] * m["hd"] * (seq_len / 2)
    return 6.0 * matmul_params(config) + attn


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call of each kernel this architecture
    runs in a train step at [batch, seq_len]: the three Mosaic flash kernels."""
    m = dims(config)
    return flops.flash_kernels(m["h"], m["kv"], m["hd"], batch, seq_len)


def weight_bytes_per_decode_step(config: Dict[str, Any]) -> float:
    """Every matmul weight is read once a step, whatever the batch."""
    return float(matmul_params(config) * dims(config)["bytes_per_param"])


def kv_bytes_per_token(config: Dict[str, Any]) -> float:
    """K and V of one cached position, all layers."""
    m = dims(config)
    return float(2 * m["L"] * m["kv"] * m["hd"] * m["bytes_per_param"])


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must read: the weights once and the live K/V of
    the sequences in the batch (not the padded block tables). A dense block
    reads every weight whatever `live_seqs` is."""
    return weight_bytes_per_decode_step(config) + kv_bytes_per_token(config) * kv_tokens
