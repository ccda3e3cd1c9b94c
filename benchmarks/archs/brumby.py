"""Architecture `brumby`: Manifest AI's Brumby (Brumby-14B-Base, `model_type:
brumby`), everything the benchmark knows about it, in one file that a
configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_min_bytes, decode_state_bytes, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer is Qwen3's block with softmax attention replaced by gated power
retention of degree 2 (Manifest AI, "Scaling Context Requires Rethinking
Attention", arXiv:2507.04239; the `retention` package's `power_retention(Q, K,
V, log_G, deg=2)`). Where each line comes from: [K] a key of the published
`config.json` (the catalog's row `Brumby-14B-Base`); [M] ISSUE 42's statement
of what retention adds, where the keys are silent (degree, the gate's
projection, the scale, the normaliser and its epsilon, q/k-norm, rope). There
is no network here: what [M] says was not re-read from the source by this
file's writer, and the configuration lists it under `assumed.layer_equations`.
d = `head_dim`, r = `num_attention_heads` / `num_key_value_heads`.

1. `h = RMSNorm(x; w_in, rms_norm_eps)`; `q = h Wq` as `num_attention_heads`
   heads of d, `k = h Wk`, `v = h Wv` as `num_key_value_heads` heads, no bias   [K]
   RMSNorm over each head's d dims of q and of k, one scale vector each      [M]
   rope (`rope_theta`, rotate-half, whole head) on q and k                   [K] theta; [M] that it is applied
2. the gate: `c_t = h_t Wg`, one logit a K/V head, no bias; `l_t = log
   sigmoid(c_t)`; `L_t = sum_{u <= t} l_u`                                   [M]
3. query head i reads K/V head i // r: for s <= t, `a_ts = exp(L_t - L_s)
   (q_t . k_s / sqrt(d))^2` (degree 2; the scale inside the power)           [M]
   `y_t = sum_s a_ts v_s / (sum_s a_ts + 1e-6)`                              [M] the normaliser and its epsilon
   no softmax, no mask beyond s <= t, no window                              [K] `use_sliding_window` false
4. `x += concat_i(y^i) Wo`; `x += W_down(silu(W_gate n) * (W_up n))`, n the
   RMSNorm of x; after the last layer RMSNorm and the untied head            [K]

The plain reference: jax.numpy, float32, matmul precision "highest", step 3's
quadratic expression in query blocks: no state, no chunks, no phi, no cache, no
batching, one sequence at a time, the head in slices of the vocabulary. It
shares no code with ray_tpu/models/transformer.py (which serves the recurrent
and the chunked form of the same equations) and reads only the layout of the
weights (stacked layers, [in, out] matrices). `use_sliding_window`,
`sliding_window`, `rope_scaling` and `attention_bias` are read only to refuse a
value that switches on what this file does not compute; `max_window_layers`
says nothing while `use_sliding_window` is false.

The counts are the operations and bytes the algorithm needs, from shapes
alone. A sequence's state is counted at its least: S is symmetric in phi's two
indices, d (d + 1) / 2 unordered pairs x (d + 1) float32 a K/V head, read once
and written once a decode step, whatever layout or code implements it (the
program's layout holds 65 x 128 = 8 320 entries where the pairs are 8 256), so
that no share of a peak over these bytes can pass 100 % by the count.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

# ------------------------------------------------------------- the mapping

# What this block does not compute, read only to refuse a value that switches it on: that is another architecture.
MUST_BE_OFF = ("use_sliding_window", "sliding_window", "rope_scaling", "attention_bias")
PUBLISHED_KEYS = frozenset(MUST_BE_OFF) | {
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim", "num_hidden_layers",
    "max_window_layers", "vocab_size", "max_position_embeddings", "rope_theta", "rms_norm_eps", "hidden_act",
    "tie_word_embeddings", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 128,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_hidden_layers": 2,
    "max_window_layers": 2,
    "vocab_size": 256,
    "max_position_embeddings": 256,
    # A page is a sequence's whole state: one a sequence, as many as slots and the trash page; tests/tiny.json's
    # longest request is 176 + 8 + 64 = 248 positions.
    "assumed": {"page_tokens": {"value": 256}, "max_pages_per_seq": {"value": 1}, "pool_pages": {"value": 5}},
}

DEGREE = 2  # [M]
EPS = 1e-6  # [M] the normaliser's


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k in MUST_BE_OFF:
        if config.get(k):
            raise ValueError(f"brumby does not compute {k}={config[k]!r}")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only the gated-silu MLP is mapped")
    degree = int(config.get("assumed", {}).get("retention_degree", {}).get("value", DEGREE))
    if degree != DEGREE:
        raise ValueError(f"retention of degree {degree} is not computed: {DEGREE} is")
    return {
        "d": int(config["hidden_size"]),
        "f": int(config["intermediate_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
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
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"], d_head=m["hd"],
        d_ff=m["f"], max_seq_len=int(config["max_position_embeddings"]), rope_theta=m["theta"], norm_eps=m["eps"],
        tie_embeddings=m["tied"], qk_norm=True, qk_norm_per_head=True, retention_degree=DEGREE,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward runs the chunked form: the flash kernels are softmax attention.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
Q_BLOCK = 512  # query rows per block of the retention (bounds the scores to [heads, 512, keys])
VOCAB_SLICE = 16384  # most columns of the head upcast at a time (all 151 936 x 5 120 in float32 are 3.1 GB)


def _f32(w):
    return w.astype(F32)


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


def _retention(q, k, v, log_g):
    """q [s, h, hd], k / v [s, kv, hd], log_g [s, kv] -> [s, h * hd]: step 3,
    every pair s <= t by the quadratic expression, in query blocks."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head i reads K/V head i // rep
    L = jnp.repeat(jnp.cumsum(log_g, axis=0), rep, axis=1).T  # [h, s]
    outs = []
    for q0 in range(0, s, Q_BLOCK):
        q1 = min(s, q0 + Q_BLOCK)
        scores = jnp.einsum("qhd,khd->hqk", q[q0:q1], k[:q1]) / jnp.sqrt(F32(hd))  # [M] the scale inside the power
        seen = jnp.arange(q0, q1)[:, None] >= jnp.arange(q1)[None, :]
        decay = jnp.exp(jnp.where(seen[None], L[:, q0:q1, None] - L[:, None, :q1], -jnp.inf))
        a = decay * jnp.square(scores)  # [M] degree 2
        num, den = jnp.einsum("hqk,khd->qhd", a, v[:q1]), jnp.sum(a, axis=-1).T[..., None]
        outs.append(num / (den + EPS))  # [M] the normaliser
    return jnp.concatenate(outs, axis=0).reshape(s, h * hd)


def _layer(x, w, m: Dict):
    """One block on x [s, d]; `w` is the layer's weights as stored, upcast where used."""
    a = w["attn"]
    hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
    s = hn.shape[0]
    q, k = (hn @ _f32(a["wq"])).reshape(s, m["h"], m["hd"]), (hn @ _f32(a["wk"])).reshape(s, m["kv"], m["hd"])
    q, k = _rms_norm(q, a["q_norm"]["scale"], m["eps"]), _rms_norm(k, a["k_norm"]["scale"], m["eps"])  # [M] each head's dims
    q, k = _rope(q, m["theta"]), _rope(k, m["theta"])  # [M]
    v = (hn @ _f32(a["wv"])).reshape(s, m["kv"], m["hd"])
    log_g = jax.nn.log_sigmoid(hn @ _f32(a["wg"]))  # [M] one gate a K/V head, on the layer's normed input
    x = x + _retention(q, k, v, log_g) @ _f32(a["wo"])
    hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
    mlp = w["mlp"]
    return x + (jax.nn.silu(hn @ _f32(mlp["w_gate"])) * (hn @ _f32(mlp["w_up"]))) @ _f32(mlp["w_down"])


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32. Each
    layer is a `jax.checkpoint` (dense_decoder.py says why)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"][tokens])
        for layer in range(m["L"]):
            x = jax.checkpoint(lambda x, w: _layer(x, w, m))(x, jax.tree_util.tree_map(lambda a: a[layer], params["blocks"]))
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"])


def _logits(params, h):
    """h [n, d] float32 -> logits [n, V], the head upcast a slice of the vocabulary at a time."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"]["embedding"].T
    d, V = head.shape
    width = max(w for w in range(1, min(V, VOCAB_SLICE) + 1) if V % w == 0)  # 151 936 = 16 x 9 496
    slices = jnp.moveaxis(head.reshape(d, V // width, width), 1, 0)
    return jnp.moveaxis(jax.lax.map(lambda w: h @ _f32(w), slices), 0, 1).reshape(h.shape[0], V)


def sequence_nll(params, tokens, config: Dict[str, Any]):
    """Mean next-token cross-entropy of ONE sequence (positions 0..s-2)."""
    with jax.default_matmul_precision("highest"):
        logits = _logits(params, hidden_states(params, tokens, dims(config))[:-1])
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def logits_at(params, tokens, positions, config: Dict[str, Any]):
    """Next-token logits [len(positions), V] after each of `positions` of ONE sequence."""
    with jax.default_matmul_precision("highest"):
        return _logits(params, hidden_states(params, tokens, dims(config))[positions])


# -------------------------------------------------------------- the counts


def matmul_params(config: Dict[str, Any]) -> int:
    """Parameters that take part in a matrix multiplication per token: the
    layers' projections, the gate's, and the output head (the embedding is a gather)."""
    m = dims(config)
    per_layer = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + m["d"] * m["kv"] + 3 * m["d"] * m["f"]
    return m["L"] * per_layer + m["d"] * m["V"]


def state_entries(config: Dict[str, Any]) -> int:
    """float32 entries of ONE sequence's state in ONE layer, at the least:
    per K/V head the d (d + 1) / 2 unordered pairs of phi, each with v's d
    entries (S) and one of the normaliser's (z)."""
    m = dims(config)
    return m["kv"] * (m["hd"] * (m["hd"] + 1) // 2) * (m["hd"] + 1)


def decode_state_bytes(config: Dict[str, Any], live_seqs: int) -> float:
    """State one decode step must move: every live row's, every layer's, read once and written once."""
    return float(live_seqs * dims(config)["L"] * 2 * state_entries(config) * 4)


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must move: every weight once and the live rows'
    states in and out. `kv_tokens` changes nothing: a state does not grow."""
    return float(matmul_params(config) * dims(config)["bytes_per_param"]) + decode_state_bytes(config, live_seqs)


def retention_flops_per_token(config: Dict[str, Any], chunk: int = 256) -> float:
    """Forward FLOPs of ONE layer's retention a token in the chunked form: each
    query head reads the state (2 x pairs x d), each K/V head adds to it, and
    the in-chunk pairs (QK^T and AV over half a chunk on average)."""
    m = dims(config)
    pairs = m["hd"] * (m["hd"] + 1) // 2
    return 2.0 * (m["h"] + m["kv"]) * pairs * m["hd"] + 4.0 * m["h"] * m["hd"] * chunk / 2


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x matmul parameters, plus the
    retention of every layer (x3 with the backward)."""
    return 6.0 * matmul_params(config) + 3.0 * dims(config)["L"] * retention_flops_per_token(config, min(256, seq_len))


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call a layer of what retention runs
    when served: `decode_state_update`, a step of `batch` live rows (decay,
    add, and r query heads' read: 3 + 2 r operations an entry of S; the state
    in and out), and `prefill_chunk`, `seq_len` rows of one sequence (the
    chunked form; its state in and out)."""
    m = dims(config)
    r = m["h"] // m["kv"]
    one_state = 2.0 * state_entries(config) * 4
    return {
        "decode_state_update": ((3.0 + 2 * r) * batch * state_entries(config), batch * one_state),
        "prefill_chunk": (seq_len * retention_flops_per_token(config, seq_len), one_state),
    }
