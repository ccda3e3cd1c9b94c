"""Architecture `olmoe`: OLMoE-1B-7B (allenai), everything the benchmark
knows about it, in one file that a configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config),
                   routed_experts(params, tokens, config)
    the counts     train_flops_per_token, decode_step_min_bytes, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

The block, as the published model computes it (HF `modeling_olmoe.py`):
pre-norm RMSNorm; `q = RMSNorm_q(h Wq)`, `k = RMSNorm_k(h Wk)`, each norm over
the WHOLE projected vector (before the split into heads, own learned scale,
`rms_norm_eps`), `v = h Wv`; rotate-half rope; causal multi-head attention;
`Wo`. Then `p = softmax(h Wr)` over all experts, the `num_experts_per_tok`
largest `p` and their experts, renormalised only if `norm_topk_prob`, and
`out = sum_j p_j W_down[e_j](silu(W_gate[e_j] h) * W_up[e_j] h)`. No bias, no
shared expert, untied head. The loss is next-token cross-entropy alone.

The plain reference: jax.numpy, float32, matmul precision "highest", no
kernels, no cache, no batching, no sort, no grouping: one sequence at a time,
one layer's weights upcast at a time and within it one expert at a time
(64 experts of one layer are 1.6 GB in float32). It shares no code with
ray_tpu/models/transformer.py and reads only the layout of the weights
(stacked layers, [in, out] matrices, experts stacked on the axis after the
layer's). Departures from the published computation, all of them:

- HF computes in the checkpoint's bfloat16; this is float32 throughout (it is
  the yardstick, not the deployment).
- HF gathers the tokens routed to an expert (`torch.where` + `index_add_`),
  whose shapes depend on the data. Here every expert is applied to every
  token and its result weighted by the router's weight for that (token,
  expert), which is exactly zero where the expert is not among the token's
  top k: the same sum at static shapes, at `num_experts / num_experts_per_tok`
  times the expert FLOPs.
- The pre-training auxiliary losses (load balancing, router z-loss) are not
  computed: HF leaves them out too unless `output_router_logits` is set.
- `clip_qkv`, `attention_bias` and `rope_scaling` are read only to refuse a
  value that switches them on (the published value of each is off).

The counts are the operations and bytes the algorithm needs, from shapes
alone: a routed FFN is counted by the experts a token PASSES THROUGH, never by
the experts that exist.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..lib import flops

# ------------------------------------------------------------- the mapping

# What this block does not compute, read only to refuse a value that switches it on: that is another architecture.
MUST_BE_OFF = ("attention_bias", "clip_qkv", "rope_scaling")
# Published keys this architecture gives a meaning to.
PUBLISHED_KEYS = frozenset(MUST_BE_OFF) | {
    "hidden_size", "intermediate_size", "num_attention_heads", "num_key_value_heads", "num_hidden_layers",
    "vocab_size", "max_position_embeddings", "rope_theta", "rms_norm_eps", "hidden_act", "tie_word_embeddings",
    "num_experts", "num_experts_per_tok", "norm_topk_prob", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 32,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "num_hidden_layers": 2,
    "vocab_size": 256,
    "max_position_embeddings": 256,
    "num_experts": 8,
    "num_experts_per_tok": 2,
}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k in MUST_BE_OFF:
        if config.get(k):
            raise ValueError(f"olmoe does not compute {k}={config[k]!r}")
    if config.get("hidden_act", "silu") != "silu":
        raise ValueError("only gated-silu experts are mapped")
    return {
        "d": int(config["hidden_size"]),
        "f": int(config["intermediate_size"]),  # ONE expert's width
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["hidden_size"]) // int(config["num_attention_heads"]),
        "L": int(config["num_hidden_layers"]),
        "V": int(config["vocab_size"]),
        "E": int(config["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "renorm": bool(config.get("norm_topk_prob", False)),
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
    assumed = {k: v["value"] for k, v in config.get("assumed", {}).items()}
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"],
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=m["renorm"], qk_norm=True,
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=m["theta"],
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


def _router_weights(hn, router, m: Dict):
    """hn [s, d] -> (weights [s, E]: the router's probability where the expert
    is among the token's k most probable, exactly 0 elsewhere; experts [s, k])."""
    probs = jax.nn.softmax(hn @ router.astype(F32), axis=-1)
    top_p, top_e = jax.lax.top_k(probs, m["k"])
    if m["renorm"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    chosen = jax.nn.one_hot(top_e, m["E"], dtype=F32)  # [s, k, E]
    return jnp.sum(chosen * top_p[..., None], axis=1), top_e


def _experts(hn, weights, mlp):
    """sum over experts e of weights[:, e] * W_down[e](silu(hn W_gate[e]) * (hn W_up[e])),
    one expert upcast at a time; `mlp` holds one layer's expert weights as stored.
    An expert's term is a `jax.checkpoint`: the same numbers, and where the
    reference is differentiated no expert's activations or upcast weights are
    kept for the backward pass (64 experts' would be 7 GB a layer)."""

    @jax.checkpoint
    def term(hn, w_gate, w_up, w_down, w_e):
        return w_e[:, None] * ((jax.nn.silu(hn @ w_gate.astype(F32)) * (hn @ w_up.astype(F32))) @ w_down.astype(F32))

    def add_expert(acc, xs):
        return acc + term(hn, *xs), None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(hn), (mlp["w_gate"], mlp["w_up"], mlp["w_down"], weights.T))
    return acc


def _layer(x, w, m: Dict):
    """One block on x [s, d] -> (x, experts [s, k]); `w` is the layer's weights as stored, upcast where used."""
    a = jax.tree_util.tree_map(lambda t: t.astype(F32), w["attn"])
    hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
    s = hn.shape[0]
    q = _rms_norm(hn @ a["wq"], a["q_norm"]["scale"], m["eps"])  # the whole projection, then heads
    k = _rms_norm(hn @ a["wk"], a["k_norm"]["scale"], m["eps"])
    q = _rope(q.reshape(s, m["h"], m["hd"]), m["theta"])
    k = _rope(k.reshape(s, m["kv"], m["hd"]), m["theta"])
    v = (hn @ a["wv"]).reshape(s, m["kv"], m["hd"])
    x = x + _attention(q, k, v) @ a["wo"]
    hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
    weights, top_e = _router_weights(hn, w["mlp"]["router"], m)
    return x + _experts(hn, weights, w["mlp"]), top_e


def _layers(params, tokens, m: Dict):
    """tokens [s] int32 -> (final-norm hidden states [s, d], experts [L, s, k]), float32.
    Each layer is a `jax.checkpoint`: the same numbers, and where the
    reference is differentiated (lib/correct.training_reference) only one
    layer's scores and expert activations are alive in the backward pass."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        chosen = []
        for layer in range(m["L"]):
            x, top_e = jax.checkpoint(lambda x, w: _layer(x, w, m))(x, jax.tree_util.tree_map(lambda a: a[layer], params["blocks"]))
            chosen.append(top_e)
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"]), jnp.stack(chosen)


def hidden_states(params, tokens, m: Dict):
    return _layers(params, tokens, m)[0]


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


def routed_experts(params, tokens, config: Dict[str, Any]):
    """The experts the reference routes each token of ONE sequence to, most
    probable first: [L, s, k]. A parity test asserts that the program chose
    the same, so that what differs is arithmetic and not a flipped choice."""
    return _layers(params, tokens, dims(config))[1]


# -------------------------------------------------------------- the counts


def expert_params(config: Dict[str, Any]) -> int:
    """ONE expert's three matrices."""
    m = dims(config)
    return 3 * m["d"] * m["f"]


def shared_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters every token passes through whatever its routing:
    attention projections and router of every layer, and the output head
    (the embedding is a gather)."""
    m = dims(config)
    per_layer = 2 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"] + m["d"] * m["E"]
    return m["L"] * per_layer + m["d"] * m["V"]


def active_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters ONE token passes through: the shared ones and
    `num_experts_per_tok` of the `num_experts` experts a layer."""
    m = dims(config)
    return shared_matmul_params(config) + m["L"] * m["k"] * expert_params(config)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a
    token passes through (active experts only), plus causal attention (QK^T
    and PV: 2 matmuls x 2 FLOPs x seq/2 visible positions x d per layer
    forward, x3 with the backward)."""
    m = dims(config)
    attn = 12 * m["L"] * m["h"] * m["hd"] * (seq_len / 2)
    return 6.0 * active_matmul_params(config) + attn


# The grouped expert matmuls of a train step, named by what a device trace can tell of a call: its result's shape.
GROUPED_MATMULS = ("rows_x_f", "rows_x_d", "dw_gate_up", "dw_down")


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call of each kernel this architecture
    runs in a train step at [batch, seq_len]: the three Mosaic flash kernels,
    and the grouped expert matmuls over rows = batch x seq_len x
    num_experts_per_tok (token, expert) pairs. A call is known by its result:
    `rows_x_f` [rows, f] is gate's or up's forward product and the down
    projection's product for its rows' gradient; `rows_x_d` [rows, d] the down
    projection's forward and gate's or up's rows' gradient; `dw_gate_up`
    [E, d, f] and `dw_down` [E, f, d] the products for the weights'
    gradients. Each is 2 x rows x d x f FLOPs, whatever the routing, and
    moves the same three tensors once, in the parameters' type."""
    m = dims(config)
    out = dict(flops.flash_kernels(m["h"], m["kv"], m["hd"], batch, seq_len))
    rows, b = batch * seq_len * m["k"], m["bytes_per_param"]
    need_flops = 2.0 * rows * m["d"] * m["f"]
    need_bytes = float(b * (rows * m["d"] + m["E"] * m["d"] * m["f"] + rows * m["f"]))
    for kind in GROUPED_MATMULS:
        out[kind] = (need_flops, need_bytes)
    return out


def kv_bytes_per_token(config: Dict[str, Any]) -> float:
    """K and V of one cached position, all layers."""
    m = dims(config)
    return float(2 * m["L"] * m["kv"] * m["hd"] * m["bytes_per_param"])


def experts_touched(config: Dict[str, Any], live_seqs: int) -> float:
    """Experts of one layer that a batch of `live_seqs` tokens is expected
    to touch when each token takes k of E uniformly: E (1 - (1 - k/E)^live_seqs)."""
    m = dims(config)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** live_seqs)


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must read: the shared weights once, the experts
    the batch touches (not all of them: at one live sequence an eighth), and
    the live K/V of the sequences in the batch."""
    m = dims(config)
    weights = shared_matmul_params(config) + m["L"] * experts_touched(config, live_seqs) * expert_params(config)
    return float(weights * m["bytes_per_param"]) + kv_bytes_per_token(config) * kv_tokens
