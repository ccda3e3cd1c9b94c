"""Architecture `afmoe`: Arcee Trinity (Trinity-Mini, `model_type: afmoe`),
everything the benchmark knows about it, in one file that a configuration
names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config),
                   routed_experts(params, tokens, config)
    the counts     train_flops_per_token, decode_step_min_bytes, decode_step_bytes, kernels, ...
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer, and where each line of it comes from. [K] a key of the published
`config.json` (the catalog's row `Trinity-Mini`); [M] the published modeling
code of the model type (`modeling_afmoe.py`), as ISSUE 37 states it, where the
keys are silent. There is no network here: what [M] says was not re-read from
the source by this file's writer, and the configuration lists it under
`assumed.layer_equations`.

- `x0 = E[tokens] * sqrt(hidden_size)`                         [K] `mup_enabled`; [M] the factor
- four RMSNorms a layer, each with a scale, `rms_norm_eps`:     [M] the placement; [K] the eps
  `x += post_attn_norm(Attn(input_norm(x)))`, `x += post_mlp_norm(FFN(pre_mlp_norm(x)))`
- `q = h Wq` as `num_attention_heads` heads of `head_dim`, `k`, `v` as
  `num_key_value_heads` heads of it                            [K]
- RMSNorm over each head's `head_dim` dims of q and of k, one scale vector
  each, shared by the heads                                    [M]
- rope (`rope_theta`, rotate-half, whole head) on q and k where
  `layer_types[l]` is `sliding_attention`, none on `full_attention`   [K] the types, theta; [M] which kind is rotated
- scores `q . k / sqrt(head_dim)`; query i sees key j iff `j <= i`, and on a
  sliding layer also `i - j < sliding_window`; softmax in float32      [K] `sliding_window`
- `o = (softmax . v) * sigmoid(h Wg)`, `Wg` hidden x heads*head_dim on the
  layer's normed input; `Attn = o Wo`; no bias anywhere         [M]
- layers below `num_dense_layers`: SwiGLU of width `intermediate_size`   [K]
- the others: `s = sigmoid(h Wr)` over `num_experts` (`score_func`); the
  `num_experts_per_tok` experts with the largest `s + b`, b a per-expert bias
  that only selects; `w_e = route_scale * s_e / (sum of the chosen s + 1e-20)`
  (`route_norm`; s WITHOUT the bias); `FFN = SwiGLU_shared(h) + sum_e w_e
  SwiGLU_e(h)`, each of width `moe_intermediate_size`, `num_shared_experts`
  shared ones as one SwiGLU of their summed width              [K] every size and switch; [M] the bias, the 1e-20
- `n_group = topk_group = num_expert_groups = num_limited_groups = 1`: no
  group-limited selection                                       [K]
- final RMSNorm, `logits = x Whead` (`tie_word_embeddings` false)   [K]

The plain reference: jax.numpy, float32, matmul precision "highest", no
kernels, no cache, no batching, no sort, no grouping: one sequence at a time,
attention in query blocks (8 192 positions fit), one expert upcast at a time
(a routed layer's 128 experts are 3.4 GB in float32; the reference runs in
the replica beside the engine's weights and pool), the head in slices of the
vocabulary. It shares no code with ray_tpu/models/transformer.py and reads
only the layout of the weights (two stacked groups, `dense_blocks` before
`blocks`; [in, out] matrices; experts stacked on the axis after the layer's).
Departures from the published computation, all of them:

- the published code computes in the checkpoint's bfloat16; this is float32
  throughout (it is the yardstick, not the deployment);
- it gathers the tokens routed to an expert; here every expert is applied to
  every token and weighted by the router's weight for that (token, expert),
  exactly zero where the expert is not chosen: the same sum at static shapes;
- a sliding layer's query block multiplies only the keys a row of it can see
  (a slice) and masks inside them: the same numbers as masking the whole row;
- `load_balance_coeff` (the bias's update rate in training) and
  `use_grouped_mm` (which matmul the experts use) change no forward number;
  `rope_scaling`, group-limited selection and another `score_func` or
  `hidden_act` are refused.

The counts are the operations and bytes the algorithm needs, from shapes
alone: a routed FFN is counted by the experts a batch TOUCHES, never by the
experts that exist, and a window layer's K/V by what its window lets a row see.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..lib import flops

# ------------------------------------------------------------- the mapping

# Read only to refuse a value this file does not compute: one group means no group-limited selection.
ONE_GROUP = ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")
# Read, and without effect on a forward pass (see the docstring).
NO_FORWARD_EFFECT = ("load_balance_coeff", "use_grouped_mm")
PUBLISHED_KEYS = frozenset(ONE_GROUP) | frozenset(NO_FORWARD_EFFECT) | {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "num_hidden_layers", "num_dense_layers", "layer_types", "sliding_window", "global_attn_every_n_layers",
    "vocab_size", "max_position_embeddings", "rope_theta", "rope_scaling", "rms_norm_eps", "hidden_act",
    "tie_word_embeddings", "num_experts", "num_experts_per_tok", "num_shared_experts", "score_func", "route_norm",
    "route_scale", "mup_enabled", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "num_hidden_layers": 6,
    "num_dense_layers": 2,
    "layer_types": ["sliding_attention", "sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"],
    "sliding_window": 16,
    "vocab_size": 256,
    "max_position_embeddings": 2048,
    "num_experts": 8,
    "num_experts_per_tok": 2,
    # At these widths six bfloat16 layers with 2 of 8 experts a token resolve a router's near-tie the other way than the
    # reference at one position in ~100 (margins of 0.2-1.6 read, PR 37, CPU), which tests/tiny.json's q99 cannot carry:
    # the rehearsal runs the program in float32 and sees paths, shapes and counters; precision is read on the chip.
    "torch_dtype": "float32",
    # The rehearsal keeps the cell's traffic file but for tests/tiny.json's few keys: a 1 024-token shared prefix and
    # eight turns of history need 2 048 positions a sequence, and pages for four such slots.
    "assumed": {"page_tokens": {"value": 16}, "max_pages_per_seq": {"value": 128}, "pool_pages": {"value": 640}},
}

SLIDING, FULL = "sliding_attention", "full_attention"


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k in ONE_GROUP:
        if int(config.get(k, 1)) != 1:
            raise ValueError(f"afmoe: {k}={config[k]!r} asks for group-limited selection, which this file does not compute")
    if config.get("rope_scaling"):
        raise ValueError(f"afmoe does not compute rope_scaling={config['rope_scaling']!r}")
    if config.get("hidden_act", "silu") != "silu" or config.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("only gated-silu experts under a sigmoid router are mapped")
    L, types = int(config["num_hidden_layers"]), list(config["layer_types"])
    if len(types) != L or set(types) - {SLIDING, FULL}:
        raise ValueError(f"layer_types names {len(types)} layers of kinds {sorted(set(types))} for num_hidden_layers {L}")
    every = config.get("global_attn_every_n_layers")
    if every and any((t == FULL) != ((i + 1) % int(every) == 0) for i, t in enumerate(types)):
        raise ValueError(f"layer_types does not put a full layer at every {every}th place")
    window = int(config["sliding_window"])
    return {
        "d": int(config["hidden_size"]),
        "f_dense": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),  # ONE expert's width
        "f_shared": int(config.get("num_shared_experts", 0)) * int(config["moe_intermediate_size"]),
        "h": int(config["num_attention_heads"]),
        "kv": int(config["num_key_value_heads"]),
        "hd": int(config["head_dim"]),
        "L": L,
        "dense": int(config.get("num_dense_layers", 0)),
        "windows": tuple(window if t == SLIDING else 0 for t in types),  # 0: the layer sees everything
        "rope": tuple(t == SLIDING for t in types),
        "V": int(config["vocab_size"]),
        "E": int(config["num_experts"]),
        "k": int(config["num_experts_per_tok"]),
        "renorm": bool(config.get("route_norm", True)),
        "route_scale": float(config.get("route_scale", 1.0)),
        "embed_scale": math.sqrt(int(config["hidden_size"])) if config.get("mup_enabled") else 1.0,
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
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=m["renorm"],
        router_score="sigmoid", route_scale=m["route_scale"], d_ff_shared=m["f_shared"],
        n_dense_layers=m["dense"], d_ff_dense=m["f_dense"], windows=m["windows"], rope_layers=m["rope"],
        qk_norm=True, qk_norm_per_head=True, attn_gate=True, post_norms=True, embed_scale=bool(config.get("mup_enabled")),
        max_seq_len=int(config["max_position_embeddings"]), rope_theta=m["theta"],
        norm_eps=m["eps"], tie_embeddings=m["tied"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward masks the windows in the plain expression: the flash kernels know `causal` only.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
Q_BLOCK = 512  # query rows per block of the attention (bounds the scores to [heads, 512, keys])
VOCAB_SLICE = 16384  # most columns of the head upcast at a time (all 200 192 x 2 048 in float32 are 1.6 GB)


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


def _attention(q, k, v, window: int):
    """q [s, h, hd], k/v [s, kv, hd] -> [s, h*hd]: query i sees key j iff
    j <= i and, under a window (0: none), i - j < window. In query blocks, one
    after another (`lax.map`: only one block's scores are alive at a time)."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k = jnp.repeat(k, rep, axis=1)
    v = jnp.repeat(v, rep, axis=1)
    block = min(Q_BLOCK, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, n * block - s), (0, 0), (0, 0)))  # rows past s are cut off below
    span = min(s, block + window - 1) if window else s  # the keys the rows of one block can see between them

    def one_block(q0):
        k0 = jnp.clip(q0 + block - span, 0, s - span)  # `span` keys ending with the block's last row
        qb, kb, vb = jax.lax.dynamic_slice_in_dim(q, q0, block), jax.lax.dynamic_slice_in_dim(k, k0, span), jax.lax.dynamic_slice_in_dim(v, k0, span)
        scores = jnp.einsum("qhd,khd->hqk", qb, kb) / jnp.sqrt(F32(hd))
        back = (q0 + jnp.arange(block))[:, None] - (k0 + jnp.arange(span))[None, :]
        mask = (back >= 0) & (back < window) if window else back >= 0
        probs = jax.nn.softmax(jnp.where(mask[None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", probs, vb)

    return jax.lax.map(one_block, jnp.arange(n) * block).reshape(n * block, h * hd)[:s]


def _swiglu(hn, mlp):
    return (jax.nn.silu(hn @ _f32(mlp["w_gate"])) * (hn @ _f32(mlp["w_up"]))) @ _f32(mlp["w_down"])


def _router_weights(hn, mlp, m: Dict):
    """hn [s, d] -> (weights [s, E]: the router's weight where the expert is
    among the token's k chosen, exactly 0 elsewhere; experts [s, k])."""
    scores = jax.nn.sigmoid(hn @ _f32(mlp["router"]))
    ranked = scores + _f32(mlp["router_bias"])  # the bias selects; it never weighs
    top_e = jax.lax.top_k(ranked, m["k"])[1]
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    if m["renorm"]:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    top_s = top_s * m["route_scale"]
    chosen = jax.nn.one_hot(top_e, m["E"], dtype=F32)  # [s, k, E]
    return jnp.sum(chosen * top_s[..., None], axis=1), top_e


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _experts(hn, weights, stacks, layer: int):
    """sum over experts e of weights[:, e] * SwiGLU_e(hn), one expert upcast at
    a time. `stacks` holds the routed group's expert weights as stored, all
    layers of it [layers, E, ., .], read at [layer, e]: a layer's slice of them
    (1.6 GB in bfloat16) is never copied out. An expert's term is a
    `jax.checkpoint` (olmoe.py says why)."""

    @jax.checkpoint
    def term(hn, w_gate, w_up, w_down, w_e):
        return w_e[:, None] * ((jax.nn.silu(hn @ _f32(w_gate)) * (hn @ _f32(w_up))) @ _f32(w_down))

    def add_expert(acc, e):
        return acc + term(hn, *(stacks[name][layer, e] for name in EXPERT_WEIGHTS), weights[:, e]), None

    acc, _ = jax.lax.scan(add_expert, jnp.zeros_like(hn), jnp.arange(weights.shape[1]))
    return acc


def _layer(x, w, m: Dict, window: int, rope: bool, stacks=None, layer: int = 0):
    """One block on x [s, d] -> (x, experts [s, k] or None); `w` is the layer's
    weights as stored, upcast where used, but for a routed layer's experts,
    which `_experts` reads out of the group's `stacks`."""
    a = w["attn"]
    hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
    s = hn.shape[0]
    q = _rms_norm((hn @ _f32(a["wq"])).reshape(s, m["h"], m["hd"]), a["q_norm"]["scale"], m["eps"])  # each head's dims
    k = _rms_norm((hn @ _f32(a["wk"])).reshape(s, m["kv"], m["hd"]), a["k_norm"]["scale"], m["eps"])
    v = (hn @ _f32(a["wv"])).reshape(s, m["kv"], m["hd"])
    if rope:
        q, k = _rope(q, m["theta"]), _rope(k, m["theta"])
    o = _attention(q, k, v, window)
    o = o * jax.nn.sigmoid(hn @ _f32(a["wg"]))
    x = x + _rms_norm(o @ _f32(a["wo"]), w["post_attn_norm"]["scale"], m["eps"])
    hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
    if "router" not in w["mlp"]:  # a leading dense layer
        return x + _rms_norm(_swiglu(hn, w["mlp"]), w["post_mlp_norm"]["scale"], m["eps"]), None
    weights, top_e = _router_weights(hn, w["mlp"], m)
    out = _experts(hn, weights, stacks, layer)
    out = out + _swiglu(hn, w["mlp"]["shared"])
    return x + _rms_norm(out, w["post_mlp_norm"]["scale"], m["eps"]), top_e


def _layers(params, tokens, m: Dict):
    """tokens [s] int32 -> (final-norm hidden states [s, d], experts [routed layers, s, k]), float32.
    Each layer is a `jax.checkpoint` (olmoe.py says why)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"][tokens]) * m["embed_scale"]
        chosen = []
        for layer in range(m["L"]):
            group, i = ("dense_blocks", layer) if layer < m["dense"] else ("blocks", layer - m["dense"])
            mlp = params[group]["mlp"]
            stacks = {name: mlp[name] for name in EXPERT_WEIGHTS} if "router" in mlp else None
            rest = dict(params[group], mlp={name: a for name, a in mlp.items() if not (stacks and name in stacks)})
            w = jax.tree_util.tree_map(lambda a: a[i], rest)
            window, rope = m["windows"][layer], m["rope"][layer]
            x, top_e = jax.checkpoint(lambda x, w, stacks: _layer(x, w, m, window, rope, stacks, i))(x, w, stacks)
            if top_e is not None:
                chosen.append(top_e)
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"]), jnp.stack(chosen)


def hidden_states(params, tokens, m: Dict):
    return _layers(params, tokens, m)[0]


def _logits(params, h):
    """h [n, d] float32 -> logits [n, V], the head upcast a slice of the vocabulary at a time."""
    head = params.get("lm_head")
    if head is None:
        head = params["embed"]["embedding"].T
    d, V = head.shape
    width = max(w for w in range(1, min(V, VOCAB_SLICE) + 1) if V % w == 0)  # 200 192 = 23 x 8 704
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


def routed_experts(params, tokens, config: Dict[str, Any]):
    """The experts the reference routes each token of ONE sequence to, best
    first: [routed layers, s, k]. A parity test asserts that the program chose
    the same, so that what differs is arithmetic and not a flipped choice."""
    return _layers(params, tokens, dims(config))[1]


# -------------------------------------------------------------- the counts


def expert_params(config: Dict[str, Any]) -> int:
    """ONE routed expert's three matrices."""
    m = dims(config)
    return 3 * m["d"] * m["f"]


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's attention matrices: q, the gate and the output projection, k and v."""
    m = dims(config)
    return 3 * m["d"] * m["h"] * m["hd"] + 2 * m["d"] * m["kv"] * m["hd"]


def shared_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters every token passes through whatever its routing:
    attention of every layer, the leading dense layers' FFN, router and shared
    expert of every routed layer, and the output head (the embedding is a gather)."""
    m = dims(config)
    routed = m["L"] - m["dense"]
    return (m["L"] * attention_params(config) + m["dense"] * 3 * m["d"] * m["f_dense"]
            + routed * (m["d"] * m["E"] + 3 * m["d"] * m["f_shared"]) + m["d"] * m["V"])


def active_matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters ONE token passes through: the shared ones and
    `num_experts_per_tok` of the `num_experts` experts a routed layer."""
    m = dims(config)
    return shared_matmul_params(config) + (m["L"] - m["dense"]) * m["k"] * expert_params(config)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a token
    passes through (active experts only), plus attention (QK^T and PV: 2 matmuls
    x 2 FLOPs x the visible positions x heads x head_dim a layer forward, x3
    with the backward): seq/2 on a full layer, what the window leaves on a
    sliding one."""
    m = dims(config)
    visible = sum(seq_len / 2 if not w or w >= seq_len else w - w * (w - 1) / (2 * seq_len) for w in m["windows"])
    return 6.0 * active_matmul_params(config) + 12 * m["h"] * m["hd"] * visible


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call of each kernel a TRAIN step of
    this architecture would run at [batch, seq_len]: the flash kernels' counts
    for a full layer. No cell trains it: the serving cell's kernels are counted
    by `decode_step_bytes`, `decode_expert_products` and `decode_attention_bytes`."""
    m = dims(config)
    return dict(flops.flash_kernels(m["h"], m["kv"], m["hd"], batch, seq_len))


def kv_bytes_per_token_layer(config: Dict[str, Any]) -> float:
    """K and V of one cached position of ONE layer."""
    m = dims(config)
    return float(2 * m["kv"] * m["hd"] * m["bytes_per_param"])


def experts_touched(config: Dict[str, Any], rows: int) -> float:
    """Experts of one routed layer that `rows` tokens are expected to touch
    when each takes k of E uniformly: E (1 - (1 - k/E)^rows). An upper
    expectation: random weights route unevenly and touch fewer."""
    m = dims(config)
    return m["E"] * (1.0 - (1.0 - m["k"] / m["E"]) ** rows)


def decode_attention_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """K/V one decode step's attention must read over ALL layers: a full layer
    every live position, a sliding layer at most `sliding_window` a row,
    `min(kv_tokens, live x window)`: exact where every live context is past the
    window (the agent-turns cell: prompts of 2.1 k tokens and more against a
    window of 2 048), an over-count of the bytes where some are shorter."""
    m = dims(config)
    seen = sum(min(kv_tokens, live_seqs * w) if w else kv_tokens for w in m["windows"])
    return kv_bytes_per_token_layer(config) * seen


def decode_step_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int, experts_touched_a_step: float) -> float:
    """What one decode step must read: the shared weights once, the experts its
    rows touched (a count summed over the routed layers, as the program's
    `clocks.decode_experts` keeps it), and the K/V its windows let it see."""
    m = dims(config)
    weights = shared_matmul_params(config) + experts_touched_a_step * expert_params(config)
    return float(weights * m["bytes_per_param"]) + decode_attention_bytes(config, live_seqs, kv_tokens)


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """`decode_step_bytes` with the experts a uniform router is expected to
    touch. The cell's roofline reads the program's own count instead
    (`readers/trace_modules.step_bytes`, `_counted.py`): an expectation that overstates
    the bytes can read past 100 %."""
    m = dims(config)
    return decode_step_bytes(config, live_seqs, kv_tokens, (m["L"] - m["dense"]) * experts_touched(config, live_seqs))


def decode_expert_products(config: Dict[str, Any], experts_touched_a_step: float) -> Dict[str, Any]:
    """What tells a decode step's expert products in a trace, and what ONE
    expert matrix stack read by them costs. `stacks`: the shapes of a routed
    group's expert weights as the serving steps hold them, [routed layers, E,
    d, f] (gate, up) and [routed layers, E, f, d] (down); `rows`: a decode
    step's rows through every expert, [E, slots, f] (the program multiplies
    few rows by all experts: `transformer._every_expert_ffn`). `needed` and
    `streamed`: (FLOPs, HBM bytes) of one projection of one layer, over the
    experts the step's rows TOUCHED (the mean over the routed layers of the
    program's count; slots x k rows of products) and over all E that the
    program streams (slots x E rows); `rows_in_bytes` the step's [slots, d]
    input. The [E, slots, f] intermediates are left out of both: their
    layouts in the compiled step say on-chip memory (`S(1)`), and a byte
    counted that is not read could push a share of the peak past 100 %. At 64
    rows the bytes bound both; their ratio is the share of the stream that an
    untouched expert's matrix is read for nothing."""
    m = dims(config)
    slots, routed = int(config["assumed"]["max_slots"]["value"]), m["L"] - m["dense"]
    matrix = m["d"] * m["f"]
    return {
        "stacks": [[routed, m["E"], m["d"], m["f"]], [routed, m["E"], m["f"], m["d"]]],
        "rows": [m["E"], slots, m["f"]],
        "needed": (2.0 * slots * m["k"] * matrix, float(m["bytes_per_param"] * experts_touched_a_step / routed * matrix)),
        "streamed": (2.0 * slots * m["E"] * matrix, float(m["bytes_per_param"] * m["E"] * matrix)),
        "rows_in_bytes": float(m["bytes_per_param"] * slots * m["d"]),
    }
