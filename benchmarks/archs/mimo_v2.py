"""Architecture `mimo_v2`: Xiaomi MiMo-V2 (MiMo-V2.5's language model,
`model_type: mimo_v2`), everything the benchmark knows about it, in one file
that a configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_min_bytes, decode_state_bytes, decode_kv_bytes,
                   decode_expert_products, wide_key_decode_work, wide_key_prefill_work, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer, and where each line comes from: [K] a key of the published
`config.json` (the catalog's row `MiMo-V2.5`); [M] what the keys are silent on,
as ISSUE 61 states it. There is no network here: what [M] says was not re-read
from any source by this file's writer, and the configuration lists it under
`assumed.layer_equations`. d = `hidden_size`, eps `layernorm_epsilon`, no bias
(`attention_bias` false), untied head. Layer l is GLOBAL where
`hybrid_layer_pattern[l]` is 0 and a WINDOW layer where it is 1.

1. `h = RMSNorm(x)`; `[q | k | v] = h W_qkv` (`attention_projection_layout:
   fused_qkv`: one matrix; the weights are kept as three leaves, the product
   is the same). q has `num_attention_heads` heads of `head_dim` (192); k as
   many heads of `head_dim` and v of `v_head_dim` (128) as the layer's kind
   has K/V heads: `num_key_value_heads` (4) global, `swa_num_key_value_heads`
   (8) window; query head i reads K/V head i // (heads / kv heads)        [K]
2. Rope on the first `int(head_dim x partial_rotary_factor)` dims (64) of
   each q and k head [K]; rotate-half pairs (i, i + 32) of them [M]; theta
   `rope_theta` (1e7) global, `swa_rope_theta` (1e4) window [K];
   `rope_scaling` "default": none [K]. No q/k-norm (no key).
3. `v <- attention_value_scale x v` (0.707) [K]; applied to v behind its
   projection [M] (linear: the place does not change the result).
4. `s_ij = q_i . k_j / sqrt(head_dim)`. Global: j <= i, plain softmax
   (`add_full_attention_sink_bias` false) [K]. Window: i - window < j <= i,
   window = `sliding_window` = `sliding_window_size` (128) [K]: a query sees
   itself and the 127 before it [M]; `p_ij = exp(s_ij) / (exp(b_head) + sum_j
   exp(s_ij))`, b a learned float32 logit a QUERY head
   (`add_swa_attention_sink_bias` true) [K] the switch; [M] the form.
   `attention_chunk_size` is read, must equal the window, and computes
   nothing of its own [M].
5. `x <- x + (sum_j p_ij v_j) W_o`.
6. `h2 = RMSNorm(x)`. `moe_layer_freq[l]` 0 (layer 0): SwiGLU of width
   `intermediate_size`. 1: `z = sigmoid(h2 W_r)` over all `n_routed_experts`
   (`scoring_func`); the `num_experts_per_tok` largest of `z + e_bias`
   (`topk_method: noaux_tc`, `n_group` 1: the bias only selects); weights z of
   the chosen, renormalised to 1 (`norm_topk_prob`), times 1
   (`routed_scaling_factor` null); `x <- x + sum` over the chosen experts HELD
   here of `w_e SwiGLU_e(h2)` (width `moe_intermediate_size`); no shared
   expert (`n_shared_experts` null)                                       [K]
7. Final RMSNorm, `logits = x W_head` over the vocabulary's slice.

Not instantiated: the three next-token modules and the vision and audio
towers of the row's `described_as` (no key gives them a size).

The plain reference: jax.numpy, float32, matmul precision "highest", no
kernels, no cache, no ring, no batching: one sequence at a time, token against
token. So that 17 k positions fit beside the engine's weights and caches it
works in blocks: a layer's k and v of every position first (few K/V heads:
small), then the rows ROW_BLOCK at a time, one group of query heads (those of
one K/V head) at a time, Q_BLOCK query rows of scores at a time; a window
layer's query block multiplies only the keys its rows can see (a slice) and
masks inside them; a SwiGLU's columns F_BLOCK at a time, one expert at a time,
the head a slice of the vocabulary at a time. It shares no code with
ray_tpu/models/transformer.py and reads only the layout of the weights
(`dense_blocks` [1, ..] the leading layer; `blocks` [periods, ..] the periods'
global layers; `window_blocks` [periods, window layers a period, ..]; [in,
out] matrices; the held experts stacked on the axis after the layers';
`attn.sink` [.., heads] float32).

The counts are the bytes the algorithm needs, from shapes alone: a decode
step reads every HELD expert, each live row's global K/V once (a K head at
its 192 dims: what padding the pages add is not needed), and of a window
layer at most `window` positions a row.
"""

from __future__ import annotations

import importlib.util
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

if "window_kv_heads" not in open(importlib.util.find_spec("ray_tpu.models.transformer").origin).read():
    # Refused where the configuration is looked up, in the driver, before any process is started: a checkout whose
    # program has no window layer that keeps a ring would fail later, inside the replica that owns the chip. The
    # program's source is read as text: nothing of it is imported here.
    raise ImportError("this checkout's program keeps no ring for a window layer beside paged layers "
                      "(TransformerConfig.window_kv_heads): it cannot run a mimo_v2 configuration")

# ------------------------------------------------------------- the mapping

# Read only to refuse another value: each names a branch this file does not compute.
FIXED = {"attention_bias": False, "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc", "norm_topk_prob": True,
         "n_group": 1, "topk_group": 1, "tie_word_embeddings": False, "add_full_attention_sink_bias": False,
         "add_swa_attention_sink_bias": True, "attention_projection_layout": "fused_qkv", "hybrid_block_size": None,
         "n_shared_experts": None, "routed_scaling_factor": None}
# Read, and required to repeat another key: the window layers' query heads and head sizes are the global layers'.
SAME_AS = {"swa_num_attention_heads": "num_attention_heads", "swa_head_dim": "head_dim", "swa_v_head_dim": "v_head_dim",
           "sliding_window_size": "sliding_window", "attention_chunk_size": "sliding_window"}
PUBLISHED_KEYS = frozenset(FIXED) | frozenset(SAME_AS) | {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "hybrid_layer_pattern", "moe_layer_freq",
    "num_attention_heads", "num_key_value_heads", "swa_num_key_value_heads", "head_dim", "v_head_dim", "partial_rotary_factor",
    "rope_theta", "swa_rope_theta", "rope_scaling", "attention_value_scale", "sliding_window", "layernorm_epsilon",
    "max_position_embeddings", "vocab_size", "n_routed_experts", "num_experts_per_tok", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 7,
    "hybrid_layer_pattern": [0, 1, 1, 0, 1, 1, 0],  # two periods of (2 window, 1 global) behind the dense global layer
    "moe_layer_freq": [0, 1, 1, 1, 1, 1, 1],
    "num_attention_heads": 8,
    "num_key_value_heads": 2,
    "swa_num_attention_heads": 8,
    "swa_num_key_value_heads": 4,
    "head_dim": 24,
    "swa_head_dim": 24,
    "v_head_dim": 16,
    "swa_v_head_dim": 16,
    "partial_rotary_factor": 0.334,  # int(24 x 0.334) = 8 rotated dims
    "rope_theta": 10000000,
    "swa_rope_theta": 10000,
    "attention_value_scale": 0.707,
    "add_swa_attention_sink_bias": True,
    "add_full_attention_sink_bias": False,
    "sliding_window": 16,
    "sliding_window_size": 16,
    "attention_chunk_size": 16,
    "layernorm_epsilon": 1e-5,
    "max_position_embeddings": 4096,
    "vocab_size": 256,
    "n_routed_experts": 8,  # held, of 16
    "num_experts_per_tok": 4,
    "reduced_from": {"n_routed_experts": 16},
    # As afmoe.TINY: at these widths bfloat16 layers resolve a router's near-tie the other way than the reference too
    # often for tests/tiny.json's q99; the rehearsal runs the program in float32 and sees paths, shapes and counters.
    "torch_dtype": "float32",
    # tests/tiny.json's longest request is 248 positions: 16 pages of 16; a prefill chunk is 16 pages, so a longer
    # prompt's ring is carried across a chunk's border only in tests/test_mimo_v2.py, which makes the chunks small.
    "assumed": {"page_tokens": {"value": 16}, "max_pages_per_seq": {"value": 16}, "pool_pages": {"value": 96},
                "expert_rank": {"value": 1}},
}


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k, must in FIXED.items():
        if k in config and config[k] != must:
            raise ValueError(f"mimo_v2 does not compute {k}={config[k]!r} (it computes {must!r})")
    for k, other in SAME_AS.items():
        if k in config and config[k] != config[other]:
            raise ValueError(f"mimo_v2 computes {k} == {other}, not {config[k]!r} beside {config[other]!r}")
    scaling = config.get("rope_scaling") or {}
    if scaling.get("rope_type", scaling.get("type", "default")) != "default":
        raise ValueError(f"mimo_v2 computes no rope scaling, not {scaling!r}")
    L, pattern, freq = int(config["num_hidden_layers"]), list(config["hybrid_layer_pattern"]), list(config["moe_layer_freq"])
    per = next((i for i in range(1, L) if pattern[i] == 0), L) - 1  # window layers between layer 0 and the next global one
    if (len(pattern) != L or len(freq) != L or per < 1 or (L - 1) % (per + 1) or pattern != [0] + ([1] * per + [0]) * ((L - 1) // (per + 1))
            or freq != [0] + [1] * (L - 1)):
        raise ValueError(f"hybrid_layer_pattern {pattern} / moe_layer_freq {freq}: a dense global layer first, then whole periods of "
                         "alike window layers that end on a global layer, all routed")
    held = int(config["n_routed_experts"])
    E = int(config.get("reduced_from", {}).get("n_routed_experts", held))
    rank = int(config.get("assumed", {}).get("expert_rank", {}).get("value", 0))
    if held * (rank + 1) > E:
        raise ValueError(f"rank {rank}'s {held} experts of {E}")
    h, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    return {
        "d": int(config["hidden_size"]),
        "f_dense": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),  # ONE expert's width
        "h": h, "hd": hd, "v": int(config["v_head_dim"]),
        "kv": int(config["num_key_value_heads"]), "kv_w": int(config["swa_num_key_value_heads"]),
        "rot": int(hd * float(config["partial_rotary_factor"])),  # [K] the rotated dims of a head: int(192 x 0.334) = 64
        "theta": float(config["rope_theta"]), "theta_w": float(config["swa_rope_theta"]),
        "value_scale": float(config["attention_value_scale"]),
        "window": int(config["sliding_window"]),
        "L": L, "per": per, "pattern": tuple(pattern),
        "V": int(config["vocab_size"]),
        "E": E, "held": held, "first": rank * held, "k": int(config["num_experts_per_tok"]),
        "eps": float(config["layernorm_epsilon"]),
        "bytes_per_param": {"bfloat16": 2, "float32": 4}[config.get("torch_dtype", "bfloat16")],
    }


def vocab_size(config: Dict[str, Any]) -> int:
    """The token ids the traffic may draw: the slice of the vocabulary held here."""
    return int(config["vocab_size"])


def model_config(config: Dict[str, Any], **overrides):
    """The program's TransformerConfig for a configuration file (call it
    only in the process that owns the chip)."""
    from ray_tpu.models import transformer as tfm

    m = dims(config)
    kw = dict(
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"], d_head=m["hd"], v_head_dim=m["v"],
        rotary_dim=m["rot"], rope_theta=m["theta"], window_rope_theta=m["theta_w"], window_kv_heads=m["kv_w"], window_sink=True,
        value_scale=m["value_scale"], windows=tuple(m["window"] * kind for kind in m["pattern"]),
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=True, router_score="sigmoid",
        n_dense_layers=1, d_ff_dense=m["f_dense"], n_experts_held=m["held"], first_expert=m["first"],
        state_slots=int(config.get("assumed", {}).get("max_slots", {}).get("value", 1)) + 1,  # a ring slot a decode row, and the trash slot
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=m["eps"], tie_embeddings=False,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward masks the windows in the plain expression: the flash kernels know `causal` only.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of a layer computed at a time
Q_BLOCK = 128  # query rows per block of scores: [a K/V head's query heads, 128, keys] float32 (16 x 128 x 17 408: 143 MB)
F_BLOCK = 2048  # columns of a SwiGLU's matrices upcast at a time
VOCAB_SLICE = 4096  # most columns of the head upcast at a time
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _f32(w):
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _row_blocks(s: int) -> Tuple[int, int]:
    """(rows a block, blocks) that cover s rows: blocks of ROW_BLOCK, or one of the whole of a shorter sequence in Q_BLOCKs."""
    block = ROW_BLOCK if s > ROW_BLOCK else -(-s // Q_BLOCK) * Q_BLOCK
    return block, -(-s // block)


def _cut(w, lead, start, size):
    """`size` of the matrix at `w[*lead]` from `start` (both (rows, columns)), upcast: the stack is read where it lies."""
    lead = tuple(jnp.asarray(i, jnp.int32) for i in lead)
    return _f32(jax.lax.dynamic_slice(w, (*lead, *start), (1,) * len(lead) + tuple(size)).reshape(size))


def _rope(x, first, theta: float, rot: int):
    """x [rows, heads, hd] at positions first..: the first `rot` dims of every head rotated in rotate-half pairs
    (i, i + rot/2) [M] by position x theta^(-2i/rot); the other dims pass."""
    half = rot // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = (first + jnp.arange(x.shape[0])).astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _heads_and_theta(window: bool, m: Dict):
    """(K/V heads, rope's base) of a window layer or a global one."""
    return (m["kv_w"], m["theta_w"]) if window else (m["kv"], m["theta"])


def _keys_values(x, w, group, lead, window: bool, m: Dict):
    """k [S, kv heads, hd] (rotated) and v [S, kv heads, v] (scaled) of every position of a layer, a row block at a time."""
    kvh, theta = _heads_and_theta(window, m)
    block, n = _row_blocks(x.shape[0])
    wk, wv = (_cut(group["attn"][name], lead, (0, 0), group["attn"][name].shape[-2:]) for name in ("wk", "wv"))

    def rows(i):
        hn = _rms_norm(jax.lax.dynamic_slice_in_dim(x, i * block, block), w["attn_norm"]["scale"], m["eps"])
        k = _rope((hn @ wk).reshape(block, kvh, m["hd"]), i * block, theta, m["rot"])
        return k, (hn @ wv).reshape(block, kvh, m["v"]) * m["value_scale"]  # [M] v scaled behind its projection

    k, v = jax.lax.map(rows, jnp.arange(n))
    return k.reshape(n * block, kvh, m["hd"]), v.reshape(n * block, kvh, m["v"])


def _attention(q, k, v, q0, window: int, sink):
    """One K/V head's query heads over its keys: q [block, heads, hd] at
    positions q0.., k [S, hd], v [S, v] -> [block, heads, v]. Query i sees key
    j iff j <= i and, under a window (0: none), i - j < window. Under `sink`
    [heads] the softmax's denominator has exp(sink) beside the keys' terms.
    Q_BLOCK rows at a time; a window's block multiplies only the span of keys
    its rows can see between them."""
    block, heads, hd = q.shape
    S = k.shape[0]
    span = min(S, Q_BLOCK + window - 1) if window else S

    def some_rows(i):
        r0 = q0 + i * Q_BLOCK
        k0 = jnp.clip(r0 + Q_BLOCK - span, 0, S - span)  # `span` keys ending with the block's last row
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK)
        kb, vb = jax.lax.dynamic_slice_in_dim(k, k0, span), jax.lax.dynamic_slice_in_dim(v, k0, span)
        scores = jnp.einsum("qhd,kd->hqk", qb, kb) / jnp.sqrt(F32(hd))
        back = (r0 + jnp.arange(Q_BLOCK))[:, None] - (k0 + jnp.arange(span))[None, :]
        seen = (back >= 0) & (back < window) if window else back >= 0
        scores = jnp.where(seen[None], scores, -jnp.inf)
        if sink is None:
            probs = jax.nn.softmax(scores, axis=-1)
        else:  # [M] a term in the denominator that is no key
            top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink[:, None, None])
            e = jnp.exp(scores - top)
            probs = e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink[:, None, None] - top))
        return jnp.einsum("hqk,kv->qhv", probs, vb)

    return jax.lax.map(some_rows, jnp.arange(block // Q_BLOCK)).reshape(block, heads, -1)


def _mixer(hn, w, group, lead, k, v, q0, window: bool, m: Dict):
    """A block of rows' attention output [block, d]: one K/V head's group of query heads at a time, its columns of
    `wq` and its rows of `wo` cut out of the stack."""
    kvh, theta = _heads_and_theta(window, m)
    rep, a = m["h"] // kvh, group["attn"]
    sink = w["attn"].get("sink")

    def one_group(g, acc):
        wq = _cut(a["wq"], lead, (0, g * rep * m["hd"]), (m["d"], rep * m["hd"]))
        q = _rope((hn @ wq).reshape(hn.shape[0], rep, m["hd"]), q0, theta, m["rot"])
        kg, vg = (jax.lax.dynamic_index_in_dim(t, g, axis=1, keepdims=False) for t in (k, v))
        sink_g = None if sink is None else jax.lax.dynamic_slice_in_dim(_f32(sink), g * rep, rep)
        o = _attention(q, kg, vg, q0, m["window"] if window else 0, sink_g)
        return acc + o.reshape(hn.shape[0], rep * m["v"]) @ _cut(a["wo"], lead, (g * rep * m["v"], 0), (rep * m["v"], m["d"]))

    return jax.lax.fori_loop(0, kvh, one_group, jnp.zeros_like(hn))


def _swiglu(hn, mlp, lead):
    """SwiGLU of hn [rows, d] with the matrices at `mlp[name][*lead]`, F_BLOCK of their columns cut out and upcast at a time."""
    d, f = mlp["w_gate"].shape[-2:]
    block = max(b for b in range(1, min(f, F_BLOCK) + 1) if f % b == 0)

    def some_columns(j, acc):
        gate, up = (_cut(mlp[name], lead, (0, j * block), (d, block)) for name in ("w_gate", "w_up"))
        return acc + (jax.nn.silu(hn @ gate) * (hn @ up)) @ _cut(mlp["w_down"], lead, (j * block, 0), (block, d))

    return jax.lax.fori_loop(0, f // block, some_columns, jnp.zeros_like(hn))


def _router_weights(hn, mlp, m: Dict):
    """hn [rows, d] -> weights [rows, E] over ALL the router's experts: the
    weight where the expert is among the token's k chosen, exactly 0 elsewhere."""
    scores = jax.nn.sigmoid(hn @ _f32(mlp["router"]))
    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # the bias selects; it never weighs
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)  # over the chosen, held or not
    return jnp.sum(jax.nn.one_hot(top_e, m["E"], dtype=F32) * top_s[..., None], axis=1)


def _ffn(hn, w, group, lead, m: Dict):
    mlp = group["mlp"]
    if "router" not in mlp:  # the leading dense layer
        return _swiglu(hn, mlp, lead)
    weights = _router_weights(hn, w["mlp"], m)

    def add_expert(e, acc):  # the HELD experts, one at a time; what the absent ones would add is left out
        return acc + jax.lax.dynamic_index_in_dim(weights, m["first"] + e, axis=1) * _swiglu(hn, mlp, (*lead, e))

    return jax.lax.fori_loop(0, m["held"], add_expert, jnp.zeros_like(hn))


def _layer(x, group, lead, window: bool, m: Dict):
    """The layer at `lead` of a stacked group on x [S, d], S whole row blocks:
    every position's k and v first, then the rows a block at a time, written
    back in place. `w`: the layer's small leaves; the matrices are cut out of
    `group` where they are used."""
    small = {"attn_norm": group["attn_norm"], "mlp_norm": group["mlp_norm"],
             "attn": {k: group["attn"][k] for k in ("sink",) if k in group["attn"]},
             "mlp": {k: group["mlp"][k] for k in ("router", "router_bias") if k in group["mlp"]}}
    w = jax.tree_util.tree_map(lambda a: a[lead], small)
    k, v = _keys_values(x, w, group, lead, window, m)
    block, n = _row_blocks(x.shape[0])

    def rows(i, x):
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        xb = xb + _mixer(_rms_norm(xb, w["attn_norm"]["scale"], m["eps"]), w, group, lead, k, v, i * block, window, m)
        xb = xb + _ffn(_rms_norm(xb, w["mlp_norm"]["scale"], m["eps"]), w, group, lead, m)
        return jax.lax.dynamic_update_slice_in_dim(x, xb, i * block, axis=0)

    return jax.lax.fori_loop(0, n, rows, x)


def layer_places(m: Dict):
    """(group, index in it, whether it is a window layer) of every layer in published order."""
    yield "dense_blocks", (0,), False
    for layer in range(1, m["L"]):
        p, j = divmod(layer - 1, m["per"] + 1)
        yield ("window_blocks", (p, j), True) if j < m["per"] else ("blocks", (p,), False)


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        block, n = _row_blocks(s)
        # rows past s are padding: causal, so no row below s sees them, and they are cut off at the end
        x = _f32(params["embed"]["embedding"][jnp.pad(tokens, (0, n * block - s))])
        for group, lead, window in layer_places(m):
            x = _layer(x, params[group], lead, window, m)
        return _rms_norm(x[:s], params["final_norm"]["scale"], m["eps"])


def _logits(params, h):
    """h [n, d] float32 -> logits [n, V], the head upcast a slice of the vocabulary at a time."""
    head = params["lm_head"]
    d, V = head.shape
    width = max(w for w in range(1, min(V, VOCAB_SLICE) + 1) if V % w == 0)
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


def expert_params(config: Dict[str, Any]) -> int:
    """ONE routed expert's three matrices."""
    m = dims(config)
    return 3 * m["d"] * m["f"]


def layer_counts(config: Dict[str, Any]) -> Tuple[int, int]:
    """(global layers, window layers)."""
    pattern = dims(config)["pattern"]
    return pattern.count(0), pattern.count(1)


def attention_params(config: Dict[str, Any], window: bool) -> int:
    """One layer's attention matrices: q and o over all heads, k and v over the kind's K/V heads."""
    m = dims(config)
    kvh = m["kv_w"] if window else m["kv"]
    return m["d"] * m["h"] * (m["hd"] + m["v"]) + m["d"] * kvh * (m["hd"] + m["v"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads: every layer's attention, the
    leading layer's dense FFN, every routed layer's router and HELD experts,
    and the head (the embedding is a gather; norms, sinks and the selecting
    bias are left out: under a thousandth of it)."""
    m = dims(config)
    n_global, n_window = layer_counts(config)
    moe = m["d"] * m["E"] + m["held"] * expert_params(config)
    return (n_global * attention_params(config, False) + n_window * attention_params(config, True)
            + 3 * m["d"] * m["f_dense"] + (m["L"] - 1) * moe + m["d"] * m["V"])


def kv_bytes_per_token_layer(config: Dict[str, Any], window: bool) -> int:
    """K and V of one position of ONE layer as they must be read: a K head at head_dim (the pages pad it to whole lane tiles)."""
    m = dims(config)
    return (m["kv_w"] if window else m["kv"]) * (m["hd"] + m["v"]) * m["bytes_per_param"]


def decode_state_bytes(config: Dict[str, Any], live_seqs: float) -> float:
    """What one decode step must read of the window layers: every live row's
    ring, at most `window` positions a row a layer, once (the one row a step
    writes is a 128th of it and left out). A row that has not filled its
    window yet reads less: an over-count by (window - positions) / window for
    the first `window` tokens of a sequence, nothing in this cell."""
    m = dims(config)
    return float(live_seqs * layer_counts(config)[1] * m["window"] * kv_bytes_per_token_layer(config, True))


def decode_kv_bytes(config: Dict[str, Any], kv_tokens: float) -> float:
    """K/V one decode step must read of the global layers: every live position's, once."""
    return float(kv_tokens * layer_counts(config)[0] * kv_bytes_per_token_layer(config, False))


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must move: every weight held here once (every
    held expert: a step of 64 rows multiplies every one, the streamed form),
    the live rows' global K/V once, and of a window layer `min(kv_tokens, live
    x window)` positions: never more than `window` a row."""
    m = dims(config)
    rings = min(decode_state_bytes(config, live_seqs), float(kv_tokens * layer_counts(config)[1] * kv_bytes_per_token_layer(config, True)))
    return float(matmul_params(config) * m["bytes_per_param"]) + rings + decode_kv_bytes(config, kv_tokens)


def decode_expert_products(config: Dict[str, Any], experts_touched_a_step: float) -> Dict[str, Any]:
    """What tells a decode step's expert products in a trace, and what ONE
    expert matrix stack read by them costs (`readers/trace_expert_products.py`;
    solar_open2.py's, at this architecture's stacks): the global layers'
    [periods, held, ., .] and the window layers' [periods, window layers a
    period, held, ., .]; `rows` [held, slots, f]; `needed` over the held
    experts the step's rows TOUCHED, `streamed` over all the held ones."""
    m = dims(config)
    slots, periods, routed = int(config["assumed"]["max_slots"]["value"]), (m["L"] - 1) // (m["per"] + 1), m["L"] - 1
    matrix, lead = m["d"] * m["f"], ([periods, m["held"]], [periods, m["per"], m["held"]])
    return {
        "stacks": [shape + tail for shape in lead for tail in ([m["d"], m["f"]], [m["f"], m["d"]])],
        "rows": [m["held"], slots, m["f"]],
        "needed": (2.0 * slots * m["k"] * m["held"] / m["E"] * matrix, float(m["bytes_per_param"] * experts_touched_a_step / routed * matrix)),
        "streamed": (2.0 * slots * m["held"] * matrix, float(m["bytes_per_param"] * m["held"] * matrix)),
        "rows_in_bytes": float(m["bytes_per_param"] * slots * m["d"]),
    }


def _pair_flops(m: Dict) -> float:
    """One (query row, cached position) pair of ONE layer: every head's score over head_dim and its sum over v_head_dim."""
    return 2.0 * m["h"] * (m["hd"] + m["v"])


def wide_key_decode_work(config: Dict[str, Any], live: int = 0, kv_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the GLOBAL layers' paged attention of ONE decode
    step (`paged_attention_decode`): `kv_tokens` cached positions, each read
    once (4 x 192 of K and 4 x 128 of V: the NEEDED bytes, whatever the pages
    pad) and attended by its own row's heads."""
    m = dims(config)
    return layer_counts(config)[0] * kv_tokens * _pair_flops(m), decode_kv_bytes(config, kv_tokens)


def wide_key_prefill_work(config: Dict[str, Any], prompt_tokens: int = 0, cached_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the GLOBAL layers' paged attention of ONE prefill
    call (`paged_attention_prefill`): the rows [cached, prompt) against the
    positions at and below each; the bytes: the prompt's K/V once."""
    m = dims(config)
    n, first = prompt_tokens, min(cached_tokens, prompt_tokens)
    pairs = (n * (n + 1) - first * (first + 1)) / 2.0
    return layer_counts(config)[0] * pairs * _pair_flops(m), decode_kv_bytes(config, n)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a token
    passes through (of its k picks, the k x held / E expected on held experts),
    plus attention: seq / 2 visible on a global layer, what the window leaves
    on a window layer. No cell trains it."""
    m = dims(config)
    n_global, n_window = layer_counts(config)
    active = matmul_params(config) - (m["L"] - 1) * (m["held"] - m["k"] * m["held"] / m["E"]) * expert_params(config)
    w = m["window"]
    visible = n_global * seq_len / 2 + n_window * (seq_len / 2 if w >= seq_len else w - w * (w - 1) / (2 * seq_len))
    return 6.0 * active + 3 * _pair_flops(m) * visible


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call a layer of the kernels a served
    global layer runs: `paged_attention_decode`, a step of `batch` rows at
    `seq_len` cached positions each, and `paged_attention_prefill`, a miss of
    `seq_len` positions (`readers/trace_latent_roofline.py` reads the steps'
    and the calls' own sizes through `wide_key_decode_work` and
    `wide_key_prefill_work`). The window layers' rings run no kernel."""
    n = layer_counts(config)[0]
    decode, prefill = wide_key_decode_work(config, batch, batch * seq_len), wide_key_prefill_work(config, seq_len, 0)
    return {"paged_attention_decode": (decode[0] / n, decode[1] / n), "paged_attention_prefill": (prefill[0] / n, prefill[1] / n)}
