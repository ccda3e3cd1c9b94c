"""Architecture `solar_open2`: Upstage Solar-Open2 (Solar-Open2-250B,
`model_type: solar_open2`), everything the benchmark knows about it, in one
file that a configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_min_bytes, decode_state_bytes, decode_kv_bytes,
                   decode_expert_products, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer, and where each line comes from: [K] a key of the published
`config.json` (the catalog's row `Solar-Open2-250B`); [M] the family's public
code where the keys are silent, as ISSUE 44 states it (Kimi Linear,
arXiv:2510.26692, `fla`'s `KimiDeltaAttention`, for `linear_attn_config`,
`kda_use_full_proj`, `kda_allow_neg_eigval`; the GLM-4.5 / solar_open router
for `n_routed_experts, n_shared_experts, norm_topk_prob,
routed_scaling_factor, first_k_dense_replace`). There is no network here: what
[M] says was not re-read from the source by this file's writer, and the
configuration lists it under `assumed.layer_equations`. d = `hidden_size`, eps
`rms_norm_eps`, no bias unless said, no rope anywhere, untied head.

1. Stack. Layer i is a GQA layer if i is in `gqa_layers`, else a KDA layer
   (`gqa_interval` of them between two GQA layers): periods of (GQA, KDA x
   `gqa_interval`).                                                         [K]
   Every layer: `x += Mixer(RMSNorm(x))`, `x += MoE(RMSNorm(x))`
   (`first_k_dense_replace: 0`: no dense layer; `intermediate_size` is the
   dense layer's width and is read by no layer).                            [K]
2. GQA layer. `q = h Wq` as `num_attention_heads` heads of `head_dim`, `k`,
   `v` as `num_key_value_heads` heads; no rope (`use_rope: false`)          [K]
   no q/k-norm                                                              [M]
   causal softmax(q k^T / sqrt(head_dim)), query head i reads K/V head i // r [K]
   `use_gqa_gate`: `o <- o * sigmoid(h Wg)`, Wg [d, heads * head_dim],
   elementwise                                                              [K] the switch; [M] the form
   `Wo` [heads * head_dim, d]                                               [K]
3. KDA layer: `linear_attn_config.num_heads` heads, d_k = d_v =
   `linear_attn_config.head_dim` (`num_kv_heads: null`: as many)            [K]
   (a) `q~ = h Wq`, `k~ = h Wk`, `v~ = h Wv`, each [d, heads * d_k]          [K] shapes
   (b) short convolution: depthwise, causal, `short_conv_kernel_size` taps, a
       weight [heads * d_k, taps] each for q, k, v, zeros before the
       sequence's start, then SiLU: `q'_t = silu(sum_j w[:, j] q~_(t-taps+1+j))`  [K] taps; [M]
   (c) per head `q = l2norm(q') / sqrt(d_k)`, `k = l2norm(k')`,
       `l2norm(x) = x / sqrt(sum x^2 + 1e-6)`, `v = v'`                      [M]
   (d) decay a head and key channel (`kda_use_full_proj: false`: two thin
       matrices): `f = (h W_fa) W_fb`, W_fa [d, d_k], W_fb [d_k, heads * d_k];
       `g_t = -exp(A_log[head]) softplus(f_t + dt_bias)` <= 0                [K] thin; [M]
   (e) `beta_t = 2 sigmoid(h W_b)`, W_b [d, heads]
       (`kda_allow_neg_eigval: true`: in (0, 2))                            [K] the 2; [M]
   (f) state S [d_k, d_v] a head, S_0 = 0, float32:
       `Sd = Diag(exp(g_t)) S_(t-1)`; `u_t = beta_t (v_t - Sd^T k_t)`;
       `S_t = Sd + k_t u_t^T`; `o_t = S_t^T q_t`                             [M]
   (g) `y = RMSNorm_dv(o; w_o, eps) * sigmoid((h W_ga) W_gb + b_g)`, W_ga
       [d, d_k], W_gb [d_k, heads * d_k], the one bias b_g; `Wo`             [M]
4. MoE. Router logits `h W_r` [d, `n_routed_experts`] in float32; `s =
   sigmoid(logits)`; the `num_experts_per_tok` experts with the largest `s +
   b_sel` (a selecting bias; no groups: the config has no `n_group`)         [K] sizes; [M] sigmoid, bias
   weights = the chosen s, renormalised to 1 (`norm_topk_prob`), x
   `routed_scaling_factor`; experts SwiGLU of `moe_intermediate_size`;
   `n_shared_experts` shared SwiGLU of that width added                      [K]
5. final RMSNorm, `logits = x Whead` (`tie_word_embeddings` false)           [K]

ONE CHIP'S SHARE. A configuration of this architecture holds one rank's share
of an expert-parallel deployment (`stands_for`): `n_routed_experts` in the
file is the experts HELD here, `reduced_from.n_routed_experts` the router's
published width, `assumed.expert_rank` which share (rank r holds experts [r x
held, (r + 1) x held)). Program and reference route over all the published
experts, renormalise over the chosen ones whether held or not, and sum the held
ones' terms: what the absent experts would add is left out of both.
`vocab_size` in the file is the slice of the vocabulary held here: a smaller
vocabulary, in traffic, logits and argmax alike.

The plain reference: jax.numpy, float32, matmul precision "highest"; the
convolution as `taps` shifted products; 3(f) token by token through `lax.scan`
(the published recurrence: no chunks, no cache); the GQA layer as a masked
softmax in query blocks; the MoE one held expert at a time over every token,
weighted by the router's weight for that (token, expert), exactly zero where
the expert is not chosen; each layer a `jax.checkpoint`; one sequence at a
time. It shares no code with ray_tpu/models/ (which serves the chunked and the
one-token form through two caches) and reads only the layout of the weights
(`blocks` [periods, ...] the GQA layers, `kda_blocks` [periods, gqa_interval,
...] the KDA layers; [in, out] matrices; the held experts stacked on the axis
after the layers'). `partial_rotary_factor` and `rope_theta` say nothing while
`use_rope` is false; `kda_use_full_proj`, `kda_allow_neg_eigval`, `use_rope`,
`first_k_dense_replace` are read to refuse another value.

The counts are the bytes the algorithm needs, from shapes alone: a decode step
reads every HELD expert (at 64 rows x 8 picks over 40 of 320 experts a step's
rows touch nearly every one, and in the deployment, 512 rows over 320, all of
them), each live row's states in and out, and its K/V once.
"""

from __future__ import annotations

import importlib.util
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

if importlib.util.find_spec("ray_tpu.ops.kda") is None:
    # Refused where the configuration is looked up, in the driver, before any process is started: a checkout from
    # before the program had the layer would fail later, inside the replica that owns the chip.
    raise ImportError("this checkout's program has no KDA layer (ray_tpu/ops/kda.py): it cannot run a solar_open2 configuration")

# ------------------------------------------------------------- the mapping

# Read only to refuse another value: each names a branch this file does not compute.
FIXED = {"use_rope": False, "kda_use_full_proj": False, "kda_allow_neg_eigval": True, "first_k_dense_replace": 0,
         "use_gqa_gate": True, "norm_topk_prob": True}
# Read, and without effect while use_rope is false / while no layer is dense.
SILENT = ("partial_rotary_factor", "rope_theta", "intermediate_size")
PUBLISHED_KEYS = frozenset(FIXED) | frozenset(SILENT) | {
    "hidden_size", "num_hidden_layers", "num_attention_heads", "head_dim", "num_key_value_heads", "vocab_size",
    "moe_intermediate_size", "rms_norm_eps", "tie_word_embeddings", "max_position_embeddings", "gqa_interval", "gqa_layers",
    "linear_attn_config", "n_routed_experts", "n_shared_experts", "routed_scaling_factor", "num_experts_per_tok", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "num_attention_heads": 4,
    "num_key_value_heads": 2,
    "head_dim": 16,
    "linear_attn_config": {"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
    "num_hidden_layers": 8,  # two periods
    "gqa_layers": [0, 4],
    "vocab_size": 256,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "n_routed_experts": 8,  # held, of 16
    "num_experts_per_tok": 4,
    "max_position_embeddings": 2048,
    "reduced_from": {"n_routed_experts": 16},
    # As afmoe.TINY: at these widths bfloat16 layers resolve a router's near-tie the other way than the reference too
    # often for tests/tiny.json's q99; the rehearsal runs the program in float32 and sees paths, shapes and counters.
    "torch_dtype": "float32",
    # tests/tiny.json's longest request is 176 + 8 + 64 = 248 positions: 16 pages of 16.
    "assumed": {"page_tokens": {"value": 16}, "max_pages_per_seq": {"value": 16}, "pool_pages": {"value": 96},
                "expert_rank": {"value": 1}},
}

L2_EPS = 1e-6  # [M]


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k, must in FIXED.items():
        if k in config and config[k] != must:
            raise ValueError(f"solar_open2 does not compute {k}={config[k]!r} (it computes {must!r})")
    lin = config["linear_attn_config"]
    h, hd = int(config["num_attention_heads"]), int(config["head_dim"])
    if int(lin["num_heads"]) != h or int(lin["head_dim"]) != hd or lin.get("num_kv_heads") not in (None, h):
        raise ValueError(f"linear_attn_config {lin} against {h} attention heads of {hd}: the program gives both kinds of layer the same heads")
    L, per = int(config["num_hidden_layers"]), int(config["gqa_interval"]) + 1 if "gqa_interval" in config else 4
    gqa = list(config["gqa_layers"])
    if L % per or gqa != list(range(0, L, per)):
        raise ValueError(f"gqa_layers {gqa} of {L} layers is not a GQA layer at the head of every {per} layers")
    held = int(config["n_routed_experts"])
    E = int(config.get("reduced_from", {}).get("n_routed_experts", held))
    rank = int(config.get("assumed", {}).get("expert_rank", {}).get("value", 0))
    if held * (rank + 1) > E:
        raise ValueError(f"rank {rank}'s {held} experts are not among the router's {E}")
    return {
        "d": int(config["hidden_size"]),
        "f": int(config["moe_intermediate_size"]),  # ONE expert's width
        "f_shared": int(config.get("n_shared_experts", 0)) * int(config["moe_intermediate_size"]),
        "h": h,
        "kv": int(config["num_key_value_heads"]),
        "hd": hd,
        "taps": int(lin["short_conv_kernel_size"]),
        "L": L,
        "per": per,  # layers a period: one GQA layer, then per - 1 KDA layers
        "V": int(config["vocab_size"]),
        "E": E,  # the router's width
        "held": held,
        "first": rank * held,
        "k": int(config["num_experts_per_tok"]),
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "eps": float(config["rms_norm_eps"]),
        "tied": bool(config.get("tie_word_embeddings", False)),
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
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["kv"], d_head=m["hd"],
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=True, router_score="sigmoid",
        route_scale=m["route_scale"], d_ff_shared=m["f_shared"], n_experts_held=m["held"], first_expert=m["first"],
        kda_per_period=m["per"] - 1, kda_conv=m["taps"], attn_gate=True, rope_layers=(False,) * m["L"],
        # the state slots a served pool holds: a decode row each and the trash slot (PagedLM passes its own)
        state_slots=int(config.get("assumed", {}).get("max_slots", {}).get("value", 1)) + 1,
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=m["eps"], tie_embeddings=m["tied"],
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward runs the chunked form and the plain softmax expression: the flash kernels refuse it.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
Q_BLOCK = 512  # query rows per block of the attention (bounds the scores to [heads, 512, keys])
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _f32(w):
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


def _attention(q, k, v):
    """q [s, h, hd], k/v [s, kv, hd] -> [s, h*hd]: causal softmax, no rope, in
    query blocks one after another."""
    s, h, hd = q.shape
    rep = h // k.shape[1]
    k, v = jnp.repeat(k, rep, axis=1), jnp.repeat(v, rep, axis=1)  # query head i reads K/V head i // rep
    block = min(Q_BLOCK, s)
    n = -(-s // block)
    q = jnp.pad(q, ((0, n * block - s), (0, 0), (0, 0)))  # rows past s are cut off below

    def one_block(q0):
        scores = jnp.einsum("qhd,khd->hqk", jax.lax.dynamic_slice_in_dim(q, q0, block), k) / jnp.sqrt(F32(hd))
        seen = (q0 + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1), v)

    return jax.lax.map(one_block, jnp.arange(n) * block).reshape(n * block, h * hd)[:s]


def _gqa_mixer(hn, a, m: Dict):
    s = hn.shape[0]
    q = (hn @ _f32(a["wq"])).reshape(s, m["h"], m["hd"])  # [M] no q/k-norm; [K] no rope
    k = (hn @ _f32(a["wk"])).reshape(s, m["kv"], m["hd"])
    v = (hn @ _f32(a["wv"])).reshape(s, m["kv"], m["hd"])
    o = _attention(q, k, v) * jax.nn.sigmoid(hn @ _f32(a["wg"]))  # [M] the gate: elementwise, on the layer's normed input
    return o @ _f32(a["wo"])


def _short_conv(x, w):
    """x [s, n], w [n, taps]: y_t = silu(sum_j w[:, j] x_(t - taps + 1 + j)), zeros before the start: `taps` shifted products."""
    s, taps = x.shape[0], w.shape[1]
    ext = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(_f32(w)[:, j] * ext[j : j + s] for j in range(taps)))  # [M] SiLU


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)  # [M]


def _delta_rule(q, k, v, g, beta):
    """3(f), token by token: q, k, g [s, h, dk], v [s, h, dv], beta [s, h] -> o [s, h, dv]."""
    def token(S, xs):
        q, k, v, g, beta = xs
        Sd = jnp.exp(g)[:, :, None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", Sd, k))
        S = Sd + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, S0, (q, k, v, g, beta))[1]


def _kda_mixer(hn, a, m: Dict):
    s, h, hd = hn.shape[0], m["h"], m["hd"]
    q, k, v = (_short_conv(hn @ _f32(a["w" + n]), a["conv_" + n]).reshape(s, h, hd) for n in "qkv")
    q, k = _l2norm(q) / math.sqrt(hd), _l2norm(k)  # [M]
    f = (hn @ _f32(a["w_fa"])) @ _f32(a["w_fb"])
    g = -jnp.exp(_f32(a["a_log"]))[None, :, None] * jax.nn.softplus(f + _f32(a["dt_bias"])).reshape(s, h, hd)  # [M]
    beta = 2.0 * jax.nn.sigmoid(hn @ _f32(a["w_b"]))  # [M] in (0, 2)
    o = _delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((hn @ _f32(a["w_ga"])) @ _f32(a["w_gb"]) + _f32(a["b_g"]))  # [M] the one bias
    return (_rms_norm(o, a["o_norm"]["scale"], m["eps"]).reshape(s, h * hd) * gate) @ _f32(a["wo"])


def _swiglu(hn, mlp):
    return (jax.nn.silu(hn @ _f32(mlp["w_gate"])) * (hn @ _f32(mlp["w_up"]))) @ _f32(mlp["w_down"])


def _router_weights(hn, mlp, m: Dict):
    """hn [s, d] -> weights [s, E] over ALL the router's experts: the weight
    where the expert is among the token's k chosen, exactly 0 elsewhere."""
    scores = jax.nn.sigmoid(hn @ _f32(mlp["router"]))  # [M]
    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # [M] the bias selects; it never weighs
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * m["route_scale"]  # over the chosen, held or not
    return jnp.sum(jax.nn.one_hot(top_e, m["E"], dtype=F32) * top_s[..., None], axis=1)


def _experts(hn, weights, stacks, index, m: Dict):
    """sum over the HELD experts e of weights[:, first + e] * SwiGLU_e(hn),
    one expert upcast at a time, read at `stacks[name][index + (e,)]` out of
    the group's stack (a layer's slice of it is never copied out). What the
    absent experts would add is left out."""

    @jax.checkpoint
    def term(hn, w_gate, w_up, w_down, w_e):
        return w_e[:, None] * ((jax.nn.silu(hn @ _f32(w_gate)) * (hn @ _f32(w_up))) @ _f32(w_down))

    def add_expert(acc, e):
        return acc + term(hn, *(stacks[name][(*index, e)] for name in EXPERT_WEIGHTS), weights[:, m["first"] + e]), None

    return jax.lax.scan(add_expert, jnp.zeros_like(hn), jnp.arange(m["held"]))[0]


def _layer(x, w, m: Dict, stacks, index):
    """One layer on x [s, d]; `w` its weights as stored but for the experts,
    which `_experts` reads out of the group's `stacks` at `index`."""
    hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
    x = x + (_kda_mixer if "conv_q" in w["attn"] else _gqa_mixer)(hn, w["attn"], m)
    hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
    return x + _experts(hn, _router_weights(hn, w["mlp"], m), stacks, index, m) + _swiglu(hn, w["mlp"]["shared"])


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32. Each
    layer is a `jax.checkpoint` (dense_decoder.py says why)."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"][tokens])
        for layer in range(m["L"]):
            p, j = divmod(layer, m["per"])
            group, index = ("blocks", (p,)) if j == 0 else ("kda_blocks", (p, j - 1))
            mlp = params[group]["mlp"]
            stacks = {name: mlp[name] for name in EXPERT_WEIGHTS}
            rest = dict(params[group], mlp={name: a for name, a in mlp.items() if name not in stacks})
            w = jax.tree_util.tree_map(lambda a: a[index], rest)
            x = jax.checkpoint(lambda x, w, stacks, index=index: _layer(x, w, m, stacks, index))(x, w, stacks)
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"])


def _logits(params, h):
    return h @ _f32(params["lm_head"] if "lm_head" in params else params["embed"]["embedding"].T)


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
    """(GQA layers, KDA layers)."""
    m = dims(config)
    return m["L"] // m["per"], m["L"] // m["per"] * (m["per"] - 1)


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads: both kinds of mixer, every
    routed layer's router, shared expert and HELD experts, and the head (the
    embedding is a gather; norms, biases and convolution weights are left
    out: under a thousandth of it)."""
    m = dims(config)
    wide = m["h"] * m["hd"]
    gqa = 3 * m["d"] * wide + 2 * m["d"] * m["kv"] * m["hd"]  # q, the gate, o; k, v
    kda = 4 * m["d"] * wide + 2 * (m["d"] * m["hd"] + m["hd"] * wide) + m["d"] * m["h"]  # q, k, v, o; the two thin pairs; beta
    moe = m["d"] * m["E"] + 3 * m["d"] * m["f_shared"] + m["held"] * expert_params(config)
    n_gqa, n_kda = layer_counts(config)
    return n_gqa * gqa + n_kda * kda + m["L"] * moe + m["d"] * m["V"]


def state_bytes_a_layer(config: Dict[str, Any]) -> int:
    """ONE sequence's state in ONE KDA layer: d_k x d_v float32 a head (the tails are 0.3 % of it and left out)."""
    m = dims(config)
    return m["h"] * m["hd"] * m["hd"] * 4


def decode_state_bytes(config: Dict[str, Any], live_seqs: float) -> float:
    """State one decode step must move: every live row's, every KDA layer's, read once and written once."""
    return float(live_seqs * layer_counts(config)[1] * 2 * state_bytes_a_layer(config))


def decode_kv_bytes(config: Dict[str, Any], kv_tokens: float) -> float:
    """K/V one decode step must read: every live position's, every GQA layer's, once."""
    m = dims(config)
    return float(kv_tokens * layer_counts(config)[0] * 2 * m["kv"] * m["hd"] * m["bytes_per_param"])


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must move: every weight held here once (every held
    expert: see the docstring), the live rows' states in and out, their K/V once."""
    return (float(matmul_params(config) * dims(config)["bytes_per_param"])
            + decode_state_bytes(config, live_seqs) + decode_kv_bytes(config, kv_tokens))


def decode_expert_products(config: Dict[str, Any], experts_touched_a_step: float) -> Dict[str, Any]:
    """What tells a decode step's expert products in a trace, and what ONE
    expert matrix stack read by them costs (`readers/trace_expert_products.py`;
    afmoe.py's, at this architecture's stacks). `stacks`: the shapes of the
    expert weights as the serving steps hold them, the GQA layers' [periods,
    held, ., .] and the KDA layers' [periods, per - 1, held, ., .]; `rows`: a
    decode step's rows through every held expert, [held, slots, f]. `needed`:
    (FLOPs, HBM bytes) of one projection of one layer over the held experts
    the step's rows TOUCHED (the mean over the routed layers of the program's
    count); `streamed`: over all the held experts, which is what a step reads."""
    m = dims(config)
    slots, periods = int(config["assumed"]["max_slots"]["value"]), m["L"] // m["per"]
    matrix, lead = m["d"] * m["f"], ([periods, m["held"]], [periods, m["per"] - 1, m["held"]])
    return {
        "stacks": [shape + tail for shape in lead for tail in ([m["d"], m["f"]], [m["f"], m["d"]])],
        "rows": [m["held"], slots, m["f"]],
        "needed": (2.0 * slots * m["k"] * m["held"] / m["E"] * matrix, float(m["bytes_per_param"] * experts_touched_a_step / m["L"] * matrix)),
        "streamed": (2.0 * slots * m["held"] * matrix, float(m["bytes_per_param"] * m["held"] * matrix)),
        "rows_in_bytes": float(m["bytes_per_param"] * slots * m["d"]),
    }


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a token
    passes through (of its k picks, the k x held / E expected on held experts),
    plus the GQA layers' attention (seq/2 visible) and the KDA layers' state
    (read and added to: 2 x 2 x d_k x d_v a head forward)."""
    m = dims(config)
    n_gqa, n_kda = layer_counts(config)
    active = matmul_params(config) - m["L"] * (m["held"] - m["k"] * m["held"] / m["E"]) * expert_params(config)
    return 6.0 * active + 12 * n_gqa * m["h"] * m["hd"] * seq_len / 2 + 3 * n_kda * m["h"] * 4 * m["hd"] * m["hd"]


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call a layer of what a KDA layer runs
    when served: `kda_decode`, a step of `batch` live rows (decay, k^T S, the
    rank-1 update, q^T S: 8 operations an entry of S; the state in and out)."""
    entries = state_bytes_a_layer(config) / 4
    return {"kda_decode": (8.0 * batch * entries, 2.0 * batch * state_bytes_a_layer(config))}
