"""Architecture `gigachat3_5`: ai-sage's GigaChat3.5 (GigaChat3.5-432B-A28B,
`model_type: gigachat3_5`), everything the benchmark knows about it, in one
file that a configuration names with `"arch"`.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_bytes, decode_step_min_bytes, decode_state_bytes, decode_kv_bytes,
                   decode_grouped_products, latent_decode_work, latent_prefill_work, kernels
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer, and where each line comes from: [K] a key of the published
`config.json` (the catalog's row `GigaChat3.5-432B-A28B`); [A] what the config
names and does not define, as ISSUE 59 states it (Gated DeltaNet,
arXiv:2412.06464, fla's `GatedDeltaNet` as Qwen3-Next places it; the DeepSeek-V3
report, arXiv:2412.19437, for the latent layer and the router). There is no
network here: what [A] says was not re-read from any source by this file's
writer, and the configuration lists it under `assumed.layer_equations`. d =
`hidden_size`, eps `rms_norm_eps`, no bias (`attention_bias` false), untied head.

1. Norm. `N(x; w) = x / sqrt(mean(x^2) + eps) * c * sigmoid(w)`, c =
   `layernorm_gating_weight` (`norm_type: ZeroCenteredGatedNorm`: w = 0 is a
   scale of c / 2 = 1)                                                      [K] the keys; [A] the form
2. Block (`layernorm_type: pre_post`): `x += N(Mixer(N(x; w1)); w2)`,
   `x += N(FFN(N(x; w3)); w4)`; a final N before the head                   [A]
3. Stack. Layer i attends latently if i is in `full_attention_layers`, else
   it is a delta-rule layer; layers below `first_k_dense_replace` have a dense
   FFN, the others a routed one                                             [K]
4. Delta-rule layer (`linear_attention_type: GigaChat35GatedDeltaNet`), Hk =
   `linear_num_key_heads` key heads of `linear_key_head_dim`, Hv =
   `linear_num_value_heads` value heads of `linear_value_head_dim`:
   (a) `q~ = h Wq` [Hk x dk], `k~ = h Wk` [Hk x dk], `v~ = h Wv` [Hv x dv],
       `z = h Wz` [Hv x dv], `a = h Wa` [Hv], `b = h Wb` [Hv]                [K] shapes; [A]
   (b) a causal depthwise convolution of `linear_conv_kernel_dim` taps over
       each channel of `[q~; k~; v~]`, zeros before the sequence's start,
       then SiLU                                                            [K] taps; [A]
   (c) per head `q = l2norm(q') / sqrt(dk)`, `k = l2norm(k')`, `l2norm(x) = x
       / sqrt(sum x^2 + 1e-6)`; value head j reads key head j // (Hv / Hk)   [A]
   (d) `beta = sigmoid(b)`, `g = -exp(A_log) softplus(a + dt_bias)`, one
       number a value head                                                  [A]
   (e) state S [dk, dv] a value head, S_0 = 0, float32: `Sd = exp(g_t)
       S_(t-1)`; `u_t = beta_t (v_t - Sd^T k_t)`; `S_t = Sd + k_t u_t^T`;
       `o_t = S_t^T q_t`                                                    [A]
   (f) `y = Wo (o_t / sqrt(mean(o_t^2) + eps_o) * w_o * s sigmoid(z))`, the
       norm a head over dv with a plain scale `w_o`, eps_o =
       `linear_attn_o_norm_eps`, s = `linear_sigmoid_gate_scale`
       (`linear_gating_type: gated_rmsnorm_sigmoid_zero_centered`)          [K] the keys; [A] the form
5. Latent layer (MLA), H = `num_attention_heads`: `c_q = N(h W_DQ)`
   (`q_lora_rank`), `q = c_q W_UQ` as H heads of `qk_nope_head_dim +
   qk_rope_head_dim`; `[c_kv | k_r] = h W_DKV`, `c_kv = N(c_kv)`; rope on q's
   rope dims and on `k_r`, ONE key part for all heads, interleaved pairs (2i,
   2i + 1) (`rope_interleave`), theta `rope_theta`, YaRN's frequencies
   (`rope_scaling`, as archs/dots_vlm.py writes them), cos and sin times
   m(factor, mscale) / m(factor, mscale_all_dim); keys and values `c_kv W_UK`,
   `c_kv W_UV`; scores `q . k * scale`, `scale = (nope + rope)^-0.5 * m(factor,
   mscale_all_dim)^2` (`use_mla_scaling_factor`), causal, softmax in float32;
   `gated_attention`: `y = W_O (o * sigmoid(h W_G))`, W_G [d, H * v_head_dim],
   elementwise, the gate from the layer's normed input                      [K] sizes and switches; [A] the forms
6. FFN. `silu(min(W_g x, a)) * clip(W_u x, -a, a)` then `W_d`, a =
   `swiglu_limit`, dense, shared and routed alike                           [K] the key; [A] the form
   Dense width `intermediate_size`. Routed: `s = sigmoid(x W_r)` over the
   router's experts; the `num_experts_per_tok` largest `s + b`, a bias that
   only selects (`n_group` 1, `topk_group` 1: no group limit); `w_e =
   routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)`
   (`norm_topk_prob`); experts of `moe_intermediate_size`; `n_shared_experts`
   shared ones as one SwiGLU of their summed width, added ungated
   (`use_shared_expert_sigmoid` false)                                      [K] sizes and switches; [A] the bias, the 1e-20
7. `num_nextn_predict_layers` further modules behind the stack, for the
   training loss and for self-drafting: NOT instantiated (the config does not
   say which mixer a module's layer has: its indices are in no list); the key
   and `nextn_is_sparse` are read, any value                                [K]
8. `logits = N(x) W_head` (`tie_word_embeddings` false)                      [K]

ONE CHIP'S SHARE, as archs/solar_open2.py and archs/dots_vlm.py write it:
`n_routed_experts` in the file is the experts HELD here,
`reduced_from.n_routed_experts` the router's published width,
`assumed.expert_rank` which share (rank r holds experts [r x held, (r + 1) x
held)). Program and reference route over all the published experts,
renormalise over the chosen ones whether held or not, and sum the held ones'
terms. `vocab_size` in the file is the slice of the vocabulary held here.

The plain reference: jax.numpy, float32, matmul precision "highest"; the
convolution as `taps` shifted products; 4(e) token by token through `lax.scan`
(the published recurrence: no chunks, no cache); the latent layer EXPANDED,
token against token, without any cache and without absorption, a block of
heads at a time; the MoE one held expert at a time over every token, weighted
by the router's weight for that (token, expert), exactly zero where the expert
is not chosen; a SwiGLU upcasts F_BLOCK columns of its matrices at a time, cut
out of the group's stack where they lie, so that a prompt of ~3.6 k tokens
fits beside 12 GB of weights and caches. It shares no code with ray_tpu/models/
(which serves the chunked and the one-token form of 4(e) through state slots
and 5 absorbed out of latent pages) and reads only the layout of the weights
(`dense_blocks` [dense layers, ...], `blocks` [periods, ...] the periods'
latent layers, `kda_blocks` [periods, layers a period - 1, ...] their
delta-rule layers; [in, out] matrices; `w_uk` [heads, qk_nope, kv_lora], `w_uv`
[heads, kv_lora, v]; the held experts stacked on the axis after the layers').

The counts are the bytes the algorithm needs, from shapes alone: a decode step
reads every HELD expert (128 rows x 8 picks over 16 of 256 experts touch every
one), each live row's states in and out, and its latent rows once.
"""

from __future__ import annotations

import importlib.util
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

_PROGRAM = importlib.util.find_spec("ray_tpu.models.transformer")
if importlib.util.find_spec("ray_tpu.ops.kda") is None or importlib.util.find_spec("ray_tpu.ops.latent_attention") is None or "full_layers" not in open(_PROGRAM.origin).read():
    # Refused where the configuration is looked up, in the driver, before any process is started: a checkout whose
    # program lacks a layer, or has both and no stack that holds them side by side (TransformerConfig.full_layers), would
    # fail later, inside the replica that owns the chip. The program's source is read as text: nothing of it is imported here.
    raise ImportError("this checkout's program places no latent-attention layer beside delta-rule layers (ray_tpu/ops/kda.py, "
                      "ray_tpu/ops/latent_attention.py, TransformerConfig.full_layers): it cannot run a gigachat3_5 configuration")

# ------------------------------------------------------------- the mapping

# Read only to refuse another value: each names a branch this file does not compute.
FIXED = {"attention_bias": False, "hidden_act": "silu", "norm_topk_prob": True, "n_group": 1, "topk_group": 1,
         "norm_type": "ZeroCenteredGatedNorm", "layernorm_type": "pre_post", "gated_attention": True, "use_shared_expert_sigmoid": False,
         "use_mla_scaling_factor": True, "linear_attention_type": "GigaChat35GatedDeltaNet",
         "linear_gating_type": "gated_rmsnorm_sigmoid_zero_centered", "rope_interleave": True, "tie_word_embeddings": False}
# Read, and without effect on a served forward pass (see the docstring, 7; `tf_legacy_loss` shapes a training loss).
NO_FORWARD_EFFECT = ("num_nextn_predict_layers", "nextn_is_sparse", "tf_legacy_loss")
PUBLISHED_KEYS = frozenset(FIXED) | frozenset(NO_FORWARD_EFFECT) | {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace", "full_attention_layers",
    "num_attention_heads", "num_key_value_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "qk_head_dim",
    "v_head_dim", "max_position_embeddings", "rope_theta", "rope_scaling", "rms_norm_eps", "vocab_size", "n_routed_experts",
    "n_shared_experts", "num_experts_per_tok", "routed_scaling_factor", "layernorm_gating_weight", "swiglu_limit",
    "linear_key_head_dim", "linear_value_head_dim", "linear_conv_kernel_dim", "linear_num_key_heads", "linear_num_value_heads",
    "linear_sigmoid_gate_scale", "linear_attn_o_norm_eps", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 9,  # a dense delta-rule layer, then two periods of (latent, delta rule x 3)
    "first_k_dense_replace": 1,
    "full_attention_layers": [1, 5],
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "kv_lora_rank": 32,
    "q_lora_rank": 48,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "qk_head_dim": 24,
    "v_head_dim": 16,
    "linear_num_key_heads": 2,
    "linear_num_value_heads": 4,
    "linear_key_head_dim": 16,
    "linear_value_head_dim": 16,
    "linear_conv_kernel_dim": 4,
    "max_position_embeddings": 4096,
    "rope_theta": 10000,
    # The served positions (~250) lie past the original context, inside the ramp and beyond it.
    "rope_scaling": {"type": "yarn", "factor": 8, "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "vocab_size": 256,
    "n_routed_experts": 8,  # held, of 16
    "n_shared_experts": 1,
    "num_experts_per_tok": 4,
    "reduced_from": {"n_routed_experts": 16},
    # As afmoe.TINY: at these widths bfloat16 layers resolve a router's near-tie the other way than the reference too
    # often for tests/tiny.json's q99; the rehearsal runs the program in float32 and sees paths, shapes and counters.
    "torch_dtype": "float32",
    # tests/tiny.json's longest request is 176 + 8 + 64 = 248 positions: 16 pages of 16.
    "assumed": {"page_tokens": {"value": 16}, "max_pages_per_seq": {"value": 16}, "pool_pages": {"value": 96},
                "expert_rank": {"value": 1}},
}

L2_EPS = 1e-6  # [A]


def _mscale(factor: float, a: float) -> float:
    """YaRN's m(s, a) = 0.1 a ln s + 1."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k, must in FIXED.items():
        if k in config and config[k] != must:
            raise ValueError(f"gigachat3_5 does not compute {k}={config[k]!r} (it computes {must!r})")
    h, hv, hk = int(config["num_attention_heads"]), int(config["linear_num_value_heads"]), int(config["linear_num_key_heads"])
    dk = int(config["linear_key_head_dim"])
    if int(config.get("num_key_value_heads", h)) != h or hv != h or hv % hk or int(config["linear_value_head_dim"]) != dk:
        raise ValueError(f"{h} attention heads, {hv} value heads over {hk} key heads of {dk} x {config['linear_value_head_dim']}: the program gives a latent "
                         "layer as many key heads as query heads, a delta-rule layer as many value heads as those, key heads that divide them, and a square state")
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    if int(config.get("qk_head_dim", nope + rope)) != nope + rope:
        raise ValueError(f"qk_head_dim {config['qk_head_dim']} is not qk_nope_head_dim + qk_rope_head_dim")
    if float(config.get("linear_attn_o_norm_eps", config["rms_norm_eps"])) != float(config["rms_norm_eps"]) or float(config.get("linear_sigmoid_gate_scale", 2)) != 2.0:
        raise ValueError("the program gives the delta-rule layer's output norm the model's eps and its gate the scale 2")
    scaling = config.get("rope_scaling") or None
    if scaling and scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"gigachat3_5 computes YaRN or no rope scaling, not {scaling!r}")
    yarn = tuple(float(scaling[k]) for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim")) if scaling else None
    L, dense, full = int(config["num_hidden_layers"]), int(config.get("first_k_dense_replace", 0)), [int(i) for i in config["full_attention_layers"]]
    heads = [i for i in full if i >= dense]  # the periods' latent layers: each heads a period
    period = (heads[1] - heads[0]) if len(heads) > 1 else L - dense
    if not heads or heads != list(range(dense, L, period)) or (L - dense) % period or len(full) - len(heads) not in (0, dense) or not 0 < dense < L:
        raise ValueError(f"full_attention_layers {full} of {L} layers behind {dense} dense ones: the program computes leading dense layers of one kind, "
                         "then whole periods of (a latent layer, delta-rule layers)")
    held = int(config["n_routed_experts"])
    E = int(config.get("reduced_from", {}).get("n_routed_experts", held))
    rank = int(config.get("assumed", {}).get("expert_rank", {}).get("value", 0))
    if held * (rank + 1) > E:
        raise ValueError(f"rank {rank}'s {held} experts are not among the router's {E}")
    return {
        "d": int(config["hidden_size"]),
        "f_dense": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),  # ONE expert's width
        "f_shared": int(config.get("n_shared_experts", 0)) * int(config["moe_intermediate_size"]),
        "h": h, "nope": nope, "rope": rope, "v": int(config["v_head_dim"]), "c": int(config["kv_lora_rank"]), "r": int(config["q_lora_rank"]),
        "hv": hv, "hk": hk, "dk": dk, "taps": int(config["linear_conv_kernel_dim"]),
        "L": L, "dense": dense, "per": period,  # layers a period: one latent layer, then per - 1 delta-rule layers
        "full": tuple(full),
        "V": int(config["vocab_size"]),
        "E": E, "held": held, "first": rank * held, "k": int(config["num_experts_per_tok"]),
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "theta": float(config["rope_theta"]),
        "yarn": yarn,
        "scale": (nope + rope) ** -0.5 * (_mscale(yarn[0], yarn[5]) ** 2 if yarn else 1.0),  # [A] use_mla_scaling_factor
        "eps": float(config["rms_norm_eps"]),
        "c_norm": float(config["layernorm_gating_weight"]),
        "limit": float(config["swiglu_limit"]),
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
        vocab_size=m["V"], d_model=m["d"], n_layers=m["L"], n_heads=m["h"], n_kv_heads=m["h"], d_head=m["nope"] + m["rope"],
        kv_lora_rank=m["c"], q_lora_rank=m["r"], qk_nope_dim=m["nope"], qk_rope_dim=m["rope"], v_head_dim=m["v"],
        rope_scaling=("yarn", *m["yarn"]) if m["yarn"] else (), rope_theta=m["theta"], rope_style="interleaved", attn_gate=True,
        kda_per_period=m["per"] - 1, kda_conv=m["taps"], kda_decay="head", kda_key_heads=m["hk"], kda_head_dim=m["dk"], full_layers=m["full"],
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=True, router_score="sigmoid",
        route_scale=m["route_scale"], d_ff_shared=m["f_shared"], n_dense_layers=m["dense"], d_ff_dense=m["f_dense"],
        n_experts_held=m["held"], first_expert=m["first"], post_norms=True, norm_gate=m["c_norm"], swiglu_limit=m["limit"],
        # the state slots a served pool holds: a decode row each and the trash slot (PagedLM passes its own)
        state_slots=int(config.get("assumed", {}).get("max_slots", {}).get("value", 1)) + 1,
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=m["eps"], tie_embeddings=False,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward runs the chunked form and the expanded latent expression: the flash kernels refuse it.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
HEAD_BLOCK = 4  # latent heads whose keys, values and scores are made at a time: [4, s, s] float32 scores
F_BLOCK = 2048  # columns of a SwiGLU's matrices upcast at a time
VOCAB_SLICE = 4096  # most columns of the head upcast at a time
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _f32(w):
    return w.astype(F32)


def _norm(x, w, m: Dict):
    """N(x; w), the model's norm [A]."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + m["eps"]) * (m["c_norm"] * jax.nn.sigmoid(_f32(w)))


def _cut(w, lead, start, size):
    """w[*lead, start[0] : start[0] + size[0], ...] as float32: ONE slice of the array as stored, taken where it is used,
    so that no layer's or expert's FFN is copied out whole beside 12 GB of weights and caches."""
    lead = tuple(jnp.asarray(i, jnp.int32) for i in lead)
    begin = lead + tuple(jnp.asarray(i, jnp.int32) for i in start)
    return _f32(jax.lax.dynamic_slice(w, begin, (1,) * len(lead) + tuple(size)).reshape(size))


# -- the delta-rule layer


def _short_conv(x, w):
    """x [s, n], w [n, taps]: y_t = silu(sum_j w[:, j] x_(t - taps + 1 + j)), zeros before the start: `taps` shifted products."""
    s, taps = x.shape[0], w.shape[1]
    ext = jnp.pad(x, ((taps - 1, 0), (0, 0)))
    return jax.nn.silu(sum(_f32(w)[:, j] * ext[j : j + s] for j in range(taps)))  # [A] SiLU


def _l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)  # [A]


def _delta_rule(q, k, v, g, beta):
    """4(e), token by token: q, k [s, hv, dk], v [s, hv, dv], g, beta [s, hv] -> o [s, hv, dv]."""
    def token(S, xs):
        q, k, v, g, beta = xs
        Sd = jnp.exp(g)[:, None, None] * S
        u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", Sd, k))
        S = Sd + k[:, :, None] * u[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q)

    S0 = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    return jax.lax.scan(token, S0, (q, k, v, g, beta))[1]


def _delta_mixer(hn, a, m: Dict):
    s, dk = hn.shape[0], m["dk"]
    q, k, v = (_short_conv(hn @ _f32(a["w" + n]), a["conv_" + n]).reshape(s, -1, dk) for n in "qkv")
    q, k = _l2norm(q) / math.sqrt(dk), _l2norm(k)  # [A]
    q, k = (jnp.repeat(t, m["hv"] // m["hk"], axis=1) for t in (q, k))  # [A] value head j reads key head j // 2
    g = -jnp.exp(_f32(a["a_log"]))[None, :] * jax.nn.softplus(hn @ _f32(a["w_a"]) + _f32(a["dt_bias"]))  # [A] one decay a value head
    beta = jax.nn.sigmoid(hn @ _f32(a["w_b"]))  # [A] in (0, 1)
    o = _delta_rule(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + m["eps"]) * _f32(a["o_norm"]["scale"])  # a head's norm, a plain scale
    return (o.reshape(s, -1) * (2.0 * jax.nn.sigmoid(hn @ _f32(a["w_z"])))) @ _f32(a["wo"])  # [A] the gate: 1 at z = 0


# -- the latent layer


def _inv_freq(m: Dict):
    """The rotated pairs' frequencies [rope / 2], under YaRN where the configuration scales."""
    half = m["rope"] // 2
    f = m["theta"] ** (-jnp.arange(half, dtype=F32) / half)
    if m["yarn"] is None:
        return f
    factor, original, beta_fast, beta_slow = m["yarn"][:4]
    low, high = (2 * half * math.log(original / (2 * math.pi * n)) / (2 * math.log(m["theta"])) for n in (beta_fast, beta_slow))
    low, high = min(max(math.floor(low), 0), half - 1), min(max(math.ceil(high), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=F32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return f / factor * ramp + f * (1.0 - ramp)  # [A]


def _rope(x, m: Dict):
    """x [s, heads, rope] at positions 0..s-1; interleaved pairs (2i, 2i + 1) [K] rope_interleave."""
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * _inv_freq(m)[None, :]
    magnitude = _mscale(m["yarn"][0], m["yarn"][4]) / _mscale(m["yarn"][0], m["yarn"][5]) if m["yarn"] else 1.0
    cos, sin = jnp.cos(ang)[:, None, :] * magnitude, jnp.sin(ang)[:, None, :] * magnitude
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).reshape(x.shape)


def _latent_mixer(hn, a, m: Dict):
    """The expanded form, token against token: every position's latent becomes every head's key part and value, HEAD_BLOCK heads at a time."""
    s, h, c, nope = hn.shape[0], m["h"], m["c"], m["nope"]
    q = (_norm(hn @ _f32(a["wq_a"]), a["q_a_norm"]["scale"], m) @ _f32(a["wq_b"])).reshape(s, h, nope + m["rope"])
    q_nope, q_rope = q[..., :nope], _rope(q[..., nope:], m)
    kv = hn @ _f32(a["wkv_a"])
    c_kv = _norm(kv[:, :c], a["kv_a_norm"]["scale"], m)  # the latent's own norm
    k_r = _rope(kv[:, None, c:], m)[:, 0]  # one key part for all heads
    seen = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    hb = min(HEAD_BLOCK, h)

    def some_heads(xs):
        qn, qr, w_uk, w_uv = xs  # [s, hb, nope], [s, hb, rope], [hb, nope, c], [hb, c, v]
        k_nope, v = jnp.einsum("sc,hnc->shn", c_kv, _f32(w_uk)), jnp.einsum("sc,hcv->shv", c_kv, _f32(w_uv))
        scores = (jnp.einsum("qhn,khn->hqk", qn, k_nope) + jnp.einsum("qhr,kr->hqk", qr, k_r)) * m["scale"]
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1), v)

    def blocks_of(t, axis):
        return jnp.moveaxis(t.reshape(*t.shape[:axis], h // hb, hb, *t.shape[axis + 1 :]), axis, 0)

    o = jax.lax.map(some_heads, (blocks_of(q_nope, 1), blocks_of(q_rope, 1), blocks_of(a["w_uk"], 0), blocks_of(a["w_uv"], 0)))  # [h / hb, s, hb, v]
    o = jnp.moveaxis(o, 0, 1).reshape(s, h * m["v"])
    o = o * jax.nn.sigmoid(hn @ _f32(a["wg"]))  # [A] gated_attention: elementwise, on the layer's normed input
    return o @ _f32(a["wo"])


# -- the FFN


def _swiglu(hn, mlp, lead, m: Dict):
    """The clamped SwiGLU of hn [s, d] with the matrices at `mlp[name][*lead]`, F_BLOCK of their columns cut out and upcast at a time."""
    d, f = mlp["w_gate"].shape[-2:]
    block = max(b for b in range(1, min(f, F_BLOCK) + 1) if f % b == 0)
    limit = m["limit"]

    def some_columns(j, acc):
        gate, up = (_cut(mlp[name], lead, (0, j * block), (d, block)) for name in ("w_gate", "w_up"))
        act = jax.nn.silu(jnp.minimum(hn @ gate, limit)) * jnp.clip(hn @ up, -limit, limit)  # [A] swiglu_limit
        return acc + act @ _cut(mlp["w_down"], lead, (j * block, 0), (block, d))

    return jax.lax.fori_loop(0, f // block, some_columns, jnp.zeros_like(hn))


def _router_weights(hn, mlp, m: Dict):
    """hn [s, d] -> weights [s, E] over ALL the router's experts: the weight
    where the expert is among the token's k chosen, exactly 0 elsewhere."""
    scores = jax.nn.sigmoid(hn @ _f32(mlp["router"]))
    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # [A] the bias selects; it never weighs
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * m["route_scale"]  # over the chosen, held or not
    return jnp.sum(jax.nn.one_hot(top_e, m["E"], dtype=F32) * top_s[..., None], axis=1)


def _experts(hn, weights, mlp, lead, m: Dict):
    """sum over the HELD experts e of weights[:, first + e] * SwiGLU_e(hn), one
    expert at a time, cut out of the group's stack at [*lead, e]. What the
    absent experts would add is left out."""

    def add_expert(e, acc):
        return acc + jax.lax.dynamic_index_in_dim(weights, m["first"] + e, axis=1) * _swiglu(hn, mlp, (*lead, e), m)

    return jax.lax.fori_loop(0, m["held"], add_expert, jnp.zeros_like(hn))


def _ffn(hn, w, group, lead, m: Dict):
    mlp = group["mlp"]
    if "router" not in mlp:  # a leading dense layer
        return _swiglu(hn, mlp, lead, m)
    return _experts(hn, _router_weights(hn, w["mlp"], m), mlp, lead, m) + _swiglu(hn, mlp["shared"], lead, m)


def _layer(x, group, lead, m: Dict):
    """The layer at `lead` of a stacked group on x [s, d]. `w`: the layer's
    weights but for its FFN matrices, which `_swiglu` cuts out of `group`
    where they lie."""
    small = dict(group, mlp={k: v for k, v in group["mlp"].items() if k in ("router", "router_bias")})
    w = jax.tree_util.tree_map(lambda a: a[lead], small)
    mixer = _delta_mixer if "conv_q" in w["attn"] else _latent_mixer
    x = x + _norm(mixer(_norm(x, w["attn_norm"]["scale"], m), w["attn"], m), w["post_attn_norm"]["scale"], m)  # [A] pre_post
    return x + _norm(_ffn(_norm(x, w["mlp_norm"]["scale"], m), w, group, lead, m), w["post_mlp_norm"]["scale"], m)


def layer_places(m: Dict):
    """(group, index in it) of every layer in published order."""
    for layer in range(m["L"]):
        if layer < m["dense"]:
            yield "dense_blocks", (layer,)
        else:
            p, j = divmod(layer - m["dense"], m["per"])
            yield ("blocks", (p,)) if j == 0 else ("kda_blocks", (p, j - 1))


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32."""
    with jax.default_matmul_precision("highest"):
        x = _f32(params["embed"]["embedding"][tokens])
        for group, lead in layer_places(m):
            x = _layer(x, params[group], lead, m)
        return _norm(x, params["final_norm"]["scale"], m)


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
    """(latent layers, delta-rule layers)."""
    m = dims(config)
    latent = sum(1 for group, _ in layer_places(m) if group == "blocks") + (m["dense"] if 0 in m["full"] else 0)
    return latent, m["L"] - latent


def latent_params(config: Dict[str, Any]) -> int:
    """One latent layer's mixer: W_DQ, W_UQ, W_DKV, W_UK and W_UV, W_G, W_O."""
    m = dims(config)
    return (m["d"] * m["r"] + m["r"] * m["h"] * (m["nope"] + m["rope"]) + m["d"] * (m["c"] + m["rope"])
            + m["c"] * m["h"] * (m["nope"] + m["v"]) + 2 * m["h"] * m["v"] * m["d"])


def delta_params(config: Dict[str, Any]) -> int:
    """One delta-rule layer's mixer: W_q, W_k, W_v, W_z, W_a and W_b, W_o."""
    m = dims(config)
    return m["d"] * (2 * m["hk"] * m["dk"] + 3 * m["hv"] * m["dk"] + 2 * m["hv"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads: both kinds of mixer, the leading
    dense layers' FFN, every routed layer's router, shared expert and HELD
    experts, and the head (the embedding is a gather; norms, the selecting
    bias and convolution weights are left out: under a thousandth of it)."""
    m = dims(config)
    n_latent, n_delta = layer_counts(config)
    moe = m["d"] * m["E"] + 3 * m["d"] * m["f_shared"] + m["held"] * expert_params(config)
    return (n_latent * latent_params(config) + n_delta * delta_params(config) + m["dense"] * 3 * m["d"] * m["f_dense"]
            + (m["L"] - m["dense"]) * moe + m["d"] * m["V"])


def state_bytes_a_layer(config: Dict[str, Any]) -> int:
    """ONE sequence's state in ONE delta-rule layer: dk x dv float32 a value head (the tails are 2 % of it and left out)."""
    m = dims(config)
    return m["hv"] * m["dk"] * m["dk"] * 4


def decode_state_bytes(config: Dict[str, Any], live_seqs: float) -> float:
    """State one decode step must move: every live row's, every delta-rule layer's, read once and written once."""
    return float(live_seqs * layer_counts(config)[1] * 2 * state_bytes_a_layer(config))


def decode_kv_bytes(config: Dict[str, Any], kv_tokens: float) -> float:
    """What one decode step must read of its rows' positions: `[c_kv | k_r]` of every live position, every latent layer's,
    once, unpadded (under the name `readers/counter_cache_bytes_share.py` asks an architecture with two caches for)."""
    m = dims(config)
    return float(kv_tokens * layer_counts(config)[0] * (m["c"] + m["rope"]) * m["bytes_per_param"])


def decode_step_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int, experts_touched_a_step: float) -> float:
    """What one decode step must move: every weight outside the routed experts
    once, the held experts its rows touched (a count summed over the routed
    layers, as the program's `clocks.decode_experts` keeps it: a step of 128
    rows takes the grouped product, which reads no expert that no row chose),
    the live rows' states in and out, their latent rows once."""
    m = dims(config)
    weights = matmul_params(config) - (m["L"] - m["dense"]) * m["held"] * expert_params(config) + experts_touched_a_step * expert_params(config)
    return float(weights * m["bytes_per_param"]) + decode_state_bytes(config, live_seqs) + decode_kv_bytes(config, kv_tokens)


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """`decode_step_bytes` with the held experts that a uniform router's step
    is expected to touch: every slot's row is routed, live or not, so held x
    (1 - (1 - k / E)^slots) a routed layer (15.7 of 16 at 128 slots) whatever
    `live_seqs` is. The cell's roofline reads the program's own count instead
    (`readers/trace_modules.step_bytes`): an expectation that overstates the
    bytes can read past 100 % (afmoe.py's note; this cell's first traced run
    read 102.7 % with every held expert counted)."""
    m = dims(config)
    slots = int(config.get("assumed", {}).get("max_slots", {}).get("value", 1))
    touched = (m["L"] - m["dense"]) * m["held"] * (1.0 - (1.0 - m["k"] / m["E"]) ** slots)
    return decode_step_bytes(config, live_seqs, kv_tokens, touched)


def decode_grouped_products(config: Dict[str, Any], experts_touched_a_step: float) -> Dict[str, Dict[str, Any]]:
    """What tells a decode step's GROUPED expert products in a trace, and the
    least ONE call of each must do (`readers/trace_grouped_products.py`): a
    step of `max_slots` = 128 rows lies on the program's threshold and takes
    the grouped product (`grouped_swiglu`: gate and up and the activation;
    `grouped_matmul`: down), a call a routed layer, its result [slots x k, .]
    (a prefill chunk's call has 256 x k rows there). It reads no expert that
    no row chose: the bytes are a routed layer's share of the experts the
    program counted as touched a step; the FLOPs those of the picks expected
    on held experts (k x held / E a row)."""
    m = dims(config)
    slots, matrix = int(config["assumed"]["max_slots"]["value"]), m["d"] * m["f"]
    rows, touched_a_layer = slots * m["k"], experts_touched_a_step / (m["L"] - m["dense"])
    held_picks = rows * m["held"] / m["E"]
    return {
        "grouped_swiglu": {"result": [rows, m["f"]], "flops": 2 * 2.0 * held_picks * matrix, "bytes": float(2 * m["bytes_per_param"] * touched_a_layer * matrix)},
        "grouped_matmul": {"result": [rows, m["d"]], "flops": 2.0 * held_picks * matrix, "bytes": float(m["bytes_per_param"] * touched_a_layer * matrix)},
    }


def _absorbed_pair_flops(m: Dict) -> float:
    """One (query row, cached position) pair of ONE latent layer, absorbed: every head's score over `[c_kv | k_r]` and its sum over `c_kv`."""
    return 2.0 * m["h"] * (m["c"] + m["rope"] + m["c"])


def _expanded_pair_flops(m: Dict) -> float:
    """The same pair expanded: every head's score over `[k_nope | k_r]` and its sum over `v`."""
    return 2.0 * m["h"] * (m["nope"] + m["rope"] + m["v"])


def latent_decode_work(config: Dict[str, Any], live: int = 0, kv_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the latent attention of ONE decode step over the
    latent layers (`paged_latent_attention_decode`): `kv_tokens` cached
    positions, each read once and attended by its own row's heads, absorbed."""
    return layer_counts(config)[0] * kv_tokens * _absorbed_pair_flops(dims(config)), decode_kv_bytes(config, kv_tokens)


def latent_prefill_work(config: Dict[str, Any], prompt_tokens: int = 0, cached_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the latent attention of ONE prefill call over the
    latent layers (`paged_latent_attention_prefill`), the LEAST of the two
    forms, as archs/dots_vlm.py counts it: the rows [cached, prompt) against
    the positions below each, absorbed, or expanded with every position of
    the prompt expanded once a layer. The bytes: the prompt's latent rows once."""
    m = dims(config)
    n, first = prompt_tokens, min(cached_tokens, prompt_tokens)
    pairs = (n * (n + 1) - first * (first + 1)) / 2.0
    expanded = pairs * _expanded_pair_flops(m) + n * 2.0 * m["c"] * m["h"] * (m["nope"] + m["v"])
    return layer_counts(config)[0] * min(pairs * _absorbed_pair_flops(m), expanded), decode_kv_bytes(config, n)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a token
    passes through (of its k picks, the k x held / E expected on held experts),
    plus the latent layers' expanded attention (seq / 2 visible) and the
    delta-rule layers' state (read and added to: 2 x 2 x dk x dv a value head
    forward). No cell trains it."""
    m = dims(config)
    n_latent, n_delta = layer_counts(config)
    active = matmul_params(config) - (m["L"] - m["dense"]) * (m["held"] - m["k"] * m["held"] / m["E"]) * expert_params(config)
    return 6.0 * active + 3 * n_latent * _expanded_pair_flops(m) * seq_len / 2 + 3 * n_delta * m["hv"] * 4 * m["dk"] * m["dk"]


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call a layer of the kernels a served
    layer runs: `kda_decode`, a step of `batch` live rows (decay, k^T S, the
    rank-1 update, q^T S: 8 operations an entry of S; the state in and out);
    `paged_latent_attention_decode`, that step at `seq_len` cached positions a
    row, and `paged_latent_attention_prefill`, a miss of `seq_len` positions."""
    entries, n_latent = state_bytes_a_layer(config) / 4, layer_counts(config)[0]
    decode, prefill = latent_decode_work(config, batch, batch * seq_len), latent_prefill_work(config, seq_len, 0)
    return {"kda_decode": (8.0 * batch * entries, 2.0 * batch * state_bytes_a_layer(config)),
            "paged_latent_attention_decode": (decode[0] / n_latent, decode[1] / n_latent),
            "paged_latent_attention_prefill": (prefill[0] / n_latent, prefill[1] / n_latent)}
