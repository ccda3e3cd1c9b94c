"""Architecture `dots_vlm`: the language model of rednote-hilab's dots.vlm1
(`dots.vlm1.inst`, `model_type: dots_vlm`, which reuses DeepSeek-V3's
decoder), everything the benchmark knows about it, in one file that a
configuration names with `"arch"`. The vision tower is NOT here: the catalog's
row holds the language model's keys alone and no `vision_config`, so what is
served is the language model on token ids.

    the mapping    PUBLISHED_KEYS, model_config(config, **overrides), vocab_size(config)
    the reference  sequence_nll(params, tokens, config), logits_at(params, tokens, positions, config)
    the counts     train_flops_per_token, decode_step_min_bytes, decode_expert_products, kernels,
                   latent_decode_work, latent_prefill_work
    tiny widths    TINY, for the CPU rehearsal and the tests

The layer, and where each line comes from: [K] a key of the published
`config.json` (the catalog's row `dots.vlm1.inst`); [P] the DeepSeek-V3 report
(arXiv:2412.19437) and the published `modeling_deepseek.py`, as ISSUE 50
states them, where the keys are silent. There is no network here: what [P]
says was not re-read from the source by this file's writer, and the
configuration lists it under `assumed.layer_equations`. d = `hidden_size`,
eps `rms_norm_eps`, no bias (`attention_bias` false), untied head.

1. Block: `x += Attn(RMSNorm(x))`, `x += FFN(RMSNorm(x))`; final RMSNorm.     [K]
2. Latent attention (MLA), H = `num_attention_heads` heads:
   `c_q = RMSNorm(h W_DQ)` (`q_lora_rank`); `q = c_q W_UQ` as H heads of
   `qk_nope_head_dim + qk_rope_head_dim` = `q_nope | q_rope`                    [K]
   `[c_kv | k_r] = h W_DKV` (`kv_lora_rank | qk_rope_head_dim`);
   `c_kv = RMSNorm(c_kv)`; `k_r = rope(k_r)`, ONE key part for all heads;
   `q_rope = rope(q_rope)` a head                                              [P]
   `k_nope = c_kv W_UK` (H heads of `qk_nope_head_dim`), `v = c_kv W_UV` (H
   heads of `v_head_dim`); `k = [k_nope | k_r]`; scores `q . k * scale`,
   causal, softmax in float32; `o = softmax . v`; `Attn = o W_O`                [K]
3. Rope on the `qk_rope_head_dim` dims, rotate-half pairs (i, i + dim / 2) (a
   fixed permutation of W_UQ's and W_DKV's rope columns against interleaved
   pairs: with seeded weights either is the model), theta `rope_theta`, under
   YaRN (`rope_scaling`): `f_i = theta^(-2i / dim)`; `d(n) = dim ln(original /
   (2 pi n)) / (2 ln theta)`; `low = floor(d(beta_fast))`, `high =
   ceil(d(beta_slow))`, both clipped to [0, dim / 2 - 1]; `ramp_i = clip((i -
   low) / (high - low), 0, 1)`; `inv_freq_i = f_i / factor * ramp_i + f_i *
   (1 - ramp_i)`; cos and sin times `m(factor, mscale) / m(factor,
   mscale_all_dim)` with `m(s, a) = 0.1 a ln s + 1`; `scale = (qk_nope_head_dim
   + qk_rope_head_dim)^-0.5 * m(factor, mscale_all_dim)^2`                      [K] the numbers; [P] the form
4. Layers below `first_k_dense_replace`: SwiGLU of `intermediate_size`.        [K]
   The others (`moe_layer_freq` 1: every one): `s = sigmoid(h W_r)` over the
   router's experts (`scoring_func`); `s' = s + b`, a bias that only selects
   (`topk_method: noaux_tc`); the experts lie in `n_group` groups of
   neighbours; a group's score is the sum of its two largest `s'`; the
   `topk_group` best groups stay and no expert of another can be chosen; the
   `num_experts_per_tok` largest `s'` among what stays; `w_e =
   routed_scaling_factor * s_e / (sum of the chosen s + 1e-20)`
   (`norm_topk_prob`); `FFN = SwiGLU_shared(h) + sum_e w_e SwiGLU_e(h)`, each
   of `moe_intermediate_size`, `n_shared_experts` shared ones as one SwiGLU
   of their summed width                                                        [K] sizes and switches; [P] the bias, the groups' score, the 1e-20
5. `num_nextn_predict_layers`: one further module behind the stack, for the
   training loss and for self-drafting; the report says it may be discarded
   when serving. It is not instantiated here (the key is read, any value).     [K], [P]
6. `logits = x W_head` (`tie_word_embeddings` false).                          [K]

ONE CHIP'S SHARE, as archs/solar_open2.py writes it: `n_routed_experts` in
the file is the experts HELD here, `reduced_from.n_routed_experts` the
router's published width, `assumed.expert_rank` which share (rank r holds
experts [r x held, (r + 1) x held)). Program and reference route over all the
published experts in their published groups, renormalise over the chosen ones
whether held or not, and sum the held ones' terms. `vocab_size` in the file is
the slice of the vocabulary held here. `ep_size` is the published code's own
switch for running its experts across ranks and says nothing about a forward
pass's numbers; `seq_aux` shapes a training loss that nothing here computes.

The plain reference: jax.numpy, float32, matmul precision "highest"; the
EXPANDED form, token against token, without any cache and without absorption:
every position's latent becomes every head's key part and value (a block of
heads at a time), where the program serves the absorbed form out of latent
pages; no sort: one held expert upcast at a time over every token, weighted
by the router's weight for that (token, expert), exactly zero where the
expert is not chosen; the groups by ranks written out, no top-k over groups.
It is laid out so that a prompt of ~25 000 tokens fits in ~2 GB beside 14 GB of
weights and pages: a layer first computes every position's `[c_kv | k_r]`
(57 MB), then walks the rows in blocks of ROW_BLOCK, in place; a block of rows
takes its heads HEAD_BLOCK at a time (their keys and values expanded from the
latents for that block alone) and its scores Q_BLOCK rows at a time; a SwiGLU
upcasts F_BLOCK columns of its matrices at a time. It shares no code with
ray_tpu/models/ and reads only the layout of the weights (`dense_blocks` before
`blocks`; [in, out] matrices; `w_uk` [heads, qk_nope, kv_lora], `w_uv` [heads,
kv_lora, v]; the held experts stacked on the axis after the layers').

The counts are the LEAST work that computes the layer, whichever form the
program runs: a decode step and a prefill behind a prefix hit absorbed (a
cached position read once, 2 x heads x (kv_lora + rope + kv_lora) FLOPs a
query row); a miss expanded, a prompt's positions expanded once a layer. A
program that does more work than that reads a lower share.
"""

from __future__ import annotations

import importlib.util
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

if importlib.util.find_spec("ray_tpu.ops.latent_attention") is None:
    # Refused where the configuration is looked up, in the driver, before any process is started: a checkout from
    # before the program had the layer would fail later, inside the replica that owns the chip.
    raise ImportError("this checkout's program has no latent attention (ray_tpu/ops/latent_attention.py): it cannot run a dots_vlm configuration")

# ------------------------------------------------------------- the mapping

# Read only to refuse another value: each names a branch this file does not compute.
FIXED = {"attention_bias": False, "hidden_act": "silu", "scoring_func": "sigmoid", "topk_method": "noaux_tc",
         "norm_topk_prob": True, "moe_layer_freq": 1, "tie_word_embeddings": False}
# Read, and without effect on a served forward pass (see the docstring).
NO_FORWARD_EFFECT = ("ep_size", "seq_aux", "num_nextn_predict_layers")
PUBLISHED_KEYS = frozenset(FIXED) | frozenset(NO_FORWARD_EFFECT) | {
    "hidden_size", "intermediate_size", "moe_intermediate_size", "num_hidden_layers", "first_k_dense_replace",
    "num_attention_heads", "num_key_value_heads", "kv_lora_rank", "q_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
    "v_head_dim", "max_position_embeddings", "rope_theta", "rope_scaling", "rms_norm_eps", "vocab_size",
    "n_routed_experts", "n_shared_experts", "num_experts_per_tok", "n_group", "topk_group", "routed_scaling_factor", "torch_dtype",
}

TINY = {
    "hidden_size": 64,
    "intermediate_size": 96,
    "moe_intermediate_size": 32,
    "num_hidden_layers": 3,
    "first_k_dense_replace": 1,
    "num_attention_heads": 4,
    "num_key_value_heads": 4,
    "kv_lora_rank": 32,
    "q_lora_rank": 48,
    "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8,
    "v_head_dim": 16,
    "max_position_embeddings": 4096,
    "rope_theta": 10000,
    "rms_norm_eps": 1e-6,
    # The served positions (~250) lie past the original context, inside the ramp and beyond it.
    "rope_scaling": {"type": "yarn", "factor": 40, "original_max_position_embeddings": 64, "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1},
    "vocab_size": 256,
    "n_routed_experts": 8,  # held, of 16 in 4 groups of 4
    "n_shared_experts": 1,
    "num_experts_per_tok": 4,
    "n_group": 4,
    "topk_group": 2,
    "routed_scaling_factor": 2.5,
    "reduced_from": {"n_routed_experts": 16},
    # As afmoe.TINY: at these widths bfloat16 layers resolve a router's near-tie the other way than the reference too
    # often for tests/tiny.json's q99; the rehearsal runs the program in float32 and sees paths, shapes and counters.
    "torch_dtype": "float32",
    # tests/tiny.json's longest request is 176 + 8 + 64 = 248 positions: 16 pages of 16.
    "assumed": {"page_tokens": {"value": 16}, "max_pages_per_seq": {"value": 16}, "pool_pages": {"value": 96},
                "expert_rank": {"value": 1}},
}


def _mscale(factor: float, a: float) -> float:
    """YaRN's m(s, a) = 0.1 a ln s + 1."""
    return 0.1 * a * math.log(factor) + 1.0 if factor > 1 else 1.0


def dims(config: Dict[str, Any]) -> Dict[str, Any]:
    """The sizes the reference and the counts need, under short names."""
    for k, must in FIXED.items():
        if k in config and config[k] != must:
            raise ValueError(f"dots_vlm does not compute {k}={config[k]!r} (it computes {must!r})")
    h = int(config["num_attention_heads"])
    if int(config.get("num_key_value_heads", h)) != h:
        raise ValueError("latent attention has one latent a position and as many key heads as query heads: num_key_value_heads == num_attention_heads")
    nope, rope = int(config["qk_nope_head_dim"]), int(config["qk_rope_head_dim"])
    scaling = config.get("rope_scaling") or None
    if scaling and scaling.get("type", scaling.get("rope_type")) != "yarn":
        raise ValueError(f"dots_vlm computes YaRN or no rope scaling, not {scaling!r}")
    yarn = None
    if scaling:
        yarn = tuple(float(scaling[k]) for k in ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale", "mscale_all_dim"))
    held = int(config["n_routed_experts"])
    E = int(config.get("reduced_from", {}).get("n_routed_experts", held))
    rank = int(config.get("assumed", {}).get("expert_rank", {}).get("value", 0))
    G, keep = int(config.get("n_group", 1)), int(config.get("topk_group", 1))
    if held * (rank + 1) > E or E % G or not 0 < keep <= G:
        raise ValueError(f"rank {rank}'s {held} experts of {E} in {G} groups, {keep} kept")
    softmax_scale = (nope + rope) ** -0.5 * (_mscale(yarn[0], yarn[5]) ** 2 if yarn else 1.0)  # [P] the temperature under YaRN
    return {
        "d": int(config["hidden_size"]),
        "f_dense": int(config["intermediate_size"]),
        "f": int(config["moe_intermediate_size"]),  # ONE expert's width
        "f_shared": int(config.get("n_shared_experts", 0)) * int(config["moe_intermediate_size"]),
        "h": h, "nope": nope, "rope": rope, "v": int(config["v_head_dim"]),
        "c": int(config["kv_lora_rank"]), "r": int(config["q_lora_rank"]),
        "L": int(config["num_hidden_layers"]),
        "dense": int(config.get("first_k_dense_replace", 0)),
        "V": int(config["vocab_size"]),
        "E": E, "held": held, "first": rank * held, "k": int(config["num_experts_per_tok"]), "G": G, "keep": keep,
        "route_scale": float(config.get("routed_scaling_factor", 1.0)),
        "theta": float(config["rope_theta"]),
        "yarn": yarn,
        "scale": softmax_scale,
        "eps": float(config["rms_norm_eps"]),
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
        rope_scaling=("yarn", *m["yarn"]) if m["yarn"] else (), rope_theta=m["theta"],
        d_ff=m["f"], n_experts=m["E"], n_experts_per_tok=m["k"], norm_topk_prob=True, router_score="sigmoid",
        route_scale=m["route_scale"], d_ff_shared=m["f_shared"], n_dense_layers=m["dense"], d_ff_dense=m["f_dense"],
        n_experts_held=m["held"], first_expert=m["first"], n_group=m["G"], topk_group=m["keep"],
        max_seq_len=int(config["max_position_embeddings"]), norm_eps=m["eps"], tie_embeddings=False,
        dtype={"bfloat16": jnp.bfloat16, "float32": jnp.float32}[config.get("torch_dtype", "bfloat16")],
        # The whole-sequence forward runs the expanded form as a plain masked expression: the flash kernels refuse it.
        attn_impl="naive",
    )
    kw.update(overrides)
    return tfm.TransformerConfig(**kw)


# ----------------------------------------------------- the plain reference

F32 = jnp.float32
ROW_BLOCK = 2048  # rows of a layer computed at a time (the expansions are made anew for each)
Q_BLOCK = 128  # query rows per block of scores: [HEAD_BLOCK, 128, keys] float32
HEAD_BLOCK = 4  # heads whose keys and values are expanded at a time: 2 x [keys, 4, 128] float32
# With these, compiled for v5e at 24 960 tokens: 1.69 GiB of temporaries (2.25 at 256 rows x 8 heads; 3.76 with each
# layer's weights sliced out whole), beside 13.27 GiB of weights and pages in 15.75 (my AOT compile, PR 50).
F_BLOCK = 2048  # columns of a SwiGLU's matrices upcast at a time
VOCAB_SLICE = 4096  # most columns of the head upcast at a time
EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def _f32(w):
    return w.astype(F32)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale.astype(F32)


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
    return f / factor * ramp + f * (1.0 - ramp)  # [P]


def _rope(x, positions, m: Dict):
    """x [s, heads, rope] at `positions` [s]; rotate-half pairs (i, i + rope / 2)."""
    half = m["rope"] // 2
    ang = positions.astype(F32)[:, None] * _inv_freq(m)[None, :]
    magnitude = _mscale(m["yarn"][0], m["yarn"][4]) / _mscale(m["yarn"][0], m["yarn"][5]) if m["yarn"] else 1.0
    cos, sin = jnp.cos(ang)[:, None, :] * magnitude, jnp.sin(ang)[:, None, :] * magnitude
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _row_blocks(s: int) -> Tuple[int, int]:
    """(rows a block, blocks) that cover s rows; a block is whole Q_BLOCKs where it is more than one."""
    block = s if s <= Q_BLOCK else min(ROW_BLOCK, -(-s // Q_BLOCK) * Q_BLOCK)
    return block, -(-s // block)


def _cut(w, lead, start, size):
    """w[*lead, start[0] : start[0] + size[0], ...] as float32: ONE slice of the array as stored, taken where it is used,
    so that no layer's, head block's or expert's weights are copied out whole beside 14 GB of weights and pages."""
    lead = tuple(jnp.asarray(i, jnp.int32) for i in lead)
    begin = lead + tuple(jnp.asarray(i, jnp.int32) for i in start)
    return _f32(jax.lax.dynamic_slice(w, begin, (1,) * len(lead) + tuple(size)).reshape(size))


def _latents(x, w, m: Dict):
    """Every position's normed latent and rotated key part, x [S, d] -> (c_kv [S, c], k_r [S, rope]), a block of rows at a time."""
    a, c = w["attn"], m["c"]
    block, n = _row_blocks(x.shape[0])

    def rows(i):
        hn = _rms_norm(jax.lax.dynamic_slice_in_dim(x, i * block, block), w["attn_norm"]["scale"], m["eps"])
        kv = hn @ _f32(a["wkv_a"])
        c_kv = _rms_norm(kv[:, :c], a["kv_a_norm"]["scale"], m["eps"])  # [P] the latent's own norm
        k_r = _rope(kv[:, None, c:], i * block + jnp.arange(block), m)[:, 0]  # [P] one key part for all heads
        return c_kv, k_r

    c_kv, k_r = jax.lax.map(rows, jnp.arange(n))
    return c_kv.reshape(n * block, c), k_r.reshape(n * block, m["rope"])


def _attention(q_nope, q_rope, k_nope, v, k_r, q0, m: Dict):
    """A block of heads of a block of rows against every position: q_nope [rows, hb, nope], q_rope [rows, hb, rope] at
    positions q0.., k_nope [S, hb, nope], v [S, hb, v], k_r [S, rope] -> [rows, hb * v]; Q_BLOCK rows of scores at a time."""
    rows, hb, _ = q_nope.shape
    block = min(Q_BLOCK, rows)

    def one_block(i):
        qn, qr = jax.lax.dynamic_slice_in_dim(q_nope, i * block, block), jax.lax.dynamic_slice_in_dim(q_rope, i * block, block)
        scores = (jnp.einsum("qhn,khn->hqk", qn, k_nope) + jnp.einsum("qhr,kr->hqk", qr, k_r)) * m["scale"]
        seen = (q0 + i * block + jnp.arange(block))[:, None] >= jnp.arange(k_r.shape[0])[None, :]
        return jnp.einsum("hqk,khv->qhv", jax.nn.softmax(jnp.where(seen[None], scores, -jnp.inf), axis=-1), v)

    return jax.lax.map(one_block, jnp.arange(rows // block)).reshape(rows, hb * m["v"])


def _mixer(hn, w, group, layer, c_kv, k_r, q0, m: Dict):
    """Attn of a block of rows hn [rows, d] at positions q0.. over every position's latents, HEAD_BLOCK heads at a time,
    their weights cut out of the group's stacked `attn` leaves at `layer`."""
    rows, hb = hn.shape[0], min(HEAD_BLOCK, m["h"])
    c_q = _rms_norm(hn @ _f32(w["attn"]["wq_a"]), w["attn"]["q_a_norm"]["scale"], m["eps"])
    wide, a = m["nope"] + m["rope"], group["attn"]

    def some_heads(j, acc):
        q = (c_q @ _cut(a["wq_b"], (layer,), (0, j * hb * wide), (m["r"], hb * wide))).reshape(rows, hb, wide)
        q_nope, q_rope = q[..., : m["nope"]], _rope(q[..., m["nope"]:], q0 + jnp.arange(rows), m)
        # the expanded form: every position's keys and values, for these heads
        k_nope = jnp.einsum("sc,hnc->shn", c_kv, _cut(a["w_uk"], (layer,), (j * hb, 0, 0), (hb, m["nope"], m["c"])))
        v = jnp.einsum("sc,hcv->shv", c_kv, _cut(a["w_uv"], (layer,), (j * hb, 0, 0), (hb, m["c"], m["v"])))
        return acc + _attention(q_nope, q_rope, k_nope, v, k_r, q0, m) @ _cut(a["wo"], (layer,), (j * hb * m["v"], 0), (hb * m["v"], m["d"]))

    return jax.lax.fori_loop(0, m["h"] // hb, some_heads, jnp.zeros_like(hn))


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
    ranked = scores + _f32(mlp["router_bias"])  # [P] the bias selects; it never weighs
    groups = ranked.reshape(ranked.shape[0], m["G"], -1)
    group_score = jnp.sum(jnp.sort(groups, axis=-1)[..., -2:], axis=-1)  # [P] a group's two largest
    ahead = (group_score[:, None, :] > group_score[:, :, None]) | (
        (group_score[:, None, :] == group_score[:, :, None]) & (jnp.arange(m["G"])[None, None, :] < jnp.arange(m["G"])[None, :, None]))
    kept = jnp.sum(ahead, axis=-1) < m["keep"]  # a group stays if fewer than `keep` groups are ahead of it
    ranked = jnp.where(jnp.repeat(kept, m["E"] // m["G"], axis=-1), ranked, -jnp.inf)  # [P] no expert of another group
    top_e = jax.lax.top_k(ranked, m["k"])[1]
    top_s = jnp.take_along_axis(scores, top_e, axis=-1)
    top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) * m["route_scale"]  # over the chosen, held or not
    return jnp.sum(jax.nn.one_hot(top_e, m["E"], dtype=F32) * top_s[..., None], axis=1)


def _experts(hn, weights, mlp, layer, m: Dict):
    """sum over the HELD experts e of weights[:, first + e] * SwiGLU_e(hn),
    one expert at a time, cut out of the routed group's stack at [layer, e].
    What the absent experts would add is left out."""

    def add_expert(e, acc):
        return acc + jax.lax.dynamic_index_in_dim(weights, m["first"] + e, axis=1) * _swiglu(hn, mlp, (layer, e))

    return jax.lax.fori_loop(0, m["held"], add_expert, jnp.zeros_like(hn))


def _ffn(hn, w, group, layer, m: Dict):
    mlp = group["mlp"]
    if "router" not in mlp:  # a leading dense layer
        return _swiglu(hn, mlp, (layer,))
    return _experts(hn, _router_weights(hn, w["mlp"], m), mlp, layer, m) + _swiglu(hn, mlp["shared"], (layer,))


def _layer(x, group, layer: int, m: Dict):
    """Layer `layer` of a stacked group on x [S, d], S whole row blocks: the
    latents of every position first, then the rows a block at a time, written
    back in place. `w`: the layer's small leaves (norms, the down-projections,
    the router); the large ones are cut out of `group` where they are used."""
    small = {"attn_norm": group["attn_norm"], "mlp_norm": group["mlp_norm"],
             "attn": {k: group["attn"][k] for k in ("wq_a", "q_a_norm", "wkv_a", "kv_a_norm")},
             "mlp": {k: group["mlp"][k] for k in ("router", "router_bias") if k in group["mlp"]}}
    w = jax.tree_util.tree_map(lambda a: a[layer], small)
    c_kv, k_r = _latents(x, w, m)
    block, n = _row_blocks(x.shape[0])

    def rows(i, x):
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block)
        xb = xb + _mixer(_rms_norm(xb, w["attn_norm"]["scale"], m["eps"]), w, group, layer, c_kv, k_r, i * block, m)
        xb = xb + _ffn(_rms_norm(xb, w["mlp_norm"]["scale"], m["eps"]), w, group, layer, m)
        return jax.lax.dynamic_update_slice_in_dim(x, xb, i * block, axis=0)

    return jax.lax.fori_loop(0, n, rows, x)


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32."""
    with jax.default_matmul_precision("highest"):
        s = tokens.shape[0]
        block, n = _row_blocks(s)
        # rows past s are padding: causal, so no row below s sees them, and they are cut off at the end
        x = _f32(params["embed"]["embedding"][jnp.pad(tokens, (0, n * block - s))])
        for layer in range(m["L"]):
            group, i = ("dense_blocks", layer) if layer < m["dense"] else ("blocks", layer - m["dense"])
            x = _layer(x, params[group], i, m)
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


def attention_params(config: Dict[str, Any]) -> int:
    """One layer's latent attention: W_DQ, W_UQ, W_DKV, W_UK and W_UV, W_O."""
    m = dims(config)
    return (m["d"] * m["r"] + m["r"] * m["h"] * (m["nope"] + m["rope"]) + m["d"] * (m["c"] + m["rope"])
            + m["c"] * m["h"] * (m["nope"] + m["v"]) + m["h"] * m["v"] * m["d"])


def matmul_params(config: Dict[str, Any]) -> int:
    """Matmul parameters a decode step reads: every layer's attention, the
    leading dense layers' FFN, every routed layer's router, shared expert and
    HELD experts, and the head (the embedding is a gather; norms and the
    selecting bias are left out: under a thousandth of it)."""
    m = dims(config)
    routed = m["L"] - m["dense"]
    moe = m["d"] * m["E"] + 3 * m["d"] * m["f_shared"] + m["held"] * expert_params(config)
    return m["L"] * attention_params(config) + m["dense"] * 3 * m["d"] * m["f_dense"] + routed * moe + m["d"] * m["V"]


def latent_bytes_per_token_layer(config: Dict[str, Any]) -> float:
    """`[c_kv | k_r]` of one cached position of ONE layer, as it must be read (the pages pad it to whole lane tiles)."""
    m = dims(config)
    return float((m["c"] + m["rope"]) * m["bytes_per_param"])


def decode_latent_bytes(config: Dict[str, Any], kv_tokens: float) -> float:
    """Latent rows one decode step must read: every live position's, every layer's, once."""
    return float(kv_tokens * dims(config)["L"] * latent_bytes_per_token_layer(config))


def decode_step_min_bytes(config: Dict[str, Any], live_seqs: int, kv_tokens: int) -> float:
    """What one decode step must move: every weight held here once (every
    held expert: 32 rows x 8 picks over 16 of 256 experts touch nearly all,
    and in the deployment, 512 rows, all of them) and its rows' latents once."""
    return float(matmul_params(config) * dims(config)["bytes_per_param"]) + decode_latent_bytes(config, kv_tokens)


def decode_expert_products(config: Dict[str, Any], experts_touched_a_step: float) -> Dict[str, Any]:
    """What tells a decode step's expert products in a trace, and what ONE
    expert matrix stack read by them costs (`readers/trace_expert_products.py`;
    afmoe.py's, under a share as solar_open2.py's): `stacks` [routed layers,
    held, ., .]; `rows` [held, slots, f]; `needed` over the held experts the
    step's rows TOUCHED, `streamed` over all the held ones."""
    m = dims(config)
    slots, routed = int(config["assumed"]["max_slots"]["value"]), m["L"] - m["dense"]
    matrix = m["d"] * m["f"]
    return {
        "stacks": [[routed, m["held"], m["d"], m["f"]], [routed, m["held"], m["f"], m["d"]]],
        "rows": [m["held"], slots, m["f"]],
        "needed": (2.0 * slots * m["k"] * m["held"] / m["E"] * matrix, float(m["bytes_per_param"] * experts_touched_a_step / routed * matrix)),
        "streamed": (2.0 * slots * m["held"] * matrix, float(m["bytes_per_param"] * m["held"] * matrix)),
        "rows_in_bytes": float(m["bytes_per_param"] * slots * m["d"]),
    }


def _absorbed_pair_flops(m: Dict) -> float:
    """One (query row, cached position) pair of ONE layer, absorbed: every head's score over `[c_kv | k_r]` and its sum over `c_kv`."""
    return 2.0 * m["h"] * (m["c"] + m["rope"] + m["c"])


def _expanded_pair_flops(m: Dict) -> float:
    """The same pair expanded: every head's score over `[k_nope | k_r]` and its sum over `v`."""
    return 2.0 * m["h"] * (m["nope"] + m["rope"] + m["v"])


def latent_decode_work(config: Dict[str, Any], live: int = 0, kv_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the latent attention of ONE decode step over all
    layers (`paged_latent_attention_decode`): `kv_tokens` cached positions,
    each read once and attended by its own row's heads, absorbed."""
    m = dims(config)
    return m["L"] * kv_tokens * _absorbed_pair_flops(m), decode_latent_bytes(config, kv_tokens)


def latent_prefill_work(config: Dict[str, Any], prompt_tokens: int = 0, cached_tokens: int = 0, **_) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) of the latent attention of ONE prefill call over all
    layers (`paged_latent_attention_prefill`), the LEAST of the two forms:
    the rows [cached, prompt) against the positions below each. Absorbed: the
    pairs at 2 x heads x (2 kv_lora + rope), nothing expanded. Expanded: the
    pairs at 2 x heads x (nope + rope + v) and every position of the prompt
    expanded once a layer (2 x kv_lora x heads x (nope + v)). The bytes: the
    prompt's latent rows once."""
    m = dims(config)
    n, first = prompt_tokens, min(cached_tokens, prompt_tokens)
    pairs = (n * (n + 1) - first * (first + 1)) / 2.0
    absorbed = pairs * _absorbed_pair_flops(m)
    expanded = pairs * _expanded_pair_flops(m) + n * 2.0 * m["c"] * m["h"] * (m["nope"] + m["v"])
    return m["L"] * min(absorbed, expanded), decode_latent_bytes(config, n)


def train_flops_per_token(config: Dict[str, Any], seq_len: int) -> float:
    """Forward + backward, no recomputation: 6 x the matmul parameters a token
    passes through (of its k picks, the k x held / E expected on held
    experts), plus the expanded attention (seq / 2 visible). No cell trains it."""
    m = dims(config)
    active = matmul_params(config) - (m["L"] - m["dense"]) * (m["held"] - m["k"] * m["held"] / m["E"]) * expert_params(config)
    return 6.0 * active + 3 * m["L"] * _expanded_pair_flops(m) * seq_len / 2


def kernels(config: Dict[str, Any], batch: int, seq_len: int) -> Dict[str, Tuple[float, float]]:
    """{kind: (FLOPs, HBM bytes)} of ONE call a layer of the kernels a served
    latent layer runs: `paged_latent_attention_decode`, a step of `batch` rows
    at `seq_len` cached positions each, and `paged_latent_attention_prefill`,
    a miss of `seq_len` positions (`readers/trace_latent_roofline.py` reads
    the steps' and the calls' own sizes through `latent_decode_work` and
    `latent_prefill_work`)."""
    L = dims(config)["L"]
    decode, prefill = latent_decode_work(config, batch, batch * seq_len), latent_prefill_work(config, seq_len, 0)
    return {"paged_latent_attention_decode": (decode[0] / L, decode[1] / L), "paged_latent_attention_prefill": (prefill[0] / L, prefill[1] / L)}
