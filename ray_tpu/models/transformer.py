"""Flagship decoder-only transformer (Llama-2 family), TPU-first.

The reference ships no model code — its training path wraps torch models in
DDP/FSDP (reference: python/ray/train/torch/train_loop_utils.py:162
prepare_model) and its LLM benchmarks delegate to DeepSpeed user code
(reference: release/air_examples/gptj_deepspeed_finetuning/). The TPU-native
framework instead provides first-class model implementations, because model
structure and sharding layout must be co-designed for the MXU/ICI:

- layers are STACKED and iterated with `lax.scan` -> compile time is O(1)
  in depth (one layer traced once), and stacked params shard with a single
  right-aligned rule (see ray_tpu.parallel.sharding);
- all matmuls run in bfloat16 with fp32 accumulation
  (`preferred_element_type`) to hit the MXU at full rate;
- attention is pluggable: "full" (single device / tensor-parallel),
  "ring" (ICI ring over the "seq" axis) or "ulysses" (all-to-all head
  resharding) for long-context;
- `jax.checkpoint` (remat) trades FLOPs for HBM when activations dominate.

Pure functional: params are a plain pytree; there is no module system to
fight the jit tracer.
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from functools import partial
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ..parallel.ring_attention import attention_reference, ring_attention
from ..parallel.ulysses import ulysses_attention

PyTree = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    d_model: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    d_ff: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16
    attn_impl: str = "full"  # "full" (fused/flash) | "naive" | "ring" | "ulysses"
    remat: bool = True
    # "dots": save matmul outputs, recompute only elementwise ops on the
    # backward pass (jax.checkpoint_policies) — the right default on TPU
    # where HBM usually fits the dots and recomputing matmuls wastes MXU.
    # None: save nothing (lowest memory, recompute everything).
    remat_policy: Optional[str] = "dots"
    tie_embeddings: bool = False
    # Architecture switches covering the GPT-J family (reference workload:
    # release/air_examples/gptj_deepspeed_finetuning/): "gelu" MLP has no
    # gate projection; parallel_block computes attention and MLP from ONE
    # pre-norm and sums both into the residual (GPT-J's ln_1-only block).
    mlp_act: str = "swiglu"  # "swiglu" | "gelu"
    parallel_block: bool = False
    # GPT-J applies RoPE to only the first rotary_dim dims of each head
    # (64 of 256); None rotates the full head (llama). norm_type "layer"
    # mean-centers before scaling (GPT-J's LayerNorm, bias unmodeled);
    # "rms" is llama's RMSNorm.
    rotary_dim: Optional[int] = None
    norm_type: str = "rms"  # "rms" | "layer"
    rope_style: str = "half"  # "half" (llama rotate-half) | "interleaved" (GPT-J)
    # Routed FFN (OLMoE family): n_experts > 0 replaces the dense MLP by
    # n_experts SwiGLU experts of width d_ff each, of which every token
    # takes its n_experts_per_tok most probable (dropless: no capacity, no
    # dropped token). norm_topk_prob renormalises the chosen probabilities
    # to sum to 1. qk_norm: an RMSNorm with its own scale over the WHOLE q
    # and k projections, between the projections and rope.
    n_experts: int = 0
    n_experts_per_tok: int = 0
    norm_topk_prob: bool = False
    qk_norm: bool = False
    # Trinity (afmoe) family, each off by default. d_head: a head's size where
    # it is not d_model / n_heads. qk_norm_per_head: the q/k RMSNorm runs over
    # each head's dims with one scale of head_dim. router_score "sigmoid":
    # independent scores; the experts are chosen by score + a per-expert bias
    # that only selects, weighted by the scores themselves (renormalised under
    # norm_topk_prob) times route_scale. d_ff_shared: width of a SwiGLU expert
    # that every token passes through beside the routed ones. The first
    # n_dense_layers layers have a dense SwiGLU of width d_ff_dense in the
    # routed FFN's place (a stacked group of their own, params["dense_blocks"]).
    # windows: per layer, how many positions back a query sees (0: all of them);
    # rope_layers: per layer, whether q and k are rotated. Both empty or as long
    # as the stack; they ride the layer scans, so one compiled body serves every
    # layer of a group. attn_gate: the attention output times
    # sigmoid(h Wg) before Wo. post_norms: a norm on each sublayer's output
    # before the residual add. embed_scale: embeddings times sqrt(d_model).
    d_head: Optional[int] = None
    qk_norm_per_head: bool = False
    router_score: str = "softmax"  # "softmax" | "sigmoid"
    route_scale: float = 1.0
    d_ff_shared: int = 0
    n_dense_layers: int = 0
    d_ff_dense: int = 0
    windows: Tuple[int, ...] = ()
    rope_layers: Tuple[bool, ...] = ()
    attn_gate: bool = False
    post_norms: bool = False
    embed_scale: bool = False
    # Power retention (Brumby family) in softmax attention's place, off at 0;
    # 2 is the one degree computed. A layer then attends by
    # a_ts = exp(L_t - L_s) (q_t . k_s / sqrt(head_dim))^2, normalised by its
    # own sum + RETENTION_EPS, L the running sum of the gate's log-sigmoid
    # (`wg`, one logit a K/V head); no softmax, mask beyond s <= t or window.
    # What a served sequence keeps of its past is then no K/V but one state,
    # read and written whole every decode step: the "retention" row of `KINDS`.
    retention_degree: int = 0
    # One chip's share of an expert-parallel deployment's experts, off at 0
    # (all held): the experts [first_expert, first_expert + n_experts_held) of
    # every routed layer are here. The router keeps its n_experts outputs and
    # its top-k over all of them, renormalised over the k chosen whether held
    # or not; the expert stacks are [layers, held, ., .] and a token's result
    # is the sum over its chosen experts that are held. What the absent ones
    # would add is left out: that partial sum goes on to the next layer.
    n_experts_held: int = 0
    first_expert: int = 0
    # Solar-Open2 family: the stack is periods of one full layer (softmax
    # attention with this config's heads, gate and all; latent attention where
    # kv_lora_rank is set: see `full_layers` below) followed by kda_per_period Kimi
    # Delta Attention layers (ops/kda.py: n_heads heads of head_dim key and
    # value channels, a short convolution of kda_conv taps on q, k and v, a
    # per-channel decay through a thin pair of matrices of rank head_dim, a
    # delta rule with beta in (0, 2), an output norm a head and a thin gate).
    # Off at 0. The periods are one scan whose body is the period's layers in
    # order: `blocks` holds the softmax layers [periods, ...], `kda_blocks`
    # the others [periods, kda_per_period, ...]. Served, a sequence has two
    # caches: K/V pages of the softmax layers alone and, in one of
    # `state_slots` slots, what the "kda" row of `KINDS` keeps of the others.
    kda_per_period: int = 0
    kda_conv: int = 4
    state_slots: int = 0
    # Latent attention (the DeepSeek-V2 / V3 family's MLA) in softmax
    # attention's place, off at kv_lora_rank 0. A head's query is q_lora_rank
    # -> (qk_nope_dim | qk_rope_dim) behind an RMSNorm; keys and values come
    # from ONE latent a position, c_kv (kv_lora_rank, behind an RMSNorm), which
    # `w_uk` / `w_uv` expand into a head's qk_nope_dim key part and v_head_dim
    # value; one rotated key part of qk_rope_dim is shared by all heads. What
    # a served sequence keeps is `[c_kv | k_r]` a position: the "latent" row of
    # `KINDS`. d_head is the query head, qk_nope_dim + qk_rope_dim.
    kv_lora_rank: int = 0
    q_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0
    # Rope frequency scaling, off when empty: ("yarn", factor, original
    # context, beta_fast, beta_slow, mscale, mscale_all_dim), see `_rope_freqs`.
    rope_scaling: Tuple = ()
    # Group-limited selection of a sigmoid router's experts (`_router_probs`),
    # off at n_group 1: the experts lie in n_group groups, of which a token
    # keeps the topk_group best before its top-k.
    n_group: int = 1
    topk_group: int = 1
    # GigaChat3.5 family, each off by default. A KDA stack's full layers may be
    # latent-attention layers (kv_lora_rank; `attn_gate` then gates them too,
    # `wg` [d, n_heads * v_head_dim], between `w_uv` and `wo`), and leading
    # dense layers may stand before its periods: `full_layers` names the full
    # layers in published order (empty: one at the head of every period), a
    # dense layer in it is a full layer, one not in it a delta-rule layer.
    # kda_decay "head": the delta-rule layers take the gated-delta-rule form
    # (Gated DeltaNet): ONE decay a head through `w_a` [d, n_heads], beta =
    # sigmoid in (0, 1), kda_key_heads key heads each read by n_heads /
    # kda_key_heads value heads (0: as many), a full-width output gate
    # 2 sigmoid(h `w_z`); the recurrence, the state and the kernel are the
    # "kda" row's, the decay broadcast over a head's channels. kda_head_dim: a
    # delta-rule head's d_k = d_v where it is not the full layers' d_head.
    # norm_gate c: every norm's scale is c * sigmoid(w) (a zero-centred gated
    # norm: w = 0 scales by c / 2), the latent layer's two inner norms too.
    # swiglu_limit a: SwiGLU as silu(min(gate, a)) * clip(up, -a, a), dense,
    # shared and routed alike.
    full_layers: Tuple[int, ...] = ()
    kda_decay: str = "channel"  # "channel" | "head"
    kda_key_heads: int = 0
    kda_head_dim: int = 0
    norm_gate: float = 0.0
    swiglu_limit: float = 0.0
    # MiMo-V2 family, each off by default. window_kv_heads: the window layers
    # (`windows` > 0) have that many K/V heads where the others have
    # n_kv_heads, and a served sequence keeps of each of them only a RING of
    # its window's positions in a state slot (the "window" row of `KINDS`)
    # beside the K/V pages of the layers that see everything. The stack is then
    # ONE leading dense layer without a window and whole periods of alike window
    # layers that end on a layer without one (`stack_plan`). At 0 a window
    # layer is a softmax layer whose window rides the scan and whose every
    # position is paged (Trinity). window_rope_theta: rope's base on the window
    # layers (0: rope_theta). window_sink: a learned logit a query head
    # (`sink`, float32) in the denominator of a window layer's softmax:
    # p_ij = exp(s_ij) / (exp(sink) + sum_j exp(s_ij)). value_scale: v times
    # it, behind its projection. v_head_dim (above) then also says how wide a
    # softmax or window head's values are where its keys are d_head.
    window_kv_heads: int = 0
    window_rope_theta: float = 0.0
    window_sink: bool = False
    value_scale: float = 1.0

    @property
    def head_dim(self) -> int:
        return self.d_head or self.d_model // self.n_heads

    @property
    def value_dim(self) -> int:
        """A softmax or window head's values: as wide as its keys unless v_head_dim says otherwise."""
        return self.v_head_dim or self.head_dim

    @property
    def experts_held(self) -> int:
        return self.n_experts_held or self.n_experts

    def replace(self, **kw) -> "TransformerConfig":
        return dataclasses.replace(self, **kw)


def llama2_7b(**overrides) -> TransformerConfig:
    return TransformerConfig().replace(**overrides)


def llama2_13b(**overrides) -> TransformerConfig:
    return TransformerConfig(
        d_model=5120, n_layers=40, n_heads=40, n_kv_heads=40, d_ff=13824
    ).replace(**overrides)


def gpt_j_6b(**overrides) -> TransformerConfig:
    """GPT-J-6B config (the reference's DeepSpeed finetune workload,
    reference: release/air_examples/gptj_deepspeed_finetuning/): gelu MLP
    (no gate), parallel attention+MLP block. Biases are not modeled (the
    HF loader folds what it can and documents the rest)."""
    return TransformerConfig(
        vocab_size=50400, d_model=4096, n_layers=28, n_heads=16, n_kv_heads=16,
        d_ff=16384, rope_theta=10000.0, mlp_act="gelu", parallel_block=True,
        rotary_dim=64, norm_type="layer", rope_style="interleaved",
    ).replace(**overrides)


def tiny(**overrides) -> TransformerConfig:
    """CI-sized config (runs on the 8-device CPU mesh in seconds)."""
    return TransformerConfig(
        vocab_size=256, d_model=64, n_layers=2, n_heads=4, n_kv_heads=2,
        d_ff=128, max_seq_len=128, remat=False,
    ).replace(**overrides)


# ------------------------------------------------------------------ params


def init_params(key: jax.Array, cfg: TransformerConfig) -> PyTree:
    """Stacked-layer param pytree; paths match
    ray_tpu.parallel.sharding.TRANSFORMER_RULES (right-aligned for the
    leading n_layers dim). `blocks` holds the stack, or, behind
    cfg.n_dense_layers leading dense layers (`dense_blocks`), the rest of it."""
    hd, nh, nkv = cfg.head_dim, cfg.n_heads, cfg.n_kv_heads
    d, v, E = cfg.d_model, cfg.vocab_size, cfg.n_experts
    if E and cfg.mlp_act != "swiglu":
        raise ValueError("routed experts are SwiGLU")
    if cfg.router_score not in ("softmax", "sigmoid"):
        raise ValueError(f"router_score {cfg.router_score!r} is neither softmax nor sigmoid")
    for name in ("windows", "rope_layers"):
        if len(getattr(cfg, name)) not in (0, cfg.n_layers):
            raise ValueError(f"{name} has {len(getattr(cfg, name))} entries for {cfg.n_layers} layers")
    if cfg.n_dense_layers and not (E and 0 < cfg.n_dense_layers < cfg.n_layers and cfg.d_ff_dense):
        raise ValueError("leading dense layers go before routed ones and need d_ff_dense")
    if cfg.retention_degree not in (0, 2):
        raise ValueError(f"retention_degree {cfg.retention_degree!r}: 0 (softmax attention) or 2 is computed")
    if cfg.retention_degree and (cfg.attn_gate or any(cfg.windows) or nh % nkv or hd % 2):
        raise ValueError("power retention has a gate of its own (`wg`: a logit a K/V head) and no window")
    held = cfg.experts_held
    if not 0 <= cfg.first_expert <= E - held:
        raise ValueError(f"experts [{cfg.first_expert}, {cfg.first_expert + held}) are not among the router's {E}")
    per, nd = cfg.kda_per_period, cfg.n_dense_layers
    if per:
        heads_of_periods = tuple(range(nd, cfg.n_layers, per + 1))
        full = cfg.full_layers or heads_of_periods
        if ((cfg.n_layers - nd) % (per + 1) or tuple(i for i in full if i >= nd) != heads_of_periods
                or len([i for i in full if i < nd]) not in (0, nd) or any(cfg.windows) or cfg.retention_degree or cfg.parallel_block):
            raise ValueError("a KDA stack is leading dense layers of ONE kind, then whole periods of (one full layer, kda_per_period delta-rule layers): "
                             "no other list of full layers, no stack that ends inside a period, no window, retention or parallel block")
        if not cfg.kv_lora_rank and (any(cfg.rope_layers) or not cfg.rope_layers):
            raise ValueError("a KDA stack whose periods are headed by a softmax layer has no rope (rope_layers all off): rope in such a period is computed against no reference yet")
        if cfg.kda_decay not in ("channel", "head") or cfg.n_heads % (cfg.kda_key_heads or cfg.n_heads) or (cfg.kda_decay == "channel" and cfg.kda_key_heads):
            raise ValueError(f"kda_decay {cfg.kda_decay!r} with {cfg.kda_key_heads} key heads: 'channel' (as many key heads as heads) or 'head' (key heads that divide the {cfg.n_heads} heads)")
    if cfg.kv_lora_rank and not (cfg.q_lora_rank and cfg.qk_nope_dim and cfg.qk_rope_dim and cfg.v_head_dim
                                 and cfg.head_dim == cfg.qk_nope_dim + cfg.qk_rope_dim and cfg.qk_rope_dim % 2 == 0):
        raise ValueError("latent attention needs q_lora_rank, qk_nope_dim, qk_rope_dim (even), v_head_dim, and d_head their query head qk_nope_dim + qk_rope_dim")
    if cfg.kv_lora_rank and (cfg.retention_degree or cfg.qk_norm or any(cfg.windows) or cfg.rope_layers):
        raise ValueError("a latent-attention layer has no window, q/k-norm, rope switch or retention (a gate it may have, and delta-rule layers beside it in a KDA stack's periods)")
    if cfg.rope_scaling and (cfg.rope_scaling[0] != "yarn" or len(cfg.rope_scaling) != 7):
        raise ValueError(f"rope_scaling {cfg.rope_scaling!r}: ('yarn', factor, original context, beta_fast, beta_slow, mscale, mscale_all_dim) is computed")
    if cfg.n_group > 1 and (cfg.router_score != "sigmoid" or E % cfg.n_group or not 0 < cfg.topk_group <= cfg.n_group or E // cfg.n_group < 2):
        raise ValueError("group-limited selection: a sigmoid router whose experts divide into n_group groups of at least two, topk_group of them kept")
    if cfg.window_kv_heads:
        if _ring_period(cfg) is None:
            raise ValueError(
                f"windows {cfg.windows!r} under window_kv_heads: a stack with window rings is global first (ONE leading dense layer "
                "without a window: n_dense_layers 1), then whole periods of alike window layers that end on a global layer"
            )
        if (cfg.retention_degree or per or cfg.kv_lora_rank or cfg.qk_norm or cfg.attn_gate or cfg.rope_layers or cfg.parallel_block
                or nh % cfg.window_kv_heads or nh % nkv):
            raise ValueError("window rings stand beside plain softmax layers: no retention, delta-rule or latent layer, q/k-norm, gate, rope switch or parallel block; both K/V head counts divide the heads")
    elif cfg.window_rope_theta or cfg.window_sink or cfg.value_scale != 1.0 or (cfg.v_head_dim and not cfg.kv_lora_rank):
        raise ValueError("window_rope_theta, window_sink, value_scale and a softmax head's v_head_dim are computed in a stack with window rings (window_kv_heads) alone")
    k = iter(jax.random.split(key, 16))
    # What this model has over the llama and OLMoE blocks draws from a stream
    # of its own: theirs give the same weights for a key as before.
    k2 = iter(jax.random.split(jax.random.fold_in(key, 7), 16))

    def dense(key, shape, fan_in, dtype=cfg.dtype):
        return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dtype)

    def into_ffn(key, shape):
        """A SwiGLU's gate or up matrix [.., d, f]. Under a clamp (`swiglu_limit`) one column in sixteen is drawn eight
        times as wide: a trained model has such outlier channels and the clamp is there for them; at unit scale no
        pre-activation comes near a limit of 10, and a program that left the clamp out would pass every check."""
        w = dense(key, shape, d)
        return w * jnp.where(jnp.arange(shape[-1]) % 16 == 0, 8, 1).astype(w.dtype) if cfg.swiglu_limit else w

    # L below: a stack's leading shape, (layers,) or (periods, layers a period).
    def swiglu(k, L, f):
        return {
            "w_gate": into_ffn(next(k), (*L, d, f)),
            "w_up": into_ffn(next(k), (*L, d, f)),
            "w_down": dense(next(k), (*L, f, d), f),
        }

    def kda_attn(k, L):
        """A delta-rule layer's mixer (ops/kda.py), in the form `kda_decay`
        names. `a_log` and `dt_bias` as the family draws them, so that a
        seeded state remembers some tens of tokens: a state not carried over
        a chunk's border, or not cleared, shows in the logits."""
        hd, by_head = _kda_cfg(cfg).head_dim, cfg.kda_decay == "head"
        n, f32 = nh * hd, jnp.float32
        widths = dict(zip("qkv", _kda_widths(_kda_cfg(cfg))))
        dt = jnp.exp(jax.random.uniform(next(k), (*L, nh if by_head else n), f32, math.log(0.001), math.log(0.1)))
        return {
            "wq": dense(next(k), (*L, d, widths["q"]), d),
            "wk": dense(next(k), (*L, d, widths["k"]), d),
            "wv": dense(next(k), (*L, d, n), d),
            "wo": dense(next(k), (*L, n, d), n),
            **{"conv_" + name: dense(next(k), (*L, widths[name], cfg.kda_conv), cfg.kda_conv) for name in "qkv"},
            # the decay: a head's one logit, or a key channel's through a thin pair
            **({"w_a": dense(next(k), (*L, d, nh), d)} if by_head else
               {"w_fa": dense(next(k), (*L, d, hd), d), "w_fb": dense(next(k), (*L, hd, n), hd)}),
            "a_log": jnp.log(jax.random.uniform(next(k), (*L, nh), f32, 1.0, 16.0)),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus^-1
            "w_b": dense(next(k), (*L, d, nh), d),
            "o_norm": {"scale": jnp.ones((*L, hd), cfg.dtype)},
            # the output gate: full width, or a thin pair and its bias
            **({"w_z": dense(next(k), (*L, d, n), d)} if by_head else
               {"w_ga": dense(next(k), (*L, d, hd), d), "w_gb": dense(next(k), (*L, hd, n), hd), "b_g": dense(next(k), (*L, n), 4.0)}),
        }

    def latent_attn(k, L):
        """A latent-attention layer's mixer (`_mla_mixer`): the up-projections
        a head, `w_uk` [heads, qk_nope_dim, kv_lora_rank] and `w_uv` [heads,
        kv_lora_rank, v_head_dim], as the absorbed form multiplies them."""
        c, r = cfg.kv_lora_rank, cfg.q_lora_rank
        return {
            "wq_a": dense(next(k), (*L, d, r), d),
            "q_a_norm": {"scale": jnp.ones((*L, r), cfg.dtype)},
            "wq_b": dense(next(k), (*L, r, nh * hd), r),
            "wkv_a": dense(next(k), (*L, d, c + cfg.qk_rope_dim), d),
            "kv_a_norm": {"scale": jnp.ones((*L, c), cfg.dtype)},
            "w_uk": dense(next(k), (*L, nh, cfg.qk_nope_dim, c), c),
            "w_uv": dense(next(k), (*L, nh, c, cfg.v_head_dim), c),
            "wo": dense(next(k), (*L, nh * cfg.v_head_dim, d), nh * cfg.v_head_dim),
        }

    def blocks(k, k2, L, routed: bool, f: int, kind: str):
        """A stack of alike layers of `kind` (a row of `KINDS`); k2: the stream of what a family has over the llama and OLMoE blocks."""
        L = (L,) if isinstance(L, int) else L
        kda = kind == "kda"
        kvh, vd = cfg.window_kv_heads if kind == "window" else nkv, cfg.value_dim  # of a softmax, retention or window layer's own projections
        out = {
            "attn_norm": {"scale": jnp.ones((*L, d), cfg.dtype)},
            "attn": kda_attn(k, L) if kda else latent_attn(k, L) if kind == "latent" else {
                "wq": dense(next(k), (*L, d, nh * hd), d),
                "wk": dense(next(k), (*L, d, kvh * hd), d),
                "wv": dense(next(k), (*L, d, kvh * vd), d),
                "wo": dense(next(k), (*L, nh * vd, d), nh * vd),
            },
            "mlp_norm": {"scale": jnp.ones((*L, d), cfg.dtype)},
            "mlp": (
                {
                    "w_gate": into_ffn(next(k), (*L, held, d, f)),
                    "w_up": into_ffn(next(k), (*L, held, d, f)),
                    "w_down": dense(next(k), (*L, held, f, d), f),
                    "router": dense(next(k), (*L, d, E), d),
                }
                if routed
                else swiglu(k, L, f)
                if cfg.mlp_act == "swiglu"
                else {
                    "w_up": dense(next(k), (*L, d, f), d),
                    "w_down": dense(next(k), (*L, f, d), f),
                }
            ),
        }
        if cfg.qk_norm and not kda:
            qn, kn = (hd, hd) if cfg.qk_norm_per_head else (nh * hd, nkv * hd)
            out["attn"]["q_norm"] = {"scale": jnp.ones((*L, qn), cfg.dtype)}
            out["attn"]["k_norm"] = {"scale": jnp.ones((*L, kn), cfg.dtype)}
        if cfg.attn_gate and not kda:  # as wide as what it gates: the heads' outputs before `wo`
            out["attn"]["wg"] = dense(next(k2), (*L, d, nh * (cfg.v_head_dim if kind == "latent" else hd)), d)
        if cfg.retention_degree:
            out["attn"]["wg"] = dense(next(k2), (*L, d, nkv), d)
        if kind == "window" and cfg.window_sink:
            # A trained sink is non-zero; zeros would hide the term from every check: exp(0) = 1 beside some tens of keys.
            out["attn"]["sink"] = jax.random.normal(next(k2), (*L, nh), jnp.float32)
        if cfg.post_norms:
            out["post_attn_norm"] = {"scale": jnp.ones((*L, d), cfg.dtype)}
            out["post_mlp_norm"] = {"scale": jnp.ones((*L, d), cfg.dtype)}
        if routed and cfg.router_score == "sigmoid":
            # A trained bias is non-zero; zeros would hide the term from every
            # check. At this scale it changes about a third of a token's experts.
            # A group-limited router draws it at a tenth of that (one spacing
            # of the candidates' scores around the last pick): the bias is
            # there to level the experts' load, it also moves a group's score,
            # and at 0.1 an expert two deviations down is picked by under one
            # row of a 256-row chunk, so which experts of a chip's share a
            # chunk touched, and the bytes its grouped products read, followed
            # the seed (PERF.md §6, PR 50). The ungrouped routers keep theirs.
            out["mlp"]["router_bias"] = dense(next(k2), (*L, E), 100.0 if cfg.n_group == 1 else 1e4, jnp.float32)
        if routed and cfg.d_ff_shared:
            out["mlp"]["shared"] = swiglu(k2, L, cfg.d_ff_shared)
        return out

    kinds = {m.tree: m.kind for _, members in stack_plan(cfg) for m in members}  # each stacked tree's kind of layer
    ring_per = _ring_period(cfg) or 0
    periods = (cfg.n_layers - nd) // ((per or ring_per) + 1)
    params = {
        "embed": {"embedding": dense(next(k), (v, d), d)},
        "blocks": blocks(k, k2, periods, bool(E), cfg.d_ff, kinds["blocks"]),
        "final_norm": {"scale": jnp.ones((d,), cfg.dtype)},
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense(next(k), (d, v), d)

    def streams(*salts):  # from streams of their own: the other layers draw as any stack of theirs would
        return (iter(jax.random.split(jax.random.fold_in(key, salt), 32)) for salt in salts)

    if nd:
        params["dense_blocks"] = blocks(*(streams(17, 19) if kinds["dense_blocks"] == "kda" else (k2, k2)), nd, False, cfg.d_ff_dense, kinds["dense_blocks"])
    if per:
        params["kda_blocks"] = blocks(*streams(11, 13), (periods, per), bool(E), cfg.d_ff, "kda")
    if ring_per:
        params["window_blocks"] = blocks(*streams(23, 29), (periods, ring_per), bool(E), cfg.d_ff, "window")
    if cfg.norm_gate:  # c * sigmoid(w): w = 0 where a plain scale is 1 (a delta-rule layer's output norm keeps its plain scale)
        def gated(path) -> bool:
            return "norm" in path and "o_norm" not in path

        params = jax.tree_util.tree_map_with_path(lambda path, a: jnp.zeros_like(a) if gated(jax.tree_util.keystr(path)) else a, params)
    return params


def param_count(params: PyTree) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


# ------------------------------------------------------------------ layers


def _ckpt(val, name: str):
    """Tags a value for remat_policy="hot" (save_only_these_names): names
    mark the SAVED residual frontier; everything unnamed rematerializes.
    Exclusion-style policies cannot work here — checkpoint_name is an
    identity op, so "excluding" a named value just makes the partitioner
    save its unnamed producer instead (same bytes). Inclusion is the only
    reliable way to pin a bf16 save frontier."""
    from jax.ad_checkpoint import checkpoint_name

    return checkpoint_name(val, name)


# The save frontier for remat_policy="hot": small bf16 per-layer tensors
# (q/k/v post-rope, attention out, MLP input, MLP activation) + the flash
# kernel's o/lse (named in ops/flash_attention.py). Backward recomputes
# only the norms, rope on nothing (q/k/v are saved post-rope), and the
# gate/up MLP dots (~10% extra layer FLOPs) instead of the whole layer.
# The routed FFN saves what the router decided (moe_route: probabilities,
# chosen experts, the sort and its inverse, group sizes; a few MB) and the rows
# sorted by expert (moe_xs_bf16): its backward recomputes the grouped matmuls,
# never the top-k or the sorts; rows move back by gathers through that sort.
HOT_SAVE_NAMES = (
    "flash_o",
    "flash_lse",
    "q_bf16",
    "k_bf16",
    "v_bf16",
    "attn_out_bf16",
    "mlp_in_bf16",
    "mlp_act_bf16",
    "moe_route",
    "moe_xs_bf16",
)


# THE table of device-side names: every `jax.named_scope` this package opens,
# each with the work that lies under it (as `KINDS` is the one table of layer
# kinds). A scope is metadata of the compiled program (an op's `op_name` path)
# and nothing at run time: a device trace carries it per op (`tf_op`), with
# the phase beside it (`jvp(`: forward, `transpose(jvp(`: backward,
# `rematted_computation`: recomputation, neither: the update), and
# benchmarks/lib/xplane_meta.py and tools/device_scope_report.py split a
# step's device time by both. An op under nested scopes counts under the
# innermost. A new layer kind owes its scopes here beside its row in `KINDS`;
# tests/test_scopes.py holds the table to the source and the compiled steps
# to the table.
SCOPES = {
    "embed": "the token embeddings' gather (and its scale)",
    "norm": "`_norm`: a block's norms (post-norms too) and the final one",
    "attn.qkv": "the q, k, v projections (and a retention layer's gate logits)",
    "attn.qk_norm": "the RMSNorm of q and k between projection and rope",
    "attn.rope": "rope on q and k",
    "attn.core": "`attend` and the views of q, k, v and o on both sides of it: the kernels, a cache's writes, XLA's copies round them",
    "attn.window": "a windowed layer's attention, whole-sequence or paged",
    "attn.gate": "the attention output's sigmoid gate (a softmax layer's, a latent layer's)",
    "attn.out": "`wo`",
    "attn.mla.q": "a latent layer's query: low-rank pair, norm, split, rope",
    "attn.mla.kv_down": "a latent layer's down-projection: c_kv, k_r",
    "attn.mla.expand": "the expanded form's up-projections of c_kv",
    "attn.mla.absorb": "the absorbed form's `w_uk` on the query, `w_uv` on the output",
    "attn.mla.out": "a latent layer's `wo`",
    "kda.gates": "a delta-rule layer's decay (a key channel's, or a head's broadcast over its channels) and beta",
    "kda.conv": "its short convolutions and q/k norms (shared key heads repeated to their value heads)",
    "kda.chunk": "its chunked recurrence (prefill, whole sequences)",
    "kda.step": "its one-token recurrence (decode)",
    "kda.out": "its output norm, gate (thin, or full width)",
    "retention.chunk": "a retention layer's prefill chunk",
    "ffn": "the dense feed-forward (a routed layer keeps its `moe.*`)",
    "moe.router": "router logits, top-k, weights",
    "moe.route.groups": "group-limited selection",
    "moe.dispatch": "rows sorted by expert",
    "moe.experts": "the experts' products",
    "moe.combine": "rows back to token order under the router's weights",
    "moe.shared": "the shared expert",
    "residual": "a block's residual adds",
    "head": "the logits' product (training, `head_loss`: a chunk's, and the two backward products)",
    "loss": "`head_loss`'s passes over a chunk's logits and its chunk scan, the mean's weights, the cotangent (train/zero.py: the mean over the devices)",
    "optimizer": "`tx.update` and `optax.apply_updates` of the unsharded step",
    "zero.grad_scatter": "train/zero.py: a gradient's reduce-scatter along the leaf's cut dimension, the shard's flattening",
    "zero.update": "train/zero.py: the parameters' shards, `tx.update`",
    "zero.param_gather": "train/zero.py: the updates' all-gather into the leaf's own shape, `apply_updates` on the whole leaf",
}


def rms_norm(x, scale, eps):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return (x * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _rms_norm_per_head(x, scale, eps, head_dim: int):
    """rms_norm over each head's lanes of x [.., heads * head_dim] with one
    scale [head_dim] for all heads. Only the statistic sees the heads: x
    stays as its projection gave it."""
    heads = x.shape[-1] // head_dim
    sq = jnp.square(x.astype(jnp.float32)).reshape(*x.shape[:-1], heads, head_dim)
    inv = jnp.repeat(lax.rsqrt(jnp.mean(sq, axis=-1) + eps), head_dim, axis=-1)
    return (x * inv).astype(x.dtype) * jnp.tile(scale, heads)


def layer_norm(x, scale, eps):
    """Mean-centering LayerNorm, scale-only (GPT-J's ln, bias unmodeled)."""
    xf = x.astype(jnp.float32)
    xf = xf - jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    return (xf * lax.rsqrt(var + eps)).astype(x.dtype) * scale


def _norm_scale(w, cfg: TransformerConfig):
    """What a norm multiplies by: its leaf `w`, or under `cfg.norm_gate` c the gated scale c * sigmoid(w)."""
    if not cfg.norm_gate:
        return w
    return (cfg.norm_gate * jax.nn.sigmoid(w.astype(jnp.float32))).astype(w.dtype)


def _norm(x, scale, cfg: TransformerConfig):
    with jax.named_scope("norm"):
        if cfg.norm_type == "layer":
            return layer_norm(x, scale, cfg.norm_eps)
        return rms_norm(x, _norm_scale(scale, cfg), cfg.norm_eps)


def _yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention factor m(s, a) = 0.1 a ln s + 1 (1 at a factor of 1 or less)."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def _rope_freqs(cfg: TransformerConfig):
    """The rotated pairs' frequencies [rotary_dim / 2]: theta^(-2i / dim), and
    under YaRN (`cfg.rope_scaling`) each divided by `factor` to the degree
    that its pair turns fewer than beta_slow times over the original context,
    not at all where it turns more than beta_fast times, linearly between
    (pair indices `low` .. `high`, from the number of turns n a pair of index
    d makes: d(n) = dim ln(original / (2 pi n)) / (2 ln theta))."""
    half = (cfg.qk_rope_dim or cfg.rotary_dim or cfg.head_dim) // 2
    freqs = cfg.rope_theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if not cfg.rope_scaling:
        return freqs
    _, factor, original, beta_fast, beta_slow, _, _ = cfg.rope_scaling

    def pair_of(turns: float) -> float:
        return 2 * half * math.log(original / (2 * math.pi * turns)) / (2 * math.log(cfg.rope_theta))

    low = min(max(math.floor(pair_of(beta_fast)), 0), half - 1)
    high = min(max(math.ceil(pair_of(beta_slow)), 0), half - 1)
    ramp = jnp.clip((jnp.arange(half, dtype=jnp.float32) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return freqs / factor * ramp + freqs * (1.0 - ramp)


def _rope_magnitude(cfg: TransformerConfig) -> float:
    """What YaRN multiplies cos and sin by: m(factor, mscale) / m(factor, mscale_all_dim); 1 without scaling."""
    if not cfg.rope_scaling:
        return 1.0
    _, factor, _, _, _, mscale, mscale_all_dim = cfg.rope_scaling
    return _yarn_mscale(factor, mscale) / _yarn_mscale(factor, mscale_all_dim)


def _cos_sin(cfg: TransformerConfig, angles):
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    m = _rope_magnitude(cfg)
    return (cos, sin) if m == 1.0 else (cos * m, sin * m)


def _window_rope_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """The config as rope on a window layer reads it: `window_rope_theta` its base."""
    return cfg.replace(rope_theta=cfg.window_rope_theta)


def rope_tables(cfg: TransformerConfig, seq_len: int):
    with jax.named_scope("attn.rope"):
        freqs = _rope_freqs(cfg)
        angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * freqs[None, :]
        return _cos_sin(cfg, angles)  # [seq, rotary_dim/2]


def rope_at(cfg: TransformerConfig, positions):
    """rope_tables' rows at the positions given [n]: cos, sin [n, rotary_dim/2]."""
    with jax.named_scope("attn.rope"):
        angles = positions.astype(jnp.float32)[:, None] * _rope_freqs(cfg)[None, :]
        return _cos_sin(cfg, angles)


def _rotate(x, cos, sin, interleave: bool):
    # [s, r] (every row the same positions) or [b, s, r] -> over all heads
    c, s = (t[None, :, None, :] if t.ndim == 2 else t[:, :, None, :] for t in (cos, sin))
    if interleave:
        # GPT-J convention: pairs are (even, odd) interleaved dims.
        x1, x2 = x[..., ::2], x[..., 1::2]
        o1, o2 = x1 * c - x2 * s, x2 * c + x1 * s
        return jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _rope_flat(xf, cos, sin, cfg: TransformerConfig):
    """apply_rope's rotation of xf [b, s, heads * head_dim] float32, a
    projection not split into heads: the head view's products and sum, lane
    for lane. A lane's partner is `step` lanes to its right if it is the
    first of its pair, else to its left (two rolls and a select; a pair never
    leaves its head); a pair's angle stands on both its lanes, the identity
    rotation beyond rotary_dim, the same in every head."""
    hd = cfg.head_dim
    rd = cfg.rotary_dim or hd
    interleave = cfg.rope_style == "interleaved"
    step = 1 if interleave else rd // 2

    def per_lane(t, rest):
        t = jnp.repeat(t, 2, axis=-1) if interleave else jnp.concatenate([t, t], axis=-1)
        t = jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, hd - rd)], constant_values=rest)
        t = jnp.tile(t, xf.shape[-1] // hd)
        return t if t.ndim == 3 else t[None]

    c, s = per_lane(cos, 1.0), per_lane(sin, 0.0)
    first = (jnp.arange(xf.shape[-1]) % hd // step) % 2 == 0
    right, left = jnp.roll(xf, -step, axis=-1), jnp.roll(xf, step, axis=-1)
    return jnp.where(first, xf * c - right * s, xf * c + left * s)


def apply_rope(x, cos, sin, cfg: TransformerConfig):
    """x: [b, s, h, d], or [b, s, h * d], a projection not yet split into
    heads (`_block` says which it passes, and why; the numbers are the same,
    bit for bit); cos / sin [s, rotary_dim/2] (the same positions in every
    row: train, prefill) or [b, s, rotary_dim/2] (each row its own: decode).
    Llama rotates the full head (rotate-half); GPT-J rotates only the first
    rotary_dim dims, interleaved pairs, leaving the rest pass-through."""
    xf = x.astype(jnp.float32)
    if x.ndim == 3:
        return _rope_flat(xf, cos, sin, cfg).astype(x.dtype)
    rd = cfg.rotary_dim
    interleave = cfg.rope_style == "interleaved"
    if rd is not None and rd < x.shape[-1]:
        rot = _rotate(xf[..., :rd], cos, sin, interleave)
        out = jnp.concatenate([rot, xf[..., rd:]], axis=-1)
    else:
        out = _rotate(xf, cos, sin, interleave)
    return out.astype(x.dtype)


# A layer without a window, where windows ride a scan as data: every key is
# within reach (positions are int32 and far below it).
NO_WINDOW = 1 << 30


def _per_layer(cfg: TransformerConfig, first: int, n: int):
    """(windows [n] int32, rope switches [n] bool) of layers [first, first +
    n), the values that ride a group's scan beside its stacked weights; None
    for what the config does not vary (nothing rides, the body is as before)."""
    windows = rope = None
    if cfg.windows and not cfg.window_kv_heads:  # a window ring's size is static: nothing of it rides
        with jax.named_scope("attn.window"):
            w = jnp.asarray(cfg.windows[first:first + n], jnp.int32)
            windows = jnp.where(w > 0, w, NO_WINDOW)
    if cfg.rope_layers:
        with jax.named_scope("attn.rope"):
            rope = jnp.asarray(cfg.rope_layers[first:first + n], bool)
    return windows, rope


def _window_scope(window):
    """The `attn.window` scope around a paged attention call of a layer whose
    window rides the scan; nothing for a config without windows."""
    return jax.named_scope("attn.window") if window is not None else contextlib.nullcontext()


def _rope_switch(cos, sin, rope_on):
    """The rope tables of one layer: as given, or the identity rotation
    where the layer's switch (a traced bool) is off."""
    if rope_on is None:
        return cos, sin
    with jax.named_scope("attn.rope"):
        return jnp.where(rope_on, cos, 1.0), jnp.where(rope_on, sin, 0.0)


class StackMember(NamedTuple):
    """One kind of layer within a repeat of a segment of the stack: `layers`
    alike layers in a row, their weights stacked in `params[tree]` ([repeats,
    ...], or [repeats, layers, ...] where a repeat has several); `first`: where
    the first of them lies in that kind's cache leaves (`KINDS`)."""

    kind: str
    tree: str
    layers: int
    first: int

    def place(self, repeat, j=None):
        """The place in its kind's cache leaves of layer `j` of repeat `repeat`."""
        at = repeat if self.layers == 1 else repeat * self.layers + j
        return self.first + at if self.first else at  # no `0 + at` in a traced body


def stack_plan(cfg: TransformerConfig) -> Tuple[Tuple[int, Tuple[StackMember, ...]], ...]:
    """The layer stack in published order, from the config alone: segments
    (repeats, members), each the members' layers in order, `repeats` times.
    Alike layers are one segment of one member (a routed model's leading dense
    layers a segment of their own); a periodic pattern is one segment whose
    members are a period: a full layer ("softmax", or "latent" where the
    config has latent attention), then `kda_per_period` delta-rule layers,
    behind leading dense layers of whichever kind `full_layers` gives them;
    or (`window_kv_heads`) a period of window layers that keep a ring, then
    one softmax layer, behind ONE leading dense softmax layer.
    `_walk_stack` walks it, `cache_layout` reads what a served sequence keeps
    from it; a new pattern is a new return value here."""
    per, nd = cfg.kda_per_period, cfg.n_dense_layers
    kind = "retention" if cfg.retention_degree else "latent" if cfg.kv_lora_rank else "softmax"
    if cfg.window_kv_heads:
        # The leading dense global layer, then periods of window layers that end on a global one: the global layers
        # are the "softmax" row's (the dense one its first), the others the "window" row's.
        ring_per = _ring_period(cfg)
        return ((1, (StackMember("softmax", "dense_blocks", 1, 0),)),
                ((cfg.n_layers - 1) // (ring_per + 1), (StackMember("window", "window_blocks", ring_per, 0), StackMember("softmax", "blocks", 1, 1))))
    if per:
        # The dense layers are of the kind the published list gives them; `first` counts the layers of a kind before a member's.
        dense_kind = kind if 0 in cfg.full_layers else "kda"
        dense = ((nd, (StackMember(dense_kind, "dense_blocks", 1, 0),)),) if nd else ()
        full_before, kda_before = (nd, 0) if dense_kind == kind else (0, nd)
        return (*dense, ((cfg.n_layers - nd) // (per + 1), (StackMember(kind, "blocks", 1, full_before), StackMember("kda", "kda_blocks", per, kda_before))))
    dense = ((nd, (StackMember(kind, "dense_blocks", 1, 0),)),) if nd else ()
    return (*dense, (cfg.n_layers - nd, (StackMember(kind, "blocks", 1, nd),)))


def _ring_period(cfg: TransformerConfig) -> Optional[int]:
    """The window layers of one period of a stack with window rings
    (`window_kv_heads`): `windows` is 0 for the leading dense layer, then whole
    periods of that many alike windows and one 0. None for any other list."""
    w = cfg.windows
    if not cfg.window_kv_heads or cfg.n_dense_layers != 1 or len(w) != cfg.n_layers or len(w) < 3 or w[0] or not w[1]:
        return None
    per = next((i for i in range(1, len(w)) if not w[i]), len(w)) - 1
    period = (w[1],) * per + (0,)
    return per if (len(w) - 1) % (per + 1) == 0 and tuple(w[1:]) == period * ((len(w) - 1) // (per + 1)) else None


class LayerPlace(NamedTuple):
    """Where `_walk_stack` is, as a layer's callback sees it: `layer`, the
    layer's place among the layers of its kind (its layer of that kind's cache
    leaves; None where the walk keeps nothing: training), and the values that
    ride the scan for this layer (`_per_layer`; None for what the config does
    not vary)."""

    layer: Any
    window: Any
    rope_on: Any


EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


# The serving steps' cut between the routed FFN's two forms, in rows of the
# call. The every-expert product does `rows` FLOPs a weight byte: under the
# chip's ridge (v5e: 197 TFLOP/s / 819 GB/s = 240) it costs the experts' bytes
# whatever the router chose, above it the FLOPs of products the router weighs
# with 0. The grouped product (ops/grouped_matmul.py) costs the touched
# experts' bytes and a sort of the rows. `lax.ragged_dot`, the training path's,
# serves no such call: on the chip it pads every group to 512 rows
# (`ragged_dot_tiling`). Measured on the chip at 64 / 128 / 256 rows, both
# served shapes (PERF.md §6, PR 47).
GROUPED_FROM_ROWS = 128


def experts_grouped_at(rows: int) -> bool:
    """Whether a serving call of that many rows takes the grouped product."""
    return rows >= GROUPED_FROM_ROWS


def _layer_of(stack, index):
    """One layer's slice of a stacked weight, read where it lies: `index` a
    traced scalar, or (period, layer in it) of a stack [periods, layers a
    period, ...]."""
    for i in index if isinstance(index, tuple) else (index,):
        stack = lax.dynamic_index_in_dim(stack, i, 0, keepdims=False)
    return stack


def _experts_in_place(blocks: PyTree):
    """A group's stacked weights as (what rides its layer scan, the experts'
    stack): a routed group's expert matrices `{name: [layers, E, ., .]}` stay
    out of the scan's xs (as xs every step copies a layer's slice out of the
    stack), and the serving steps hand `_block` the stack with the layer's
    place in it (`experts=(stack, index)`), which `_routed_ffn` reads where
    it lies. None for a dense group. Training scans the experts like every
    other weight (its backward wants a layer's gradient, not a stack's)."""
    mlp = blocks["mlp"]
    if "router" not in mlp:
        return blocks, None
    riding = dict(blocks, mlp={name: w for name, w in mlp.items() if name not in EXPERT_WEIGHTS})
    return riding, {name: mlp[name] for name in EXPERT_WEIGHTS}


def _sink_softmax(scores, sink):
    """softmax over the last axis of scores [.., h, q, k] float32 with one more
    term in the denominator, exp(sink[h]), that is no key: the probabilities of
    the keys alone (they sum to less than 1)."""
    sink = sink.astype(jnp.float32)[:, None, None]
    top = jnp.maximum(jnp.max(scores, axis=-1, keepdims=True), sink)
    e = jnp.exp(scores - top)
    return e / (jnp.sum(e, axis=-1, keepdims=True) + jnp.exp(sink - top))


def _window_attention(q, k, v, window, sink=None):
    """Causal attention of a whole sequence in which query i sees key j iff
    0 <= i - j < window (a traced scalar): the masked plain expression,
    float32 softmax, under `sink` [h] with a sink logit a head in its
    denominator. q [b, s, h, d], k / v [b, s, kv, d] (v of a width of its own)."""
    rep = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, rep, axis=2) if rep > 1 else t for t in (k, v))
    s = q.shape[1]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) / math.sqrt(q.shape[-1])
    back = jnp.arange(s)[:, None] - jnp.arange(s)[None, :]
    scores = jnp.where((back >= 0) & (back < window), scores, -jnp.inf)
    probs = (jax.nn.softmax(scores, axis=-1) if sink is None else _sink_softmax(scores, sink)).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32).astype(q.dtype)


def _attention(q, k, v, cfg: TransformerConfig, mesh: Optional[Mesh], window=None):
    if window is not None:
        # The flash, ring and ulysses kernels know `causal` only: a window
        # layer that attended to everything would be silently another model.
        if cfg.attn_impl != "naive":
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} computes no attention window; a config with `windows` "
                "runs its whole-sequence forward with attn_impl='naive' (serving pages through ops/paged_attention.py)"
            )
        with jax.named_scope("attn.window"):
            return _window_attention(q, k, v, window)
    if cfg.attn_impl == "full":
        # Fused pallas kernel (handles GQA internally; falls back to the
        # unfused path for untileable shapes). With a tensor axis in the
        # mesh, the kernel runs under shard_map with HEADS sharded over
        # "tensor" — attention is embarrassingly parallel across heads, so
        # TP attention is N independent per-shard kernels, no collectives
        # (reference: net-new; Ray delegates TP to user code, SURVEY §2h).
        from ..ops.flash_attention import flash_attention

        if (
            mesh is not None
            and "tensor" in mesh.axis_names
            and mesh.shape["tensor"] > 1
            and q.shape[2] % mesh.shape["tensor"] == 0
            and k.shape[2] % mesh.shape["tensor"] == 0
        ):
            from jax.sharding import PartitionSpec as _P

            from ..parallel.collectives import shard_map as _smap

            batch_axes = tuple(a for a in ("data", "fsdp") if a in mesh.axis_names)
            spec = _P(batch_axes if batch_axes else None, None, "tensor", None)
            return _smap(
                lambda q_, k_, v_: flash_attention(q_, k_, v_, causal=True),
                mesh,
                in_specs=(spec, spec, spec),
                out_specs=spec,
            )(q, k, v)
        return flash_attention(q, k, v, causal=True)
    if cfg.n_kv_heads != cfg.n_heads:
        rep = cfg.n_heads // cfg.n_kv_heads
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    if cfg.attn_impl == "ring":
        if mesh is None:
            raise ValueError("attn_impl='ring' requires a mesh")
        return ring_attention(q, k, v, mesh, causal=True)
    if cfg.attn_impl == "ulysses":
        if mesh is None:
            raise ValueError("attn_impl='ulysses' requires a mesh")
        return ulysses_attention(q, k, v, mesh, causal=True)
    return attention_reference(q, k, v, causal=True)


# --------------------------------------------------------- power retention
#
# A retention layer (cfg.retention_degree 2) attends by
#     a_ts = exp(L_t - L_s) (q_t . k_s / sqrt(hd))^2        for s <= t,
#     y_t  = sum_s a_ts v_s / (sum_s a_ts + RETENTION_EPS),
# L the running sum of the gate's log-sigmoid, one gate a K/V head. With
# phi(x) holding the products x_a x_b, phi(q) . phi(k) = (q . k)^2, so the
# past of a sequence is one state a K/V head: S = sum_s exp(L_t - L_s) v_s
# phi(k_s)^T [hd, D] and z = sum_s exp(L_t - L_s) phi(k_s) [D]. Three forms
# of the same numbers: `retention_whole` (a whole sequence, nothing kept),
# `retention_chunk` (a prefill chunk, from a state to a state) and
# `retention_step` (one token a sequence); ops/power_retention.py holds the
# last two as kernels over the paged pool, of which these are the parity
# references. Everything here is float32, the matmuls at RETENTION_PRECISION:
# a state is thousands of decayed additions into one array.
#
# The state's layout: S is stored TRANSPOSED, [hd (v's index), D], the pairs
# on the minor axis, and phi's D = (hd / 2 + 1) * hd entries lie as hd / 2 + 1
# rows of hd: row d holds w_d x_a x_((a + d) mod hd) for a = 0..hd-1, the
# pairs at circular distance d. Every unordered pair {a, b} is held once
# (w = sqrt(2): its two orders), the squares once (row 0, w = 1), and the
# pairs at distance hd / 2 twice with w = 1 each (row hd / 2: a and a + hd/2
# name the same pair). At hd 128: 65 rows of 128 lanes, 8 320 entries where
# the unordered pairs are 8 256; a rotation of x by d lanes makes row d.

RETENTION_EPS = 1e-6
RETENTION_PRECISION = lax.Precision.HIGHEST


def retention_state_dim(head_dim: int) -> int:
    """Entries of phi(x) for x of head_dim dims, as the state holds them."""
    return (head_dim // 2 + 1) * head_dim


def retention_phi(x):
    """x [..., hd] -> phi(x) [..., D] in x's dtype (float32 from every caller
    here): phi(x) . phi(y) = (x . y)^2, in the layout above."""
    hd = x.shape[-1]
    twice = jnp.concatenate([x, x[..., : hd // 2]], axis=-1)
    rows = [x * twice[..., d : d + hd] * (1.0 if d in (0, hd // 2) else math.sqrt(2.0)) for d in range(hd // 2 + 1)]
    return jnp.concatenate(rows, axis=-1)


def _retention_heads(q, k, v, log_g):
    """float32 views a K/V head: q [.., kv, r, hd] scaled by 1 / sqrt(hd) (the
    scale inside the power), k, v [.., kv, hd], log_g [.., kv]."""
    hd, kv = q.shape[-1], k.shape[-2]
    f32 = jnp.float32
    q = q.astype(f32).reshape(*q.shape[:-2], kv, q.shape[-2] // kv, hd) / math.sqrt(hd)
    return q, k.astype(f32), v.astype(f32), log_g.astype(f32)


def retention_chunk(q, k, v, log_g, s_in, z_in, valid=None):
    """One chunk of ONE sequence from the state before its first row to the
    state after its last: q [c, n_heads, hd], k / v [c, n_kv_heads, hd],
    log_g [c, n_kv_heads], s_in [n_kv_heads, hd, D], z_in [n_kv_heads, D] ->
    (y [c, n_heads, hd] float32, s_out, z_out). The in-chunk pairs by the
    quadratic expression, the earlier ones through phi(q)^T S_in. `valid` [c]
    bool: rows past a prompt's length (padding: they follow every valid row)
    neither decay the state nor enter it; their own outputs are arbitrary.
    The plain expression (phi(q) and phi(k) whole, in HBM): training's, and
    the reference of ops/power_retention.power_retention_prefill."""
    c, H, hd = q.shape
    q, k, v, log_g = _retention_heads(q, k, v, log_g)
    dot = partial(jnp.einsum, precision=RETENTION_PRECISION)
    if valid is not None:
        log_g = jnp.where(valid[:, None], log_g, 0.0)
    L = jnp.cumsum(log_g, axis=0)  # [c, kv]: the decay from the chunk's start to each row, that row's gate included
    back = jnp.arange(c)[:, None] - jnp.arange(c)[None, :]
    decay = jnp.exp(jnp.where(back >= 0, L.T[:, :, None] - L.T[:, None, :], -jnp.inf))  # [kv, t, s]
    a = jnp.square(dot("tjrd,sjd->jrts", q, k)) * decay[:, None]
    carried = jnp.exp(L)[:, :, None]  # [c, kv, 1]
    pq = retention_phi(q)
    num = dot("jrts,sje->tjre", a, v) + carried[..., None] * dot("tjrD,jeD->tjre", pq, s_in)
    den = jnp.moveaxis(jnp.sum(a, axis=-1), -1, 0) + carried * dot("tjrD,jD->tjr", pq, z_in)
    y = num / (den[..., None] + RETENTION_EPS)
    # What each row leaves in the state at the chunk's end.
    left = jnp.exp(L[-1][None, :] - L)
    if valid is not None:
        left = jnp.where(valid[:, None], left, 0.0)
    pk = retention_phi(k)
    s_out = jnp.exp(L[-1])[:, None, None] * s_in + dot("sje,sjD->jeD", v * left[..., None], pk)
    z_out = jnp.exp(L[-1])[:, None] * z_in + dot("sj,sjD->jD", left, pk)
    return y.reshape(c, H, hd), s_out, z_out


def retention_step(q, k, v, log_g, s, z):
    """One token a sequence: q [B, n_heads, hd], k / v [B, n_kv_heads, hd],
    log_g [B, n_kv_heads], s [B, n_kv_heads, hd, D], z [B, n_kv_heads, D] ->
    (y [B, n_heads, hd] float32, s_new, z_new): the state decayed by the
    token's gate, the token added, then read by its query heads. The plain
    expression; ops/power_retention.py is its one-pass kernel."""
    B, H, hd = q.shape
    q, k, v, log_g = _retention_heads(q, k, v, log_g)
    dot = partial(jnp.einsum, precision=RETENTION_PRECISION)
    g, pk, pq = jnp.exp(log_g), retention_phi(k), retention_phi(q)
    s = g[..., None, None] * s + v[..., :, None] * pk[..., None, :]
    z = g[..., None] * z + pk
    y = dot("bjrD,bjeD->bjre", pq, s) / (dot("bjrD,bjD->bjr", pq, z)[..., None] + RETENTION_EPS)
    return y.reshape(B, H, hd), s, z


def retention_whole(q, k, v, log_g, chunk: Optional[int] = None):
    """Whole sequences that keep nothing: q [b, s, n_heads, hd], k / v
    [b, s, n_kv_heads, hd], log_g [b, s, n_kv_heads] -> y [b, s, n_heads, hd]
    float32. `retention_chunk` from a zero state over chunks of `chunk` rows
    (PREFILL_CHUNK_TOKENS), the last one padded."""
    b, s, H, hd = q.shape
    kv = k.shape[2]
    c = min(chunk or PREFILL_CHUNK_TOKENS, s)
    n = -(-s // c)
    valid = (jnp.arange(n * c) < s).reshape(n, c)

    def one(q, k, v, log_g):
        def chunks(t):
            return jnp.pad(t, [(0, n * c - s)] + [(0, 0)] * (t.ndim - 1)).reshape(n, c, *t.shape[1:])

        def step(state, xs):
            y, s_out, z_out = retention_chunk(*xs[:4], *state, valid=xs[4])
            return (s_out, z_out), y

        D = retention_state_dim(hd)
        zero = (jnp.zeros((kv, hd, D), jnp.float32), jnp.zeros((kv, D), jnp.float32))
        _, ys = lax.scan(step, zero, (chunks(q), chunks(k), chunks(v), chunks(log_g), valid))
        return ys.reshape(n * c, H, hd)[:s]

    return jax.vmap(one)(q, k, v, log_g)


def _qkv(h, ap, cfg: TransformerConfig, split: bool, gated: bool = False):
    """h [b, s, d] -> q, k, v before rope: split into heads ([b, s, n_heads,
    hd], [b, s, n_kv_heads, hd]) or each as its projection gives it
    ([b, s, n_heads * hd], [b, s, n_kv_heads * hd]); `_block` says which.
    A fourth beside them: the retention gate's logits [b, s, n_kv_heads]
    float32 (`gated`: a retention layer), else None."""
    with jax.named_scope("attn.qkv"):
        q = jnp.einsum("bsd,dk->bsk", h, ap["wq"], preferred_element_type=jnp.float32)
        k = jnp.einsum("bsd,dk->bsk", h, ap["wk"], preferred_element_type=jnp.float32)
        v = jnp.einsum("bsd,dk->bsk", h, ap["wv"], preferred_element_type=jnp.float32)
        if cfg.qk_norm:
            # over the whole projection, before the split into heads (OLMoE), or
            # over each head's dims with one scale for all heads (afmoe)
            with jax.named_scope("attn.qk_norm"):
                norm = partial(_rms_norm_per_head, head_dim=cfg.head_dim) if cfg.qk_norm_per_head else rms_norm
                q = norm(q, ap["q_norm"]["scale"], cfg.norm_eps)
                k = norm(k, ap["k_norm"]["scale"], cfg.norm_eps)
        if cfg.value_scale != 1.0:
            v = v * cfg.value_scale
        view = (lambda t, width: t.reshape(*t.shape[:2], -1, width)) if split else (lambda t, width: t)
        gate = None
        if gated:
            gate = jnp.einsum("bsd,dk->bsk", h, ap["wg"], preferred_element_type=jnp.float32)
        return view(q, cfg.head_dim).astype(cfg.dtype), view(k, cfg.head_dim).astype(cfg.dtype), view(v, cfg.value_dim).astype(cfg.dtype), gate


def _ffn(h, mp, cfg: TransformerConfig, experts=None):
    """The block's feed-forward, h [b, s, d] -> [b, s, d]: the one copy that
    the train layer, prefill and decode share. Dense (SwiGLU or gelu) or
    routed where the layer's weights hold a router (a routed model's leading
    dense layers do not); `experts` as `_routed_ffn` takes it."""
    if "router" in mp:
        return _routed_ffn(h, mp, cfg, experts=experts)
    with jax.named_scope("ffn"):
        return _dense_ffn(h, mp, cfg)


def _swiglu_act(gate, up, cfg: TransformerConfig):
    """silu(gate) * up in float32; under `cfg.swiglu_limit` a the gate cut from above and up on both sides first."""
    f32 = jnp.float32
    if not cfg.swiglu_limit:
        return jax.nn.silu(gate.astype(f32)) * up.astype(f32)
    return jax.nn.silu(jnp.minimum(gate.astype(f32), cfg.swiglu_limit)) * jnp.clip(up.astype(f32), -cfg.swiglu_limit, cfg.swiglu_limit)


def _dense_ffn(h, mp, cfg: TransformerConfig):
    """SwiGLU or gelu of h through one set of matrices, under the caller's
    scope (`ffn`; a routed layer's shared expert: `moe.shared`)."""
    up = jnp.einsum("bsd,df->bsf", h, mp["w_up"], preferred_element_type=jnp.float32)
    if cfg.mlp_act == "swiglu":
        gate = jnp.einsum(
            "bsd,df->bsf", h, mp["w_gate"], preferred_element_type=jnp.float32
        )
        act = _swiglu_act(gate, up, cfg).astype(cfg.dtype)
    else:
        act = jax.nn.gelu(up).astype(cfg.dtype)
    act = _ckpt(act, "mlp_act_bf16")
    return jnp.einsum(
        "bsf,fd->bsd", act, mp["w_down"], preferred_element_type=jnp.float32
    ).astype(cfg.dtype)


def _router_probs(x, mp, cfg: TransformerConfig):
    """x [n, d] -> (the router's score of every expert [n, E], what the top-k
    ranks [n, E]); matmul and scores in float32 (and so is the top-k that
    reads them). Softmax: probabilities, ranked as they are. Sigmoid:
    independent scores, ranked with the per-expert bias added, which selects
    and never weighs."""
    logits = jnp.einsum(
        "nd,de->ne",
        x.astype(jnp.float32),
        mp["router"].astype(jnp.float32),
        precision=lax.Precision.HIGHEST,
    )
    if cfg.router_score == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        ranked = scores + mp["router_bias"].astype(jnp.float32)
        return scores, (_keep_best_groups(ranked, cfg) if cfg.n_group > 1 else ranked)
    probs = jax.nn.softmax(logits, axis=-1)
    return probs, probs


def _keep_best_groups(ranked, cfg: TransformerConfig):
    """Group-limited selection: ranked [n, E] as n_group groups of E / n_group
    neighbours; a group's score is the sum of its two largest entries, the
    topk_group best groups stay as they are and every entry of the others
    becomes -inf, which no top-k takes."""
    G = cfg.n_group
    with jax.named_scope("moe.route.groups"):
        groups = ranked.reshape(ranked.shape[0], G, -1)
        group_score = jnp.sum(lax.top_k(groups, 2)[0], axis=-1)  # [n, G]
        kept = jnp.sum(jax.nn.one_hot(lax.top_k(group_score, cfg.topk_group)[1], G, dtype=jnp.int32), axis=1) > 0  # [n, G]
        return jnp.where(kept[:, :, None], groups, -jnp.inf).reshape(ranked.shape)


def _tokens_per_expert(experts, n_experts: int):
    """experts: any int array of expert ids -> how often each occurs [E]."""
    return jnp.sum(experts.reshape(-1, 1) == jnp.arange(n_experts)[None, :], axis=0, dtype=jnp.int32)


def _every_expert_ffn(x, experts, top_e, top_p, cfg: TransformerConfig):
    """The routed experts' sum over few rows, x [n, d] -> [n, d] (a decode
    batch, a prefill chunk; `experts` one layer's {name: [E, ., .]}): every
    expert multiplies all n tokens (one batched matmul a projection) and a token's
    result is the sum under the router's weights, which are exactly 0 for the
    experts it did not choose. The same numbers as the grouped path up to the
    order of a float32 sum. Why: a grouped matmul tiles 512 rows a group, so
    at a handful of rows an expert it is bound by the padding's products and
    its time follows how many experts the rows happened to touch; this reads
    each expert's matrices once whatever the routing, n * E rows of products
    where the grouped one pays 512 * the touched experts. Under a share
    (`cfg.n_experts_held`) `experts` holds the held ones and a token's weights
    are the held columns of the [n, E] matrix."""
    E, held = cfg.n_experts, cfg.experts_held
    w_gate, w_up, w_down = (experts[name] for name in EXPERT_WEIGHTS)
    with jax.named_scope("moe.experts"):
        # E is a batch dimension of both operands: as a free dimension of the
        # weights alone the product is x @ W[d, e * f], and the compiler copies
        # the whole stack into that layout. Results in the parameters' type, as
        # the grouped matmuls give them.
        xe = jnp.broadcast_to(x, (held, *x.shape))
        gate = jnp.einsum("end,edf->enf", xe, w_gate, preferred_element_type=cfg.dtype)
        up = jnp.einsum("end,edf->enf", xe, w_up, preferred_element_type=cfg.dtype)
        act = _swiglu_act(gate, up, cfg).astype(cfg.dtype)
        ys = jnp.einsum("enf,efd->end", act, w_down, preferred_element_type=cfg.dtype)
    with jax.named_scope("moe.combine"):
        weights = jnp.sum(jax.nn.one_hot(top_e, E, dtype=jnp.float32) * top_p[..., None].astype(jnp.float32), axis=1)  # [n, E]
        if held != E:  # one chip's share: the held experts' columns, the others' terms left out
            weights = weights[:, cfg.first_expert : cfg.first_expert + held]
        return jnp.einsum("ne,end->nd", weights, ys.astype(jnp.float32)).astype(cfg.dtype)


def _routed_ffn(h, mp, cfg: TransformerConfig, counts: bool = False, experts=None):
    """Dropless top-k mixture of SwiGLU experts. Every (token, chosen expert)
    pair is one row: rows are sorted by expert, each expert multiplies its
    own contiguous group (lax.ragged_dot: no capacity, no padding, n * k
    rows whatever the imbalance), and the rows go back to token order under
    the router's weights. Both row movements' backward gathers (ops/moe_rows).
    A shared expert (cfg.d_ff_shared) adds its SwiGLU of every token.
    `experts` (the serving steps: `_experts_in_place`) is (the group's stack
    {name: [layers, E, ., .]}, this layer's index in it) in place of the
    layer's own matrices in `mp`; such a step multiplies every expert by
    every row (`_every_expert_ffn`) while its rows are few
    (`experts_grouped_at`), and from there on each expert by its own rows
    where the stack lies (ops/grouped_matmul.py). Returns out
    [b, s, d], and with `counts` the rows each of the router's E experts was
    chosen by [E] beside it (under a share: held or not)."""
    from ..ops.moe_rows import combine_rows, dispatch_rows

    b, s, d = h.shape
    n, k, E = b * s, cfg.n_experts_per_tok, cfg.n_experts
    with jax.named_scope("moe.router"):
        x = h.reshape(n, d)
        probs, ranked = _router_probs(x, mp, cfg)
        probs = _ckpt(probs, "moe_route")
        top_e = _ckpt(lax.top_k(ranked, k)[1], "moe_route")  # [n, k], most probable first
        top_p = jnp.take_along_axis(probs, top_e, axis=-1)
        if cfg.norm_topk_prob:
            total = jnp.sum(top_p, axis=-1, keepdims=True)
            top_p = top_p / (total + 1e-20 if cfg.router_score == "sigmoid" else total)
        if cfg.route_scale != 1.0:
            top_p = top_p * cfg.route_scale
    if experts is not None and not experts_grouped_at(n):
        stack, index = experts
        with jax.named_scope("moe.experts"):
            layer = {name: _layer_of(stack[name], index) for name in EXPERT_WEIGHTS}
        out = _every_expert_ffn(x, layer, top_e, top_p, cfg)
        with jax.named_scope("moe.combine"):
            out = out.reshape(b, s, d)
        if "shared" in mp:
            with jax.named_scope("moe.shared"):
                out = out + _dense_ffn(h, mp["shared"], cfg)
        if not counts:
            return out
        with jax.named_scope("moe.router"):
            return out, _tokens_per_expert(top_e, E)
    held, rows_per_expert = cfg.experts_held, None
    with jax.named_scope("moe.dispatch"):
        flat_e = top_e.reshape(n * k)
        if held != E:
            # One chip's share: a pick of an expert that is not held is dropped
            # before the sort: it weighs nothing and sorts behind every group
            # (expert `held`, which no group is), where the grouped products
            # leave its row alone.
            rows_per_expert = _tokens_per_expert(flat_e, E)
            here = (top_e >= cfg.first_expert) & (top_e < cfg.first_expert + held)
            top_p = jnp.where(here, top_p, 0.0)
            flat_e = jnp.where(here, top_e - cfg.first_expert, held).reshape(n * k)
        order = _ckpt(jnp.argsort(flat_e), "moe_route")  # row r of the sorted is pair order[r]
        inverse = _ckpt(jnp.argsort(order), "moe_route")  # pair p sits in sorted row inverse[p]
        group_sizes = _ckpt(_tokens_per_expert(flat_e, held), "moe_route")
        xs = _ckpt(dispatch_rows(x, order, inverse, k), "moe_xs_bf16")
    with jax.named_scope("moe.experts"):
        if experts is not None:
            ys = _grouped_experts(xs, *experts, group_sizes, cfg.swiglu_limit)
        else:
            # Results in the parameters' type (the kernel accumulates in float32):
            # nothing fuses a convert into a grouped matmul, so float32 results
            # would double the bytes of every [n * k, .] tensor here and make
            # the backward products read float32 cotangents.
            gate = lax.ragged_dot(xs, mp["w_gate"], group_sizes, preferred_element_type=cfg.dtype)
            up = lax.ragged_dot(xs, mp["w_up"], group_sizes, preferred_element_type=cfg.dtype)
            act = _swiglu_act(gate, up, cfg).astype(cfg.dtype)
            ys = lax.ragged_dot(act, mp["w_down"], group_sizes, preferred_element_type=cfg.dtype)
        if held != E:  # rows behind the last group: whatever the product left there is not a number to weigh
            ys = jnp.where((jnp.arange(n * k) < jnp.sum(group_sizes))[:, None], ys, 0)
    with jax.named_scope("moe.combine"):
        out = combine_rows(ys, top_p, order, inverse).reshape(b, s, d)
    if "shared" in mp:
        with jax.named_scope("moe.shared"):
            out = out + _dense_ffn(h, mp["shared"], cfg)
    return (out, group_sizes if rows_per_expert is None else rows_per_expert) if counts else out


def _grouped_experts(xs, stack, index, group_sizes, limit: float = 0.0):
    """The experts' SwiGLU (clamped at `limit`, if any) of rows sorted by
    expert, xs [m, d] -> [m, d], each expert's matrices read where they lie in
    the group's stack {name: [layers, E, ., .]} at the layer `index` (as
    `_layer_of` takes it): the leading axes of a stack of periods are merged,
    which moves nothing."""
    from ..ops import grouped_matmul as gm

    if isinstance(index, tuple):
        lead = stack["w_gate"].shape[: len(index)]
        index = jnp.ravel_multi_index(index, lead, mode="clip")
        stack = {name: w.reshape(-1, *w.shape[len(lead) :]) for name, w in stack.items()}
    plan = gm.visits(group_sizes, xs.shape[0])
    act = gm.grouped_swiglu(xs, stack["w_gate"], stack["w_up"], index, plan, limit=limit)
    return gm.grouped_matmul(act, stack["w_down"], index, plan)


@functools.lru_cache(maxsize=None)
def _kda_cfg(cfg: TransformerConfig) -> TransformerConfig:
    """The config as a delta-rule layer reads it: `head_dim` is ITS head's d_k
    = d_v (`kda_head_dim`, where the full layers' d_head is another size);
    `n_heads` its value heads, the full layers' own count. Every function of
    the "kda" row takes this view first; the config itself where the two agree."""
    return cfg.replace(d_head=cfg.kda_head_dim) if cfg.kda_head_dim else cfg


def _kda_mixer(h, ap, cfg: TransformerConfig, attend):
    """A delta-rule layer's mixer on its normed input h [b, s, d] -> (its
    output [b, s, d], kept): the projections, the decay and beta (float32),
    then the caller's `attend(q, k, v, g, beta, conv) -> (o [b, s, n_heads,
    head_dim] float32, kept)`, which owns what a sequence keeps: q, k, v [b,
    s, heads * head_dim] are the projections BEFORE the convolution, in the
    parameters' type (what a tail stores; q and k of `kda_key_heads` heads
    where the config shares them), g [b, s, n_heads, head_dim], beta [b, s,
    n_heads], conv the three convolutions' weights; then the output norm a
    head, the gate and `wo`. `kda_decay` "channel": a decay a key channel
    through a thin pair, beta in (0, 2), a thin gate. "head": one decay a
    head, broadcast over its channels for the one recurrence, beta in (0, 1),
    a full-width gate 2 sigmoid(z)."""
    from ..ops import kda

    cfg = _kda_cfg(cfg)
    by_head = cfg.kda_decay == "head"

    def proj(x, w):
        return jnp.einsum("bsd,dk->bsk", x, w, preferred_element_type=jnp.float32)

    with jax.named_scope("attn.qkv"):
        q, k, v = [proj(h, ap[name]).astype(cfg.dtype) for name in ("wq", "wk", "wv")]
    with jax.named_scope("kda.gates"):
        if by_head:
            g, beta = kda.gates_a_head(proj(h, ap["w_a"]), ap["a_log"], ap["dt_bias"], proj(h, ap["w_b"]), cfg.head_dim)
        else:
            f = proj(proj(h, ap["w_fa"]).astype(cfg.dtype), ap["w_fb"])
            g, beta = kda.gates(f, ap["a_log"], ap["dt_bias"], proj(h, ap["w_b"]))
    with jax.named_scope("attn.core"):
        o, kept = attend(q, k, v, g, beta, tuple(ap["conv_" + name] for name in "qkv"))
    with jax.named_scope("kda.out"):
        if by_head:
            gate = proj(h, ap["w_z"])
        else:
            gate = proj(proj(h, ap["w_ga"]).astype(cfg.dtype), ap["w_gb"]) + ap["b_g"].astype(jnp.float32)
        o = o * lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps) * ap["o_norm"]["scale"].astype(jnp.float32)
        y = (o.reshape(*gate.shape) * (2.0 * jax.nn.sigmoid(gate) if by_head else jax.nn.sigmoid(gate))).astype(cfg.dtype)
    with jax.named_scope("attn.out"):
        return _ckpt(proj(y, ap["wo"]).astype(cfg.dtype), "attn_out_bf16"), kept


def latent_softmax_scale(cfg: TransformerConfig) -> float:
    """What a latent-attention layer multiplies its scores by: 1 / sqrt of the
    query head (qk_nope_dim + qk_rope_dim), and under YaRN m(factor,
    mscale_all_dim)^2, the family's correction of the softmax's temperature
    for the stretched context."""
    scale = 1.0 / math.sqrt(cfg.qk_nope_dim + cfg.qk_rope_dim)
    if cfg.rope_scaling:
        scale *= _yarn_mscale(cfg.rope_scaling[1], cfg.rope_scaling[6]) ** 2
    return scale


def _mla_mixer(h, ap, cfg: TransformerConfig, cos, sin, attend):
    """A latent-attention layer's mixer on its normed input h [b, s, d] ->
    (its output [b, s, d], kept): the query through its low-rank pair and the
    norm between, split into a head's `q_nope` and rotated `q_rope`; ONE
    down-projection a position into the normed latent `c_kv` and the rotated
    key part `k_r`, which all heads share; then the caller's `attend(q_nope
    [b, s, n_heads, qk_nope_dim], q_rope [b, s, n_heads, qk_rope_dim], c_kv
    [b, s, kv_lora_rank], k_r [b, s, qk_rope_dim], w_uk, w_uv) -> (o [b, s,
    n_heads, v_head_dim], kept)`, which owns what a sequence keeps and in
    which form the up-projections are applied (expanded onto c_kv, or
    absorbed into the query and the output); then the output's sigmoid gate
    from h, where the config has one (`attn_gate`), and `wo`."""
    b, s, _ = h.shape
    c, nope = cfg.kv_lora_rank, cfg.qk_nope_dim
    interleave = cfg.rope_style == "interleaved"

    def proj(x, w):
        return jnp.einsum("bsd,dk->bsk", x, w, preferred_element_type=jnp.float32).astype(cfg.dtype)

    def rotate(x):  # [b, s, heads, qk_rope_dim]
        return _rotate(x.astype(jnp.float32), cos, sin, interleave).astype(cfg.dtype)

    with jax.named_scope("attn.mla.q"):
        c_q = rms_norm(proj(h, ap["wq_a"]), _norm_scale(ap["q_a_norm"]["scale"], cfg), cfg.norm_eps)
        q = proj(c_q, ap["wq_b"])
        if b * s < cfg.d_model:
            # As `_block` says of `wq`: with nothing between the projection and its split into heads the compiler gives
            # the dot a head-shaped result and re-lays `wq_b` out for it, a transpose of 75 MB a layer in every step
            # and chunk; while a call has fewer rows than the weight, the result is what should move.
            q = lax.optimization_barrier(q)
        q = q.reshape(b, s, cfg.n_heads, cfg.head_dim)
        q_nope, q_rope = q[..., :nope], rotate(q[..., nope:])
    with jax.named_scope("attn.mla.kv_down"):
        kv = proj(h, ap["wkv_a"])
        c_kv = rms_norm(kv[..., :c], _norm_scale(ap["kv_a_norm"]["scale"], cfg), cfg.norm_eps)
        k_r = rotate(kv[:, :, None, c:])[:, :, 0]
    with jax.named_scope("attn.core"):
        o, kept = attend(q_nope, q_rope, c_kv, k_r, ap["w_uk"], ap["w_uv"])
    if cfg.attn_gate:  # on the heads' outputs, expanded or absorbed alike: behind `w_uv`, before `wo`
        with jax.named_scope("attn.gate"):
            gate = jnp.einsum("bsd,dk->bsk", h, ap["wg"], preferred_element_type=jnp.float32)
            o = (o.reshape(*gate.shape) * jax.nn.sigmoid(gate)).astype(cfg.dtype)
    with jax.named_scope("attn.mla.out"):
        out = proj(o.reshape(b, s, cfg.n_heads * cfg.v_head_dim), ap["wo"])
    return _ckpt(out, "attn_out_bf16"), kept


def _kda_widths(cfg: TransformerConfig) -> Tuple[int, int, int]:
    """Channels of a delta-rule layer's q, k and v projections (`cfg`: `_kda_cfg`'s view)."""
    keys = (cfg.kda_key_heads or cfg.n_heads) * cfg.head_dim
    return keys, keys, cfg.n_heads * cfg.head_dim


def _kda_inputs(cfg: TransformerConfig, q, k, v, conv, tails, n_valid=None):
    """What the recurrence reads, from the projections of ONE sequence's rows
    in order, q, k [c, key heads * head_dim], v [c, n_heads * head_dim]: the
    short convolutions from `tails` (`_tail_shape`: (K - 1) rows of each
    projection's channels), a sequence's slot of the pool (q's, k's and v's
    rows before these, one after another; zeros at a sequence's start), SiLU,
    the split into heads, the l2 norms of q and k, and where value heads
    share key heads, value head j's q and k from key head j // their ratio ->
    (q, k, v [c, n_heads, head_dim] float32, the tails after the first
    `n_valid` rows: all of them if None)."""
    from ..ops import kda

    widths, rows = _kda_widths(cfg), cfg.kda_conv - 1
    alike = len(set(widths)) == 1
    with jax.named_scope("kda.conv"):
        if alike:  # three tails of one width: the flat layout below IS this view
            tails = tails.reshape(3, cfg.kda_conv - 1, cfg.n_heads * cfg.head_dim)
        else:
            flat, ends = tails.reshape(-1), [rows * sum(widths[: i + 1]) for i in range(3)]
            tails = [flat[end - rows * w : end].reshape(rows, w) for end, w in zip(ends, widths)]
        ys, new_tails = zip(*(kda.short_conv(x, w, tail, n_valid) for x, w, tail in zip((q, k, v), conv, tails)))
        q, k, v = (y.reshape(y.shape[0], -1, cfg.head_dim) for y in ys)
        q, k = kda.qk_norms(q, k)
        if q.shape[1] != cfg.n_heads:
            q, k = (jnp.repeat(t, cfg.n_heads // t.shape[1], axis=1) for t in (q, k))
    if alike:
        return q, k, v, jnp.stack(new_tails).reshape(_tail_shape(cfg))
    return q, k, v, jnp.concatenate([t.reshape(-1) for t in new_tails]).reshape(_tail_shape(cfg))


def _block(x, layer_params, cfg: TransformerConfig, cos, sin, attend, stats: str = "", experts=None, kind: str = "softmax"):
    """THE transformer block, x [b, s, d] -> [b, s, d]: norm, q/k/v, rope,
    attention, output projection, residual, norm, feed-forward, residual.
    What differs between training, prefill and decode is how q attends,
    and the caller passes that, one of the three forms of the layer's `kind`
    (a row of `KINDS`): `attend(q, k, v) -> (o [b, s, n_heads, head_dim],
    kept)`, with q and k after rope; a "retention" layer's `attend(q, k, v,
    log_g)`, log_g [b, s, n_kv_heads] float32 the gate's log-sigmoid; a "window" layer's `attend(q, k, v, sink)` where it has
    sink logits [n_heads] (its k and v of `window_kv_heads` heads; v `value_dim` wide, there and in a softmax layer); a "kda"
    layer's as `_kda_mixer` calls it, a "latent" layer's as `_mla_mixer` does. `kept` is whatever the caller wants back
    (the cache leaves it wrote into; None in training). Returns (out, kept),
    and as a third what the router did with this layer's input if `stats` is
    "route" (`_route_stats`), or the rows each expert took [E] if it is
    "experts" (None from a dense FFN).
    `experts`: a routed layer's expert matrices where the caller keeps them
    out of `layer_params` (`_experts_in_place`), as `_routed_ffn` takes them. The
    _ckpt names are the save frontier of remat_policy="hot"; outside
    jax.checkpoint they are the identity."""
    b, s, d = x.shape
    ap, mp = layer_params["attn"], layer_params["mlp"]

    h = _norm(x, layer_params["attn_norm"]["scale"], cfg)
    if kind in ("kda", "latent"):  # a mixer of its own around the caller's `attend`
        attn_out, kept = _kda_mixer(h, ap, cfg, attend) if kind == "kda" else _mla_mixer(h, ap, cfg, cos, sin, attend)
        return _block_ffn(x, attn_out, kept, h, layer_params, cfg, stats, experts)
    # Where q and k are split into heads decides which operand of their
    # projections moves. Split before rope, the dot's result is head-shaped
    # and the compiler lays the weight out for it: a slice and a transpose of
    # `wq` / `wk` a layer. Split after rope, the weight is read where it lies
    # in the stack and the result is what gets re-laid-out for `attend`. The
    # smaller of the two should move: the weight while a call has at least
    # as many rows as the weight has (a training batch), the result while
    # it has fewer (a decode step, a prefill chunk).
    q, k, v, gate = _qkv(h, ap, cfg, split=b * s >= d, gated=kind == "retention")
    rotates = not cfg.rope_layers or any(cfg.rope_layers)  # where no layer does, nothing to switch and nothing computed
    if not rotates:
        # Rope's elementwise pass is also what keeps the split into heads off the projections: with nothing between
        # them the compiler gives the dot a head-shaped result and re-lays `wq` out for it, a transpose of 67 MB a step.
        q, k = lax.optimization_barrier((q, k))
    with jax.named_scope("attn.rope"):
        q = _ckpt(apply_rope(q, cos, sin, cfg) if rotates else q, "q_bf16")
        k = _ckpt(apply_rope(k, cos, sin, cfg) if rotates else k, "k_bf16")
    with jax.named_scope("attn.core"):
        v = _ckpt(v, "v_bf16")
        heads = [t.reshape(b, s, -1, width) for t, width in ((q, cfg.head_dim), (k, cfg.head_dim), (v, cfg.value_dim))]
        # beside q, k, v: a retention layer's log-gate, a window layer's sink logits
        more = (ap["sink"],) if "sink" in ap else () if gate is None else (jax.nn.log_sigmoid(gate),)
        o, kept = attend(*heads, *more)
        o = o.reshape(b, s, cfg.n_heads * cfg.value_dim)
    if cfg.attn_gate:
        with jax.named_scope("attn.gate"):
            gate = jnp.einsum("bsd,dk->bsk", h, ap["wg"], preferred_element_type=jnp.float32)
            o = (o * jax.nn.sigmoid(gate)).astype(cfg.dtype)
    with jax.named_scope("attn.out"):
        attn_out = _ckpt(
            jnp.einsum(
                "bsk,kd->bsd", o, ap["wo"], preferred_element_type=jnp.float32
            ).astype(cfg.dtype),
            "attn_out_bf16",
        )
    return _block_ffn(x, attn_out, kept, h, layer_params, cfg, stats, experts)


def _block_ffn(x, attn_out, kept, h, layer_params, cfg: TransformerConfig, stats: str, experts):
    """`_block` from the mixer's output on: the residual, the feed-forward
    and its residual; `h` the mixer's normed input (a parallel block's
    feed-forward reads it too)."""
    b, s, d = x.shape
    mp = layer_params["mlp"]
    if cfg.post_norms:
        attn_out = _norm(attn_out, layer_params["post_attn_norm"]["scale"], cfg)

    # Parallel block (GPT-J): MLP reads the SAME pre-norm as attention and
    # both sum into the residual; sequential (llama) re-norms after attn.
    if cfg.parallel_block:
        mlp_in = h
    else:
        with jax.named_scope("residual"):
            x = x + attn_out
        mlp_in = _norm(x, layer_params["mlp_norm"]["scale"], cfg)
    with jax.named_scope("norm"):  # the norm's output, named for the save frontier
        mlp_in = _ckpt(mlp_in, "mlp_in_bf16")
    if stats == "experts" and "router" in mp:  # forward_decode: the experts this step's rows chose
        mlp_out, rows_per_expert = _routed_ffn(mlp_in, mp, cfg, counts=True, experts=experts)
    else:
        mlp_out, rows_per_expert = _ffn(mlp_in, mp, cfg, experts), None
    if cfg.post_norms:
        mlp_out = _norm(mlp_out, layer_params["post_mlp_norm"]["scale"], cfg)
    with jax.named_scope("residual"):
        out = x + attn_out + mlp_out if cfg.parallel_block else x + mlp_out
    if stats == "route":  # routing_stats: what the router did with this layer's input
        return out, kept, _route_stats(mlp_in.reshape(b * s, d), mp, cfg)
    if stats == "experts":
        return out, kept, rows_per_expert
    return out, kept


def _naive_only(cfg: TransformerConfig, kind: str, expression: str):
    """As with windows: the flash, ring and ulysses kernels are softmax
    attention, and a layer of another kind through them would be another model."""
    if cfg.attn_impl != "naive":
        raise ValueError(
            f"attn_impl={cfg.attn_impl!r} computes softmax attention; a config with {kind} layers "
            f"runs its whole-sequence forward with attn_impl='naive' (`{expression}`)"
        )


def _softmax_whole(cfg: TransformerConfig, mesh: Optional[Mesh], where: LayerPlace):
    if cfg.value_dim != cfg.head_dim:  # the flash, ring and ulysses kernels take one head size
        _naive_only(cfg, "softmax layers whose values are narrower than their keys, and window", "attention_reference")
    return lambda q, k, v: (_attention(q, k, v, cfg, mesh, where.window), None)


def _window_whole(cfg: TransformerConfig, mesh: Optional[Mesh], where: LayerPlace):
    """A window layer of a stack with rings over whole sequences: the masked plain expression, its window static."""
    _naive_only(cfg, "window", "_window_attention")

    def attend(q, k, v, sink=None):
        with jax.named_scope("attn.window"):
            return _window_attention(q, k, v, max(cfg.windows), sink), None

    return attend


def _retention_whole(cfg: TransformerConfig, mesh: Optional[Mesh], where: LayerPlace):
    _naive_only(cfg, "retention", "retention_whole")
    return lambda q, k, v, log_g: (retention_whole(q, k, v, log_g).astype(q.dtype), None)


def _kda_whole(cfg: TransformerConfig, mesh: Optional[Mesh], where: LayerPlace):
    """Each sequence from zero tails and a zero state, the chunked form."""
    from ..ops import kda

    _naive_only(cfg, "kda", "_kda_whole")
    cfg = _kda_cfg(cfg)

    def one(q, k, v, g, beta, conv):
        zeros = jnp.zeros(_tail_shape(cfg), q.dtype)
        q, k, v, _ = _kda_inputs(cfg, q, k, v, conv, zeros)
        with jax.named_scope("kda.chunk"):
            return kda.kda_chunk(q, k, v, g, beta, jnp.zeros((cfg.n_heads, cfg.head_dim, cfg.head_dim), jnp.float32))[0]

    return lambda q, k, v, g, beta, conv: (jax.vmap(one, in_axes=(0, 0, 0, 0, 0, None))(q, k, v, g, beta, conv), None)


def _latent_whole(cfg: TransformerConfig, mesh: Optional[Mesh], where: LayerPlace):
    """The expanded form over whole sequences: every position's latent becomes
    every head's key part and value, and a head attends with `[k_nope | k_r]`,
    the masked plain expression, float32 softmax."""
    _naive_only(cfg, "latent", "_latent_whole")
    scale = latent_softmax_scale(cfg)

    def attend(q_nope, q_rope, c_kv, k_r, w_uk, w_uv):
        s = q_nope.shape[1]
        with jax.named_scope("attn.mla.expand"):
            k_nope = jnp.einsum("bsc,hnc->bshn", c_kv, w_uk, preferred_element_type=jnp.float32).astype(cfg.dtype)
            v = jnp.einsum("bsc,hcv->bshv", c_kv, w_uv, preferred_element_type=jnp.float32).astype(cfg.dtype)
        scores = jnp.einsum("bqhn,bkhn->bhqk", q_nope, k_nope, preferred_element_type=jnp.float32)
        scores = (scores + jnp.einsum("bqhr,bkr->bhqk", q_rope, k_r, preferred_element_type=jnp.float32)) * scale
        scores = jnp.where(jnp.arange(s)[:, None] >= jnp.arange(s)[None, :], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
        return jnp.einsum("bhqk,bkhv->bqhv", probs, v, preferred_element_type=jnp.float32).astype(cfg.dtype), None

    return attend


def _walk_stack(params: PyTree, cfg: TransformerConfig, step, carry, in_place: bool):
    """THE walk of the layer stack: `stack_plan(cfg)`'s segments in order,
    each layer through `step(kind, carry, where, layer_params, experts) ->
    (carry, y)`: `where` a `LayerPlace`, `experts` as `_routed_ffn` takes them.
    A segment of single alike layers is one `lax.scan` over them, the layer's
    place and its `_per_layer` values riding as xs beside its weights. Any
    other is a scan over its repeats whose body takes each member in order,
    directly where it has one layer and through an inner scan where it has
    several. `in_place` (the serving steps) keeps a routed stack's expert
    matrices out of the scans' xs (`_experts_in_place`) and hands `step` the
    stack with the layer's index in it; training scans them like every other
    weight, and no place rides its scans of single layers (nothing is kept:
    LayerPlace.layer is None there). Returns (carry, a segment's ys each: a
    tuple of its members' stacked ys, [repeats, ...] or [repeats, layers, ...])."""
    segments_ys, first_layer = [], 0
    for repeats, members in stack_plan(cfg):
        trees = [_experts_in_place(params[m.tree]) if in_place else (params[m.tree], None) for m in members]
        if len(members) == 1 and members[0].layers == 1:
            (member,), ((riding, stack),) = members, trees

            def layer(carry, xs, member=member, stack=stack):
                at, window, rope_on, layer_params = xs
                experts = None if stack is None else (stack, at - member.first)
                return step(member.kind, carry, LayerPlace(at, window, rope_on), layer_params, experts)

            at = jnp.arange(member.first, member.first + repeats) if in_place else None
            carry, ys = lax.scan(layer, carry, (at, *_per_layer(cfg, first_layer, repeats), riding))
            ys = (ys,)
        else:

            def repeat(carry, xs, members=members, trees=trees):
                r, *riding = xs
                ys = []
                for m, (_, stack), layer_params in zip(members, trees, riding):
                    if m.layers == 1:
                        carry, y = step(m.kind, carry, LayerPlace(m.place(r), None, None), layer_params, None if stack is None else (stack, r))
                    else:

                        def layer(carry, xs, m=m, stack=stack):
                            j, layer_params = xs
                            return step(m.kind, carry, LayerPlace(m.place(r, j), None, None), layer_params, None if stack is None else (stack, (r, j)))

                        carry, y = lax.scan(layer, carry, (jnp.arange(m.layers), layer_params))
                    ys.append(y)
                return carry, tuple(ys)

            carry, ys = lax.scan(repeat, carry, (jnp.arange(repeats), *(riding for riding, _ in trees)))
        segments_ys.append(ys)
        first_layer += repeats * sum(m.layers for m in members)
    return carry, segments_ys


def _in_stack_order(plan, segments_ys):
    """`_walk_stack`'s ys as one tree of [layers, ...] leaves in published
    order, over the segments that gave any (a dense segment's routers: none)."""
    tree_map, parts = jax.tree_util.tree_map, []
    for (repeats, members), ys in zip(plan, segments_ys):
        if jax.tree_util.tree_leaves(ys):
            # each member's [repeats, its layers, ...], side by side a repeat, then the repeats in a row
            ys = [tree_map(lambda t, m=m: t.reshape(repeats, m.layers, *t.shape[1 + (m.layers > 1) :]), y) for m, y in zip(members, ys)]
            parts.append(tree_map(lambda *ts: jnp.concatenate(ts, axis=1).reshape(-1, *ts[0].shape[2:]), *ys))
    return tree_map(lambda *ts: jnp.concatenate(ts), *parts)


def _embed(params: PyTree, tokens, cfg: TransformerConfig):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"]["embedding"], tokens, axis=0)
        return x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype) if cfg.embed_scale else x


def _logits(params: PyTree, x):
    """Final-norm hidden states [..., d] -> logits [..., vocab] float32."""
    with jax.named_scope("head"):
        return jnp.einsum("...d,dv->...v", x, _head(params), preferred_element_type=jnp.float32)


def _route_stats(x, mp, cfg: TransformerConfig):
    k = cfg.n_experts_per_tok
    ranked, experts = lax.top_k(_router_probs(x, mp, cfg)[1], k + 1)
    return {
        "experts": experts[:, :k],
        "tokens_per_expert": _tokens_per_expert(experts[:, :k], cfg.n_experts),
        "gap": ranked[:, k - 1] - ranked[:, k],
    }


def forward_hidden(
    params: PyTree,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """tokens [batch, seq] -> final-norm hidden states [batch, seq, d]."""
    b, s = tokens.shape
    cos, sin = rope_tables(cfg, s)
    x = _embed(params, tokens, cfg)

    rope = {"window": rope_tables(_window_rope_cfg(cfg), s)} if cfg.window_rope_theta else {}

    def layer(kind, x, where, layer_params):
        attend = KINDS[kind].whole(cfg, mesh, where)
        return _block(x, layer_params, cfg, *_rope_switch(*rope.get(kind, (cos, sin)), where.rope_on), attend, kind=kind)[0]

    if cfg.remat:
        if cfg.remat_policy == "dots":
            policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        elif cfg.remat_policy == "attn":
            # Save ONLY the flash kernel's o+lse (named in its vjp fwd):
            # the attention forward — the most expensive recompute under
            # full remat — never re-runs in bwd, while the cheap qkv
            # projections still rematerialize. ~16 MB/layer saved vs ~1/4
            # of attention wall time recovered (measured r5).
            policy = jax.checkpoint_policies.save_only_these_names(
                "flash_o", "flash_lse"
            )
        elif cfg.remat_policy == "hot":
            # Selective remat (measured best on v5e, r5): save ONLY the
            # named bf16 frontier (HOT_SAVE_NAMES, ~176 MB/layer at bench
            # shapes vs ~2 GB/layer of fp32 saveables) — the bwd then
            # recomputes just the norms and the gate/up MLP dots (~10%
            # extra layer FLOPs) instead of the whole layer (~33%).
            policy = jax.checkpoint_policies.save_only_these_names(
                *HOT_SAVE_NAMES
            )
        else:
            policy = None
        layer = jax.checkpoint(layer, policy=policy, static_argnums=(0,))

    x, _ = _walk_stack(params, cfg, lambda kind, x, where, layer_params, _experts: (layer(kind, x, where, layer_params), None), x, in_place=False)
    return _norm(x, params["final_norm"]["scale"], cfg)


def forward(
    params: PyTree,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Optional[Mesh] = None,
) -> jax.Array:
    """tokens [batch, seq] int32 -> logits [batch, seq, vocab] float32."""
    return _logits(params, forward_hidden(params, tokens, cfg, mesh))


def _head(params: PyTree):
    """The logits' weights [d, vocab]: `lm_head`, or a tied embedding transposed."""
    head = params.get("lm_head")
    return params["embed"]["embedding"].T if head is None else head


# Float32 logits that `head_loss` holds at one time: it walks the rows in the
# largest chunks whose [rows, vocab] float32 stay under this. A chunk's product
# and its three passes (max, sum of exp, dlogits) are bound by their bytes, not
# by the chunks' count (the function alone on the chip: 256 MiB, 512 MiB and 1
# GiB read the same to 0.03 ms of 59), so the budget is what the step's peak can
# spare (Mistral's 3 x 4096 rows hold 1.5 GiB whole, OLMoE's 4 x 4096 3.1), and
# few chunks: the backward products read up to four laid-out chunks where they
# were written; OLMoE's eight at 512 MiB were first copied into one buffer, 4.5
# ms a step (PERF.md, PR 60). 1 GiB: two chunks a Mistral step, four OLMoE's.
HEAD_LOSS_CHUNK_BYTES = 1 << 30
# Up to this many chunks are laid out one after another; more of them become a
# loop. Behind a loop the TPU compiler's scheduler put the head's gradient (and
# the optimizer's update it fuses with it) AFTER the layers' backward, with
# dlogits alive all through it (or, the two backward products tied by a barrier,
# the head's gradient): 0.64 (0.25) GiB more in the plan of Mistral's step,
# whose peak lies there, and the compiler then recomputed MLP products: that
# step read 686 ms where the parent's reads 662 (PERF.md, PR 60). Laid out, both
# backward products are scheduled where the plain expression's stood, before
# the layers' backward.
HEAD_LOSS_CHUNKS_LAID_OUT = 16


def _loss_chunk_rows(rows: int, vocab: int) -> int:
    """The largest divisor of `rows` whose float32 logits fit the budget (1 where none does)."""
    most = max(1, HEAD_LOSS_CHUNK_BYTES // (4 * vocab))
    return max(c for c in range(1, min(rows, most) + 1) if rows % c == 0)


def _head_loss_rows(x, head, targets, weights):
    """One chunk: rows [..., d] -> (sum of weights x nll, nll [...], dlogits [..., vocab] in the head's dtype)."""
    with jax.named_scope("head"):
        logits = jnp.einsum("...d,dv->...v", x, head, preferred_element_type=jnp.float32)
    with jax.named_scope("loss"):
        shifted = logits - jnp.max(logits, axis=-1, keepdims=True)
        e = jnp.exp(shifted)
        total = jnp.sum(e, axis=-1, keepdims=True)
        hit = lax.broadcasted_iota(targets.dtype, logits.shape, logits.ndim - 1) == targets[..., None]
        nll = jnp.log(total[..., 0]) - jnp.sum(jnp.where(hit, shifted, 0.0), axis=-1)
        # d loss / d logits, rounded where it is made: the MXU rounds this operand of both backward products to the
        # parameters' dtype anyway (DEFAULT precision), and float32 of it is twice the bytes for them to fetch
        dlogits = ((e / total - hit.astype(jnp.float32)) * weights[..., None]).astype(head.dtype)
        return jnp.sum(nll * weights), nll, dlogits


@partial(jax.custom_vjp, nondiff_argnums=(4,))
def head_loss(x, head, targets, weights, chunked=True):
    """sum over rows of weights x cross-entropy(x @ head, targets), float32.

    x [..., d] and head [d, vocab] in the parameters' dtype, targets [...]
    int, weights [...] float32 (a mean's: mask / count). Logits, log-sum-exp
    and the sum are float32 as `log_softmax(_logits(...))` makes them, but the
    logits live a chunk of rows at a time (`HEAD_LOSS_CHUNK_BYTES`), and the
    derivative is written by hand: d loss / d logits is formed once, in the
    forward pass, and kept in the head's dtype; the backward pass is its two
    products with x and with the head, parameters' dtype x parameters' dtype
    -> float32, and the loss's cotangent on their results. Three products a
    step, none repeated. `chunked` False: one chunk whatever its size, for a
    caller whose rows are sharded over devices (a chunk would be one device's)."""
    return _head_loss_fwd(x, head, targets, weights, chunked)[0]


def _head_loss_fwd(x, head, targets, weights, chunked):
    rows, vocab = math.prod(targets.shape), head.shape[1]
    chunk = _loss_chunk_rows(rows, vocab) if chunked else rows
    if chunk == rows:  # as it is shaped: nothing to reshape
        loss, nll, dlogits = _head_loss_rows(x, head, targets, weights)
    else:

        def step(loss, chunk_of):
            # a chunk starts when the one before has written its dlogits: laid out, the compiler would else fuse the
            # chunks' passes side by side and hold every chunk's float32 logits at once
            loss, x_c = lax.optimization_barrier((loss, chunk_of[0]))
            part, nll, dlogits = _head_loss_rows(x_c, head, *chunk_of[1:])
            loss, dlogits = lax.optimization_barrier((loss + part, dlogits))
            return loss, (nll, dlogits)

        n = rows // chunk
        in_chunks = tuple(a.reshape(n, chunk, *a.shape[targets.ndim :]) for a in (x, targets, weights))
        with jax.named_scope("loss"):
            loss, (nll, dlogits) = lax.scan(step, jnp.zeros((), jnp.float32), in_chunks, unroll=n <= HEAD_LOSS_CHUNKS_LAID_OUT)
        nll = nll.reshape(targets.shape)  # dlogits stay [chunks, rows, vocab]: the backward products read the chunks where they were written
    return loss, (x, head, targets, nll, dlogits)


def _head_loss_bwd(_chunked, saved, g):
    x, head, targets, nll, dlogits = saved
    with jax.named_scope("head"):
        rows = x.reshape(*dlogits.shape[:-1], x.shape[-1])
        dx = jnp.einsum("...v,dv->...d", dlogits, head, preferred_element_type=jnp.float32)
        dhead = jnp.einsum("...d,...v->dv", rows, dlogits, preferred_element_type=jnp.float32)
    with jax.named_scope("loss"):
        dx, dhead = (g * dx).astype(x.dtype).reshape(x.shape), (g * dhead).astype(head.dtype)
        return dx, dhead, np.zeros(targets.shape, jax.dtypes.float0), g * nll


head_loss.defvjp(_head_loss_fwd, _head_loss_bwd)


def next_token_loss(
    params: PyTree,
    tokens: jax.Array,
    cfg: TransformerConfig,
    mesh: Optional[Mesh] = None,
    *,
    mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Causal LM loss: mean cross-entropy of token t+1 given tokens <= t.

    Runs the forward at full sequence length and masks the final position
    (rather than slicing to seq-1) so the sequence dim stays divisible by
    the "seq" mesh axis under sequence parallelism.

    The head and the loss are one function with its own derivative
    (`head_loss`): float32 are the logits, the log-sum-exp, the picked logit,
    the mean and every product's accumulation; the parameters' dtype
    (bfloat16) are the products' operands, d loss / d logits among them. No
    [batch, seq, vocab] float32 exists: the logits are made and used a chunk
    of rows at a time. `forward()` still returns whole float32 logits to
    whoever wants them."""
    x = forward_hidden(params, tokens, cfg, mesh)
    with jax.named_scope("loss"):
        s = tokens.shape[1]
        targets = jnp.roll(tokens, -1, axis=1)
        m = jnp.broadcast_to(jnp.arange(s)[None, :] < s - 1, tokens.shape).astype(jnp.float32)  # last position has no target
        if mask is not None:
            m = m * jnp.roll(mask, -1, axis=1).astype(jnp.float32)
        weights = m / jnp.maximum(jnp.sum(m), 1.0)
    # under a mesh that shards batch or sequence the rows stay as they lie: one chunk
    return head_loss(x, _head(params), targets, weights, mesh is None or mesh.size == 1)


@partial(jax.jit, static_argnames=("cfg",))
def routing_stats(params: PyTree, tokens: jax.Array, cfg: TransformerConfig) -> Dict[str, jax.Array]:
    """What the routers did with tokens [batch, seq], layer by layer (the
    program's counter for the routed FFN; the forward runs as in training):
    experts [L, batch*seq, k] each token's experts, most probable first;
    tokens_per_expert [L, E] (every row sums to batch*seq*k: nothing is
    dropped); gap [L, batch*seq] between the last probability a token took
    and the first it left out (how close each token is to another choice)."""
    cos, sin = rope_tables(cfg, tokens.shape[1])
    x = _embed(params, tokens, cfg)

    def layer(kind, x, where, layer_params, _experts):
        routed = "router" in layer_params["mlp"]  # a routed model's leading dense layers route nothing
        out, _, *route = _block(
            x, layer_params, cfg, *_rope_switch(cos, sin, where.rope_on), KINDS[kind].whole(cfg, None, where),
            stats="route" if routed else "", kind=kind,
        )
        return out, (route[0] if routed else None)

    _, segments = _walk_stack(params, cfg, layer, x, in_place=False)
    return _in_stack_order(stack_plan(cfg), segments)


def build_train_step(
    cfg: TransformerConfig,
    tx,
    mesh: Mesh,
    *,
    zero_axis: Optional[str] = None,
    donate: bool = True,
):
    """The standard data-parallel train step (fwd+bwd+optimizer), with the
    optimizer update optionally ZeRO-sharded over `zero_axis`
    (train/zero.py: reduce_scatter grads -> shard-local update ->
    all_gather the updates; per-chip optimizer state ~1/N).

    Returns `(init_state, step)`:
      init_state(rng) -> (params, opt_state)  [opt_state sharded when zero]
      step(params, opt_state, tokens) -> (params, opt_state, loss)
    `tokens` is the global [batch, seq] int array, batch-sharded over
    `zero_axis` in the ZeRO path.
    """
    import optax

    # State starts replicated over the mesh, as the step returns it: a leaf
    # left on the default device (the params, adam's scalar count) would
    # give the second call a new input type — a second compile of the
    # whole step — and, on several devices, pile the model on device 0.
    replicated = NamedSharding(mesh, PartitionSpec())

    def init_on_mesh(rng):
        return jax.device_put(init_params(rng, cfg), replicated)

    if zero_axis is None:

        def init_state(rng):
            params = init_on_mesh(rng)
            return params, jax.device_put(tx.init(params), replicated)

        def train_step(params, opt_state, tokens):
            loss, grads = jax.value_and_grad(next_token_loss)(
                params, tokens, cfg, mesh
            )
            with jax.named_scope("optimizer"):
                updates, opt_state = tx.update(grads, opt_state, params)
                return optax.apply_updates(params, updates), opt_state, loss

        return init_state, jax.jit(
            train_step, donate_argnums=(0, 1) if donate else ()
        )

    from ..train import zero as _zero

    abstract = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    # Inside the shard_map block the step sees its LOCAL batch shard and a
    # replicated param copy; attention and loss run mesh-free per shard.
    step, _sharder = _zero.build_zero_step(
        lambda p, tokens: next_token_loss(p, tokens, cfg, None),
        tx,
        abstract,
        mesh,
        axis=zero_axis,
        donate=donate,
    )

    def init_state(rng):
        params = init_on_mesh(rng)
        return params, _zero.init_opt_state(tx, params, mesh, zero_axis)

    return init_state, step


def flops_per_token(cfg: TransformerConfig, seq_len: int) -> float:
    """Approximate training FLOPs/token (6N + attention) for MFU accounting.

    Attention is counted CAUSALLY (seq/2 average visible positions): the
    flash kernel skips fully-masked blocks, so charging full s^2 would
    inflate MFU by the skipped half. Per token per layer: QK^T + PV =
    2 matmuls x 2 MAC-FLOPs x (seq/2) x d_model forward, x3 for fwd+bwd.
    A routed FFN counts the router, the shared expert and the
    n_experts_per_tok experts a token passes through, not the experts it
    leaves alone; a window is not taken off the attention. A retention
    layer counts its gate, and in attention's place the chunked form: every
    query head reads the state (D x head_dim), every K/V head adds to it, and
    the in-chunk pairs (half a chunk visible on average). Under a share of
    the experts a token counts its picks that fall on held ones. A latent
    layer counts its low-rank pairs and up-projections and the expanded
    form's pairs (a head's keys qk_nope_dim + qk_rope_dim wide, its values
    v_head_dim). A KDA stack counts each layer by its kind (`cache_layout`)."""
    ffn = 3 * cfg.d_model * cfg.d_ff
    if cfg.n_experts:
        # under a share, the part of a token's n_experts_per_tok picks that falls on held experts
        ffn = ffn * cfg.n_experts_per_tok * cfg.experts_held / cfg.n_experts + cfg.d_model * (cfg.n_experts + 3 * cfg.d_ff_shared)
    nd = cfg.n_dense_layers
    n_kda = dict(cache_layout(cfg).kinds).get("kda", 0)
    wide = cfg.n_heads * cfg.head_dim
    hd = _kda_cfg(cfg).head_dim  # a delta-rule head's; `values`, `keys`: its value and key heads' channels
    values, keys = cfg.n_heads * hd, (cfg.kda_key_heads or cfg.n_heads) * hd
    mixer = (
        (3 if cfg.attn_gate else 2) * cfg.d_model * cfg.n_heads * cfg.head_dim
        + 2 * cfg.d_model * cfg.n_kv_heads * cfg.head_dim
        + (cfg.d_model * cfg.n_kv_heads if cfg.retention_degree else 0)
    )
    if cfg.kv_lora_rank:
        mixer = (
            cfg.d_model * (cfg.q_lora_rank + cfg.kv_lora_rank + cfg.qk_rope_dim) + cfg.q_lora_rank * wide
            + cfg.kv_lora_rank * cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim) + (2 if cfg.attn_gate else 1) * cfg.n_heads * cfg.v_head_dim * cfg.d_model
        )
    n_params = (
        cfg.vocab_size * cfg.d_model
        + (cfg.n_layers - n_kda) * mixer
        # a delta-rule layer: q, k (its key heads), v, o; beta; the decay's and the gate's thin pairs, or a head's one logit and the full-width gate
        + n_kda * (2 * cfg.d_model * (values + keys) + cfg.d_model * cfg.n_heads
                   + (cfg.d_model * (cfg.n_heads + values) if cfg.kda_decay == "head" else 2 * (cfg.d_model * hd + hd * values)))
        + (cfg.n_layers - nd) * ffn
        + nd * 3 * cfg.d_model * cfg.d_ff_dense
        + (0 if cfg.tie_embeddings else cfg.d_model * cfg.vocab_size)
    )
    attn = 6 * (cfg.n_layers - n_kda) * cfg.n_heads * (cfg.head_dim + (cfg.v_head_dim or cfg.head_dim)) * (seq_len / 2)
    # a KDA layer in the chunked form: the state read and added to (2 x d_k x d_v a head each way) and the in-chunk pairs
    attn += 6 * n_kda * cfg.n_heads * (2 * hd * hd + 2 * hd * min(64, seq_len) / 2)
    if cfg.retention_degree:
        D, hd = retention_state_dim(cfg.head_dim), cfg.head_dim
        visible = min(PREFILL_CHUNK_TOKENS, seq_len) / 2
        attn = 6 * cfg.n_layers * ((cfg.n_heads + cfg.n_kv_heads) * D * hd + 2 * cfg.n_heads * hd * visible)
    return 6.0 * n_params + attn


# ------------------------------------------------- paged prefill and decode
#
# Inference substrate for serve/llm: the KV cache is a pool of FIXED-SIZE
# pages shared by every sequence (vLLM's PagedAttention layout). Prefill
# walks what the cache lacks of a prompt in fixed-size chunks, the first
# starting where the cache ends: each writes its k/v into the pages its block
# table names and attends over those pages, its own and everything below
# it, where they lie. Decode appends the new position and attends over the
# pages each slot holds. Both read the pool through ops/paged_attention.py
# (the *_gather expressions below for shapes those kernels cannot tile) —
# all at static shapes ([B] slots, [B, P] block tables, [N] pages), so ONE
# compiled decode step serves every batch composition, one prefill
# executable a bucket serves every hit and miss, and the
# continuous-batching scheduler never triggers a recompile.
#
# Page 0 is reserved as a trash page: masked writes (inactive slots,
# positions beyond a sequence's length, shared prefix pages owned by the
# radix cache) are redirected there instead of predicated out, which
# keeps the scatter dense and shape-stable. Trash contents are never
# read — the attention mask stops at each sequence's length.

TRASH_PAGE = 0
# Positions one pass of the layers computes in forward_prefill: the FLOOR, and
# the chunk a span's tail is walked in. The weights are read once a chunk, so a
# chunk must be worth their pass: rows at the v5e ridge (240 FLOP/byte) times
# (the bytes of the layers' weights a pass reads) / (the bytes one ROW
# multiplies), `prefill_chunk_rows`. A dense stack multiplies all it reads:
# 256 rows (PERF.md, PR 31, DeepSeek, chunks laid at multiples of their size:
# 256 and 512 serve a miss-and-three-hits document alike, a miss 11 % dearer, a
# hit 23 % cheaper; 256 halves a short suffix's wait; 1 024 is 6 % behind). A
# routed stack reads every held expert and a row multiplies n_experts_per_tok /
# n_experts of them, so its ridge lies at 2.5 (GigaChat3.5) to 8.5 (Trinity)
# times the rows: the head of a long span is walked in BIG chunks of
# PREFILL_CHUNK_CAP rows (`prefill_big_chunk_tokens`; forward_prefill's
# `big_chunks`, which serve/llm's PagedLM asks for in one executable for all its
# buckets) and what is left of it, at least a row and at most a big chunk, in
# chunks of this size, so that what a prefix hit or a short prompt computes is
# still its uncached span rounded UP to THIS many rows.
PREFILL_CHUNK_TOKENS = 256
# The rows of a big chunk, whatever more the ridge asks, and what a stack's weights must ask for, in whole small chunks,
# to be given big chunks at all. One prefill of a cell's mean prompt with big chunks of none / 512 / 1 024 rows (PERF.md
# §6, PR 62, tools/prefill_chunk_bench.py, ms): MiMo-V2.5 (6 912 tokens; asks for 898 rows) 272.5 / 205.1 / 207.3,
# Trinity (2 048; 2 181) 67.3 / 51.1 / 50.6, Solar-Open2 (1 728; 1 217) 77.7 / 65.9 / 67.4: a second size would buy
# nothing, and beyond 1 024 rows a chunk's float32 activations leave VMEM (MiMo-V2's rope: 11 ms a prompt at 256 rows,
# 41 at 1 024). The two stacks that ask for less walk small chunks alone, though a miss reads faster in big ones
# (GigaChat3.5, 651 rows: 75.8 / 68.3 / 70.4 at 1 152 tokens; dots.vlm1, 692: 1 825 / 1 634 / 1 579 at 14 848): dots.vlm1's
# cell counts a wave of misses that ends sooner as fewer tokens (PERF.md §7 L2, ROADMAP S8 (h)) and cannot judge it.
PREFILL_CHUNK_CAP = 1024


def _tail_shape(cfg: TransformerConfig) -> Tuple[int, int]:
    """The shape a sequence's convolution tails are stored in, one delta-rule
    layer's (`cfg`: `_kda_cfg`'s view): the (kda_conv - 1) rows of each of q's,
    k's and v's channels as whole (16, 128) tiles where they make such (a slot
    is then whole tiles of the pool, and a prefill's write of one is no masked
    update of sixteen slots' tiles), else as one row."""
    total = (cfg.kda_conv - 1) * sum(_kda_widths(cfg))
    return (16, total // 16) if total % (16 * 128) == 0 else (1, total)


def paged_prefill_attention_gather(q, kp, vp, block_table, start, n_kv_heads: int, window=None, scale: Optional[float] = None):
    """The plain XLA expression of prefill's chunk attention: gathers the
    WHOLE block table [P] out of one layer's pages kp / vp
    [pages, page_tokens, n_kv_heads * head_dim] (vp's heads of a width of their
    own), casts it to float32 and
    softmaxes each row of q [C, n_heads, head_dim] (row i is position
    start + i) over the `P * T`-wide row under the causal mask, and under
    `window` (a scalar) only the last `window` positions of it; `scale`: the
    scores' factor where it is not head_dim^-0.5 (heads padded with zeros to
    whole lane tiles: `kv_page_widths`). The parity
    reference of ops/paged_attention.py's paged_prefill_attention and the
    path for shapes that kernel cannot tile (the tiny CPU widths)."""
    C, H, hd = q.shape
    P, T = block_table.shape[0], kp.shape[1]
    kb = kp[block_table].reshape(P * T, n_kv_heads, hd)
    vb = vp[block_table].reshape(P * T, n_kv_heads, vp.shape[-1] // n_kv_heads)
    if H != n_kv_heads:
        kb = jnp.repeat(kb, H // n_kv_heads, axis=1)
        vb = jnp.repeat(vb, H // n_kv_heads, axis=1)
    scores = jnp.einsum(
        "qhd,shd->hqs", q.astype(jnp.float32), kb.astype(jnp.float32)
    ) / (math.sqrt(hd) if scale is None else 1.0 / scale)
    seen = jnp.arange(P * T)[None, :] <= start + jnp.arange(C)[:, None]  # [C, P*T]
    if window is not None:
        seen &= jnp.arange(P * T)[None, :] > start + jnp.arange(C)[:, None] - window
    scores = jnp.where(seen[None], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("hqs,shv->qhv", attn, vb.astype(jnp.float32)).astype(q.dtype)


def paged_attention_gather(q, kp, vp, block_tables, lengths, n_kv_heads: int, window=None, scale: Optional[float] = None):
    """The plain XLA expression of decode attention: gathers every slot's
    WHOLE block table out of one layer's pages kp / vp
    [pages, page_tokens, n_kv_heads * head_dim], casts it to float32 and
    softmaxes the `P * T`-wide row under the length mask, and under `window`
    (a scalar) only its last `window` positions. q [B, n_heads,
    head_dim], lengths [B] (>= 1); vp's heads and `scale` as
    paged_prefill_attention_gather takes them. The parity reference of
    ops/paged_attention.py and the path for shapes that kernel cannot tile
    (the tiny CPU widths); its traffic is the table, not what is live."""
    B, H, hd = q.shape
    P, T = block_tables.shape[1], kp.shape[1]
    kb = kp[block_tables].reshape(B, P * T, n_kv_heads, hd)
    vb = vp[block_tables].reshape(B, P * T, n_kv_heads, vp.shape[-1] // n_kv_heads)
    if H != n_kv_heads:
        kb = jnp.repeat(kb, H // n_kv_heads, axis=2)
        vb = jnp.repeat(vb, H // n_kv_heads, axis=2)
    scores = jnp.einsum(
        "bhd,bshd->bhs", q.astype(jnp.float32), kb.astype(jnp.float32)
    ) / (math.sqrt(hd) if scale is None else 1.0 / scale)
    kv_mask = jnp.arange(P * T)[None, :] < lengths[:, None]  # [B, P*T]
    if window is not None:
        kv_mask &= jnp.arange(P * T)[None, :] >= lengths[:, None] - window
    scores = jnp.where(kv_mask[:, None, :], scores, -jnp.inf)
    attn = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhs,bshv->bhv", attn, vb.astype(jnp.float32)).astype(q.dtype)


def paged_attention_path(cfg: TransformerConfig, page_tokens: int) -> str:
    """Which expression forward_prefill and forward_decode attend with for
    this model and page size: "paged_kernel" where ops/paged_attention.py
    can tile the pool, else "xla_gather". Shapes decide, nothing else does."""
    from ..ops.paged_attention import can_tile

    k_lanes, v_lanes = kv_page_widths(cfg)
    return "paged_kernel" if can_tile(page_tokens, k_lanes, cfg.dtype, v_lanes) else "xla_gather"


def kv_page_widths(cfg: TransformerConfig) -> Tuple[int, int]:
    """(lanes of one K head in a K/V page, of one V head). A V head is
    `value_dim` wide. A K head is head_dim wide, or, where head_dim is more
    than one 128-lane tile and not whole tiles (192), padded with zeros to the
    next whole tile (256): the paged kernels slice a head out of a page's row
    at lane-tile borders. The padding is stored and read (a third more K bytes
    at 192); q is padded alike where it meets the pages, and the scores keep
    head_dim^-0.5."""
    hd = cfg.head_dim
    return (hd if hd <= 128 or hd % 128 == 0 else -(-hd // 128) * 128), cfg.value_dim


def _to_page_lanes(t, width: int):
    """t [..., heads, head_dim] with every head padded with zeros to `width` lanes (as it is where it is that wide)."""
    return t if t.shape[-1] == width else jnp.pad(t, [(0, 0)] * (t.ndim - 1) + [(0, width - t.shape[-1])])


# ---- the five kinds of layer, each with what a served sequence keeps of it
# (a stack may hold two of them: `stack_plan`, `cache_layout`)
#
# A kind's three forms are `attend` factories of one calling convention.
# `whole(cfg, mesh, where)`: a whole sequence that keeps nothing (training,
# `forward`, `routing_stats`). `chunk(cfg, ctx)` (forward_prefill: one chunk of
# one sequence) and `step(cfg, ctx)` (forward_decode: one token a row) are
# called once, outside the layer scans, with the operands of the chunk or the
# step (`ctx`: forward_prefill's block_table, dest_table, length, write_from,
# slot, rows = a chunk's positions, page_tokens, c0 = the chunk's first
# position; forward_decode's block_tables, pos, active, page_tokens), work out
# what all the kind's layers share, and hand back `attend_in(where, pool) ->
# attend` for each of them. `where`: the layer's `LayerPlace`; `pool`: the
# WHOLE cache tree {leaf name: array}, of which a kind reads and writes its own
# leaves, in place, and hands them back as `_block`'s `kept`, in its row's
# `names` order (the walker puts them back into the tree). Some lines below
# are held to their letter: benchmarks/tests/test_*_cell.py plant their faults
# by replacing them (a chunk's `s_in` / `state_in` / `tails_in` and what it
# stores, the pools' `"s": jnp.zeros(...)`, a latent chunk's `lp_ = lp.at[...]`).
#
# "softmax": K/V pages. A page holds `page_tokens` positions of one layer's k
# or v, [layers, pages, page_tokens, n_kv_heads * head_dim]: tokens x (head,
# dim) is the tile the paged kernels copy and multiply as it lies, so heads
# and dim are ONE axis of the stored array (split, the device's tiled layout
# would put heads where the kernel needs tokens, and every step would pay a
# relayout of the pool). A sequence's block table grows by a page as it
# fills, and a full page of one prompt may serve another. `k` and `v` have
# widths of their own: `kv_page_widths` (a V head `value_dim` wide, a 192-wide
# K head padded to 256 lanes).


def _kv_leaves(cfg: TransformerConfig, num_pages: int, page_tokens: int):
    shape = (cfg.n_layers, num_pages, page_tokens)
    return {name: jnp.zeros((*shape, cfg.n_kv_heads * width), cfg.dtype) for name, width in zip("kv", kv_page_widths(cfg))}


def _page_lanes(cfg: TransformerConfig):
    """(a K head's lanes in a page, what the attention expressions are told beside it): the scores' scale where the
    heads are padded (`kv_page_widths`), nothing where they are not."""
    k_lanes, _ = kv_page_widths(cfg)
    return k_lanes, ({"scale": cfg.head_dim**-0.5} if k_lanes != cfg.head_dim else {})


def _chunk_dest_pages(ctx):
    """(pages of a prefill chunk, where each is written): a page of the chunk
    that holds a position in [write_from, length) goes where the block table
    says; one wholly below `write_from` is another owner's, and one wholly
    past the length nobody's: both to the trash page."""
    T, c0 = ctx["page_tokens"], ctx["c0"]
    pages = ctx["rows"] // T
    first = c0 + jnp.arange(pages) * T
    writable = (first + T > ctx["write_from"]) & (first < ctx["length"])
    return pages, jnp.where(writable, lax.dynamic_slice_in_dim(ctx["dest_table"], c0 // T, pages), TRASH_PAGE)


def _step_dest(ctx):
    """(page, slot in it, length after the append) of each row of a decode
    step: an active row appends at its position, an inactive one (length 0)
    to the trash page."""
    T, pos, active = ctx["page_tokens"], ctx["pos"], ctx["active"]
    dest_page = jnp.where(active, ctx["block_tables"][jnp.arange(pos.shape[0]), pos // T], TRASH_PAGE)
    return dest_page, pos % T, jnp.where(active, pos + 1, 0)


def _kv_chunk(cfg: TransformerConfig, ctx):
    """The chunk's rows write their k/v into the pages `block_table` names and
    attend causally over positions [0, chunk end) of the table's pages. Whole
    pages are written (a page is one contiguous tile of the pool; a token row
    cuts through 32 of them): every page of the chunk that holds a position in
    [write_from, length); those below are another owner's and go to the trash
    page. The rest of the prompt's last page receives the padding's k/v, which
    nothing reads (attention stops at the length and decode overwrites
    position by position)."""
    from ..ops.paged_attention import paged_prefill_attention

    T, c0, block_table = ctx["page_tokens"], ctx["c0"], ctx["block_table"]
    pages, dest_page = _chunk_dest_pages(ctx)
    use_kernel = paged_attention_path(cfg, T) == "paged_kernel"
    k_lanes, how = _page_lanes(cfg)

    def attend_in(where: LayerPlace, pool):
        layer, window, kp, vp = where.layer, where.window, pool["k"], pool["v"]

        def attend(q, k, v):
            q, k = _to_page_lanes(q, k_lanes), _to_page_lanes(k, k_lanes)
            kp_ = kp.at[layer, dest_page].set(k[0].reshape(pages, T, -1))
            vp_ = vp.at[layer, dest_page].set(v[0].reshape(pages, T, -1))
            # Attend AFTER the write: the chunk's rows read their own k/v from the pages.
            with _window_scope(window):
                if use_kernel:
                    o = paged_prefill_attention(q[0], kp_, vp_, layer, block_table, c0, ctx["length"], n_kv_heads=cfg.n_kv_heads, window=window, **how)
                else:
                    o = paged_prefill_attention_gather(q[0], kp_[layer], vp_[layer], block_table, c0, cfg.n_kv_heads, window, **how)
            return o[None].astype(cfg.dtype), (kp_, vp_)

        return attend

    return attend_in


def _kv_step(cfg: TransformerConfig, ctx):
    """Each active row appends its k/v at its position (an inactive one to
    the trash page) and attends over positions [0, pos] of the pages its
    block table names."""
    from ..ops.paged_attention import paged_attention

    T, pos, block_tables = ctx["page_tokens"], ctx["pos"], ctx["block_tables"]
    B = pos.shape[0]
    dest_page, dest_slot, lengths = _step_dest(ctx)
    use_kernel = paged_attention_path(cfg, T) == "paged_kernel"
    k_lanes, how = _page_lanes(cfg)

    def attend_in(where: LayerPlace, pool):
        layer, window, kp, vp = where.layer, where.window, pool["k"], pool["v"]

        def attend(q, k, v):
            q, k = _to_page_lanes(q, k_lanes), _to_page_lanes(k, k_lanes)
            kp_ = kp.at[layer, dest_page, dest_slot].set(k.reshape(B, -1))
            vp_ = vp.at[layer, dest_page, dest_slot].set(v.reshape(B, -1))
            # Attend AFTER the append so the new position attends to itself.
            with _window_scope(window):
                if use_kernel:
                    o = paged_attention(q[:, 0], kp_, vp_, layer, block_tables, lengths, n_kv_heads=cfg.n_kv_heads, window=window, **how)
                else:
                    o = paged_attention_gather(q[:, 0], kp_[layer], vp_[layer], block_tables, pos + 1, cfg.n_kv_heads, window, **how)
            return o.astype(cfg.dtype), (kp_, vp_)

        return attend

    return attend_in


# "retention": a page is ONE sequence's whole recurrent state, float32, of a
# fixed size whatever its length (the layout: see `retention_phi`): `s`
# [layers, pages, n_kv_heads, head_dim, D] and `z` [layers, pages, n_kv_heads,
# D]. `page_tokens` only bounds the positions a sequence may be served (it
# shapes nothing); the block table is one entry, the sequence's one page for
# its life, and `write_from` is the position its state has reached: a
# prefill's chunks (PREFILL_CHUNK_TOKENS rows inside the one page) start
# exactly there, each from the state the one before it left in the page, the
# one at position 0 from nothing whatever the page held; rows past `length`
# leave the state as it was. Nothing of a state is a prefix of another prompt.


def _retention_leaves(cfg: TransformerConfig, num_pages: int, page_tokens: int):
    D = retention_state_dim(cfg.head_dim)
    return {
        "s": jnp.zeros((cfg.n_layers, num_pages, cfg.n_kv_heads, cfg.head_dim, D), jnp.float32),
        "z": jnp.zeros((cfg.n_layers, num_pages, cfg.n_kv_heads, D), jnp.float32),
    }


def _retention_chunk(cfg: TransformerConfig, ctx):
    """The chunk at positions [c0, c0 + rows) of the sequence whose state is
    page `slot`. It starts from what the page holds, the state after
    position c0 - 1, or from nothing where c0 is 0, whatever the page's last
    owner left there; `valid` [rows]: the rows below the length. The kernel
    that never holds phi in HBM where it can tile the chunk, in place, else
    the plain expression over a copy of the state."""
    from ..ops.power_retention import can_tile_prefill, power_retention_prefill

    slot, c0 = ctx["block_table"][0], ctx["c0"]
    valid = c0 + jnp.arange(ctx["rows"]) < ctx["length"]
    use_kernel = can_tile_prefill(ctx["rows"], cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)

    def attend_in(where: LayerPlace, pool):
        layer, sp, zp = where.layer, pool["s"], pool["z"]

        def attend(q, k, v, log_g):
            if use_kernel:
                with jax.named_scope("retention.chunk"):
                    y, sp_, zp_ = power_retention_prefill(q[0], k[0], v[0], log_g[0], sp, zp, layer, slot, c0 > 0, valid, eps=RETENTION_EPS)
                return y[None].astype(cfg.dtype), (sp_, zp_)
            s_in = jnp.where(c0 > 0, sp[layer, slot], 0.0)
            z_in = jnp.where(c0 > 0, zp[layer, slot], 0.0)
            with jax.named_scope("retention.chunk"):
                y, s_out, z_out = retention_chunk(q[0], k[0], v[0], log_g[0], s_in, z_in, valid)
            return y[None].astype(cfg.dtype), (sp.at[layer, slot].set(s_out), zp.at[layer, slot].set(z_out))

        return attend

    return attend_in


def _retention_step(cfg: TransformerConfig, ctx):
    """Row b's state is page slots[b] (the trash page for a row that is not
    active); each is decayed, takes its token and is read, in place. The
    one-pass kernel where it can tile the state, else the plain expression
    over a gathered copy."""
    from ..ops.power_retention import can_tile, power_retention_decode

    active = ctx["active"]
    slots = jnp.where(active, ctx["block_tables"][:, 0], TRASH_PAGE)

    def attend_in(where: LayerPlace, pool):
        layer, sp, zp = where.layer, pool["s"], pool["z"]

        def attend(q, k, v, log_g):
            q, k, v, log_g = q[:, 0], k[:, 0], v[:, 0], log_g[:, 0]
            if can_tile(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim):
                y, sp_, zp_ = power_retention_decode(q, k, v, log_g, sp, zp, layer, slots, active, eps=RETENTION_EPS)
            else:
                y, s_new, z_new = retention_step(q, k, v, log_g, sp[layer, slots], zp[layer, slots])
                sp_, zp_ = sp.at[layer, slots].set(s_new), zp.at[layer, slots].set(z_new)
            return y[:, None].astype(cfg.dtype), (sp_, zp_)

        return attend

    return attend_in


def _retention_decode_path(cfg: TransformerConfig, page_tokens: int) -> str:
    from ..ops.power_retention import can_tile

    return "retention_kernel" if can_tile(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) else "xla_step"


def _retention_prefill_path(cfg: TransformerConfig, page_tokens: int) -> str:
    from ..ops.power_retention import can_tile_prefill

    rows, _ = prefill_chunk_tokens(cfg, 1, page_tokens)
    return "retention_kernel" if can_tile_prefill(rows, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim) else "xla_chunk"


# "kda": a state SLOT, of a delta-rule layer in either form (`kda_decay`: Kimi
# Delta Attention's decay a key channel, Gated DeltaNet's decay a head, which
# reaches the recurrence broadcast over the head's channels). Every function of
# the row reads the config through `_kda_cfg`. Of every such layer a sequence
# keeps `s` [layers, slots, n_heads, head_dim, head_dim] float32, a (value)
# head's state, and `tail` [layers, slots, *_tail_shape], the (kda_conv - 1)
# rows of the q, k and v projections that the short convolution still needs
# (q's, k's, v's, each oldest first), in the parameters' type: of a fixed size, in ONE slot for the
# sequence's life, which no allocator hands out: decode row i's is slot i + 1
# (a decode row IS the engine's slot, given at admission), a prefill writes
# the slot it is told (`slot`; the trash slot 0 from a caller that names
# none), each chunk from what the one before it left there and the one at
# position 0 from zeros WITHOUT reading what the slot held. A state at a
# page's border is not kept, so nothing of it is a prefix: write_from is 0.


def _kda_leaves(cfg: TransformerConfig, slots: int, page_tokens: int):
    n_kda, cfg = cfg.n_layers, _kda_cfg(cfg)
    if slots < 2:
        raise ValueError("a KDA stack's pool has a trash slot and at least one state slot: state_slots >= 2")
    return {
        "s": jnp.zeros((n_kda, slots, cfg.n_heads, cfg.head_dim, cfg.head_dim), jnp.float32),
        "tail": jnp.zeros((n_kda, slots, *_tail_shape(cfg)), cfg.dtype),
    }


def _kda_chunk(cfg: TransformerConfig, ctx):
    from ..ops import kda

    cfg, slot, c0 = _kda_cfg(cfg), ctx["slot"], ctx["c0"]

    def attend_in(where: LayerPlace, pool):
        layer, sp, tp = where.layer, pool["s"], pool["tail"]
        valid = c0 + jnp.arange(ctx["rows"]) < ctx["length"]  # the rows below the length

        def attend(q, k, v, g, beta, conv):
            state_in = jnp.where(c0 > 0, sp[layer, slot], jnp.zeros((), sp.dtype))
            tails_in = jnp.where(c0 > 0, tp[layer, slot], jnp.zeros((), tp.dtype))
            q, k, v, tails_out = _kda_inputs(cfg, q[0], k[0], v[0], conv, tails_in, jnp.sum(valid))
            with jax.named_scope("kda.chunk"):
                o, state_out = kda.kda_chunk(q, k, v, g[0], beta[0], state_in, valid)
            return o[None], (sp.at[layer, slot].set(state_out), tp.at[layer, slot].set(tails_out))

        return attend

    return attend_in


def _kda_step(cfg: TransformerConfig, ctx):
    """Each row takes its token through the short convolutions and the delta
    rule and is read, in place. The one-pass kernel where it can tile the
    state, else the plain expression over a gathered copy."""
    from ..ops import kda

    cfg, active = _kda_cfg(cfg), ctx["active"]

    def attend_in(where: LayerPlace, pool):
        layer, sp, tp = where.layer, pool["s"], pool["tail"]
        slots = jnp.where(active, jnp.arange(active.shape[0]) + 1, TRASH_PAGE)

        def attend(q, k, v, g, beta, conv):
            ins = jax.vmap(partial(_kda_inputs, cfg), in_axes=(0, 0, 0, None, 0))(q, k, v, conv, tp[layer, slots])
            q, k, v = (t[:, 0] for t in ins[:3])
            with jax.named_scope("kda.step"):
                if kda.can_tile(cfg.n_heads, cfg.head_dim, cfg.head_dim):
                    o, sp_ = kda.kda_decode(q, k, v, g[:, 0], beta[:, 0], sp, layer, slots, active)
                else:
                    o, s_new = kda.kda_step(q, k, v, g[:, 0], beta[:, 0], sp[layer, slots])
                    sp_ = sp.at[layer, slots].set(s_new)
            return o[:, None], (sp_, tp.at[layer, slots].set(ins[3]))

        return attend

    return attend_in


def _kda_decode_path(cfg: TransformerConfig, page_tokens: int) -> str:
    from ..ops import kda

    cfg = _kda_cfg(cfg)
    return "kda_kernel" if kda.can_tile(cfg.n_heads, cfg.head_dim, cfg.head_dim) else "xla_step"


# "latent": a page holds `page_tokens` positions of one layer's `[c_kv | k_r]`,
# the normed latent and the rotated key part that all heads share, padded with
# zeros to whole lane tiles: `ckv` [layers, pages, page_tokens, row_width]
# (ops/latent_attention.py). A page of positions like a K/V page: the block
# table, the prefix index and the suffix prefill serve it as they serve those.
# Both serving forms are ABSORBED: `w_uk` is applied to the query and `w_uv`
# to the output, and a head attends in latent space over the rows as they lie
# (one key/value row for all heads; nothing is expanded). A chunk's absorbed
# pairs cost 3.4 x the expanded form's, which would expand every cached
# position a chunk that reads it (PERF.md §6, PR 50, says where that pays).


def latent_row_width(cfg: TransformerConfig) -> int:
    from ..ops.latent_attention import row_width

    return row_width(cfg.kv_lora_rank, cfg.qk_rope_dim)


def latent_position_bytes(cfg: TransformerConfig) -> int:
    """What ONE cached position of ONE latent layer must be read as: `[c_kv |
    k_r]` without the padding to whole lane tiles that the pages store."""
    return (cfg.kv_lora_rank + cfg.qk_rope_dim) * jnp.dtype(cfg.dtype).itemsize


def _latent_leaves(cfg: TransformerConfig, num_pages: int, page_tokens: int):
    return {"ckv": jnp.zeros((cfg.n_layers, num_pages, page_tokens, latent_row_width(cfg)), cfg.dtype)}


def _latent_rows(cfg: TransformerConfig, c_kv, k_r):
    """What the pages keep of positions: `[c_kv | k_r | zeros]` [..., row_width]."""
    pad = latent_row_width(cfg) - cfg.kv_lora_rank - cfg.qk_rope_dim
    return jnp.concatenate([c_kv, k_r, jnp.zeros((*c_kv.shape[:-1], pad), c_kv.dtype)], axis=-1)


def _absorbed_queries(cfg: TransformerConfig, q_nope, q_rope, w_uk):
    """A head's query in latent space beside its rope part, as a row of the
    pages is laid out: `[q_nope W_UK | q_rope | zeros]` [..., n_heads, row_width]."""
    with jax.named_scope("attn.mla.absorb"):
        q_lat = jnp.einsum("...hn,hnc->...hc", q_nope, w_uk, preferred_element_type=jnp.float32).astype(cfg.dtype)
    return _latent_rows(cfg, q_lat, q_rope)


def _absorbed_output(cfg: TransformerConfig, o_lat, w_uv):
    """A head's output in latent space through its `w_uv`: [..., n_heads, kv_lora_rank] -> [..., n_heads, v_head_dim]."""
    with jax.named_scope("attn.mla.absorb"):
        return jnp.einsum("...hc,hcv->...hv", o_lat, w_uv, preferred_element_type=jnp.float32).astype(cfg.dtype)


def _latent_path(cfg: TransformerConfig, page_tokens: int) -> str:
    from ..ops.latent_attention import can_tile

    return "latent_kernel" if can_tile(page_tokens, cfg.n_heads, cfg.kv_lora_rank, cfg.dtype) else "xla_gather"


def _latent_chunk(cfg: TransformerConfig, ctx):
    """The chunk's rows write their latent rows into the pages `block_table`
    names (whole pages, as `_kv_chunk` writes K/V and by its rule) and attend
    causally, absorbed, over positions [0, chunk end) of the table's pages."""
    from ..ops.latent_attention import latent_prefill_attention_gather, paged_latent_prefill_attention

    T, c0, block_table = ctx["page_tokens"], ctx["c0"], ctx["block_table"]
    pages, dest_page = _chunk_dest_pages(ctx)
    use_kernel = _latent_path(cfg, T) == "latent_kernel"
    how = dict(scale=latent_softmax_scale(cfg), v_width=cfg.kv_lora_rank)

    def attend_in(where: LayerPlace, pool):
        layer, lp = where.layer, pool["ckv"]

        def attend(q_nope, q_rope, c_kv, k_r, w_uk, w_uv):
            lp_ = lp.at[layer, dest_page].set(_latent_rows(cfg, c_kv[0], k_r[0]).reshape(pages, T, -1))
            q = _absorbed_queries(cfg, q_nope[0], q_rope[0], w_uk)
            # Attend AFTER the write: the chunk's rows read their own latent rows from the pages.
            if use_kernel:
                o_lat = paged_latent_prefill_attention(q, lp_, layer, block_table, c0, ctx["length"], **how)
            else:
                o_lat = latent_prefill_attention_gather(q, lp_[layer], block_table, c0, **how)
            return _absorbed_output(cfg, o_lat, w_uv)[None], (lp_,)

        return attend

    return attend_in


def _latent_step(cfg: TransformerConfig, ctx):
    """Each active row appends its latent row at its position (an inactive one
    to the trash page) and attends, absorbed, over positions [0, pos] of the
    pages its block table names."""
    from ..ops.latent_attention import latent_attention_gather, paged_latent_attention

    T, pos, block_tables = ctx["page_tokens"], ctx["pos"], ctx["block_tables"]
    dest_page, dest_slot, lengths = _step_dest(ctx)
    use_kernel = _latent_path(cfg, T) == "latent_kernel"
    how = dict(scale=latent_softmax_scale(cfg), v_width=cfg.kv_lora_rank)

    def attend_in(where: LayerPlace, pool):
        layer, lp = where.layer, pool["ckv"]

        def attend(q_nope, q_rope, c_kv, k_r, w_uk, w_uv):
            lp_ = lp.at[layer, dest_page, dest_slot].set(_latent_rows(cfg, c_kv[:, 0], k_r[:, 0]))
            q = _absorbed_queries(cfg, q_nope[:, 0], q_rope[:, 0], w_uk)
            # Attend AFTER the append so the new position attends to itself.
            if use_kernel:
                o_lat = paged_latent_attention(q, lp_, layer, block_tables, lengths, **how)
            else:
                o_lat = latent_attention_gather(q, lp_[layer], block_tables, pos + 1, **how)
            return _absorbed_output(cfg, o_lat, w_uv)[:, None], (lp_,)

        return attend

    return attend_in


# "window": a RING in a state slot, of a window layer of a stack that has them
# beside layers that page every position (`window_kv_heads`; a MiMo-V2 stack).
# Such a layer's query sees its own position and the window - 1 before it and
# nothing older, so of each of these layers a sequence keeps its last `window`
# positions' k and v and no more: `ring_k` [layers, slots, window,
# window_kv_heads * head_dim] and `ring_v` [.., window_kv_heads * value_dim],
# position p in row p % window, in ONE slot for the sequence's life, handed out
# as the "kda" row's are (decode row i's is slot i + 1, a prefill writes the
# slot it is told, slot 0 is the trash slot). Which position a row holds follows
# from the sequence's own position alone, so nothing is cleared where a new
# prompt takes a slot: a row that would hold a position below 0 is masked, and
# the prompt's first chunk sees nothing of what the slot held. At a window of
# 128 the ring is a dense batched product of a decode step's rows (no block
# table, no gather by position): plain jax.numpy under the `attn.window` scope.
# What a ring held at a page's border is not kept, so nothing of such a
# sequence is a prefix of another prompt: write_from is 0.


def _ring_leaves(cfg: TransformerConfig, slots: int, page_tokens: int):
    if slots < 2:
        raise ValueError("a stack with window rings has a trash slot and at least one ring slot: state_slots >= 2")
    shape, kvh = (cfg.n_layers, slots, max(cfg.windows)), cfg.window_kv_heads
    return {"ring_k": jnp.zeros((*shape, kvh * cfg.head_dim), cfg.dtype), "ring_v": jnp.zeros((*shape, kvh * cfg.value_dim), cfg.dtype)}


def _ring_rows(last, ring: int):
    """The position each of a ring's rows holds once position `last` ([...]
    int32) has been written: the greatest p <= last with p % ring == row,
    [..., ring]; below 0: the row holds nothing of this sequence."""
    row = jnp.arange(ring)
    return last[..., None] - (last[..., None] - row) % ring


def _ring_attention(q, keys, values, seen, sink):
    """q [b, rows, n_heads, hd] over keys [b, n, kv_heads, hd] and values [b, n,
    kv_heads, vd], row i seeing key j where seen [b, rows, n]; float32 softmax
    with the sink logits [n_heads] in its denominator (None: none). Query head
    h reads K/V head h // (n_heads / kv_heads); nothing is repeated. Every row
    sees a key (its own position). -> [b, rows, n_heads, vd] float32."""
    b, rows, H, hd = q.shape
    kvh = keys.shape[2]
    scores = jnp.einsum("bqgrd,bngd->bgrqn", q.reshape(b, rows, kvh, H // kvh, hd), keys, preferred_element_type=jnp.float32) / math.sqrt(hd)
    scores = jnp.where(seen[:, None, None], scores, -jnp.inf).reshape(b, H, rows, -1)
    probs = (jax.nn.softmax(scores, axis=-1) if sink is None else _sink_softmax(scores, sink)).astype(values.dtype)
    o = jnp.einsum("bgrqn,bngv->bqgrv", probs.reshape(b, kvh, H // kvh, rows, -1), values, preferred_element_type=jnp.float32)
    return o.reshape(b, rows, H, -1)


def _ring_chunk(cfg: TransformerConfig, ctx):
    """The chunk's rows attend over what the ring holds of the positions below
    the chunk (the last `window` of them: what the chunk before left there;
    nothing where the chunk is the prompt's first) and over the chunk's own k
    and v, and leave the last `window` positions below the chunk's end (the
    length's, in the prompt's last chunk) in the ring. A chunk of several
    windows is attended a block of rows at a time, each beside the `window`
    positions below it (`bands`): a row sees no further, and the masked
    product of all rows by all keys grows with the chunk's rows squared."""
    from ..ops.paged_attention import largest_divisor

    slot, c0, rows, ring, kvh = ctx["slot"], ctx["c0"], ctx["rows"], max(cfg.windows), cfg.window_kv_heads
    before = _ring_rows(c0 - 1, ring)  # what the ring's rows hold as the chunk starts
    q_pos = c0 + jnp.arange(rows)
    k_pos = jnp.concatenate([before, q_pos])
    back = q_pos[:, None] - k_pos[None, :]
    seen = (back >= 0) & (back < ring) & (k_pos >= 0)[None, :]
    after = _ring_rows(jnp.minimum(c0 + rows, ctx["length"]) - 1, ring)  # and as it ends: from the chunk where that is a row of it
    from_chunk, chunk_row = (after >= c0)[:, None], jnp.clip(after - c0, 0, rows - 1)
    # Blocks of at least a window's rows (and a small chunk's): only the first sees what the ring held.
    blocks = largest_divisor(rows, max(1, rows // max(ring, PREFILL_CHUNK_TOKENS)))
    block = rows // blocks

    def bands(t):
        """t [ring + rows, ...], the ring's rows then the chunk's -> [blocks, ring + block, ...]: block b's rows
        [b * block, (b + 1) * block) of the chunk behind the `ring` rows of t before them."""
        return jnp.stack([t[b * block : (b + 1) * block + ring] for b in range(blocks)])

    seen = jnp.stack([seen[b * block : (b + 1) * block, b * block : (b + 1) * block + ring] for b in range(blocks)])

    def attend_in(where: LayerPlace, pool):
        layer, rk, rv = where.layer, pool["ring_k"], pool["ring_v"]

        def attend(q, k, v, sink=None):
            with jax.named_scope("attn.window"):
                k_in, v_in = rk[layer, slot], rv[layer, slot]
                keys = jnp.concatenate([k_in.reshape(ring, kvh, -1), k[0]])
                values = jnp.concatenate([v_in.reshape(ring, kvh, -1), v[0]])
                o = _ring_attention(q.reshape(blocks, block, *q.shape[2:]), bands(keys), bands(values), seen, sink).reshape(1, rows, q.shape[2], -1)
                k_out = jnp.where(from_chunk, k[0].reshape(rows, -1)[chunk_row], k_in)
                v_out = jnp.where(from_chunk, v[0].reshape(rows, -1)[chunk_row], v_in)
                return o.astype(cfg.dtype), (rk.at[layer, slot].set(k_out), rv.at[layer, slot].set(v_out))

        return attend

    return attend_in


def _ring_step(cfg: TransformerConfig, ctx):
    """Each active row writes its k and v into row pos % window of its slot's
    ring (an inactive one into the trash slot's) and attends over the ring's
    rows that hold a position of its own sequence."""
    pos, active, ring, kvh = ctx["pos"], ctx["active"], max(cfg.windows), cfg.window_kv_heads
    B = pos.shape[0]
    slots = jnp.where(active, jnp.arange(B) + 1, TRASH_PAGE)
    seen = (_ring_rows(pos, ring) >= 0)[:, None, :]  # AFTER the write: the new position attends to itself

    def attend_in(where: LayerPlace, pool):
        layer, rk, rv = where.layer, pool["ring_k"], pool["ring_v"]

        def attend(q, k, v, sink=None):
            with jax.named_scope("attn.window"):
                rk_ = rk.at[layer, slots, pos % ring].set(k.reshape(B, -1))
                rv_ = rv.at[layer, slots, pos % ring].set(v.reshape(B, -1))
                o = _ring_attention(q, rk_[layer, slots].reshape(B, ring, kvh, -1), rv_[layer, slots].reshape(B, ring, kvh, -1), seen, sink)
                return o.astype(cfg.dtype), (rk_, rv_)

        return attend

    return attend_in


def _ring_path(cfg: TransformerConfig, page_tokens: int) -> str:
    return "xla_ring"


class LayerKind(NamedTuple):
    """A row of `KINDS`: what a served sequence keeps of a layer of this kind
    and the layer's three forms. A new kind is a row here, its kernels and
    plain expressions under ops/, its architecture file under
    benchmarks/archs/, and a `stack_plan` that places it. One stack may hold
    two kinds, one indexed by page and one by slot: K/V pages beside state
    slots (Solar-Open2), latent pages beside state slots (GigaChat3.5), K/V
    pages of the layers that see everything beside the window layers' rings
    (MiMo-V2)."""

    names: Tuple[str, ...]  # its cache leaves, in the pool's order
    indexed: str  # what their second axis counts: "page" (PagedKVAllocator hands them out; a block table names them) | "slot" (one a decode row)
    state: bool  # of a fixed size whatever the sequence's length, and nothing of it is kept at a page's border: a recurrent state, a window's ring
    leaves: Callable  # (cfg with n_layers the layers of this kind, pages or slots, page_tokens) -> {name: zeros}
    whole: Callable
    chunk: Callable
    step: Callable
    decode_path: Callable  # (cfg, page_tokens) -> which expression `step` runs, for PagedLM.describe
    prefill_path: Optional[Callable] = None  # the same of `chunk`, where that is a choice of its own (else decode_path's answer holds for both)
    decode_key: Optional[str] = None  # the name describe() says `decode_path` under, where it is not its indexing's ("decode_attention" by page, "decode_state" by slot)


KINDS = {
    "softmax": LayerKind(("k", "v"), "page", False, _kv_leaves, _softmax_whole, _kv_chunk, _kv_step, paged_attention_path),
    "retention": LayerKind(("s", "z"), "page", True, _retention_leaves, _retention_whole, _retention_chunk, _retention_step, _retention_decode_path, _retention_prefill_path),
    "kda": LayerKind(("s", "tail"), "slot", True, _kda_leaves, _kda_whole, _kda_chunk, _kda_step, _kda_decode_path),
    "latent": LayerKind(("ckv",), "page", False, _latent_leaves, _latent_whole, _latent_chunk, _latent_step, _latent_path),
    "window": LayerKind(("ring_k", "ring_v"), "slot", True, _ring_leaves, _window_whole, _ring_chunk, _ring_step, _ring_path, decode_key="decode_window"),
}


class CacheLayout(NamedTuple):
    """What a served sequence keeps of its past under one config, from
    `stack_plan` and `KINDS`: the one place that says so. `init_kv_pages`,
    the paged forwards and serve/llm's PagedLM read it."""

    kinds: Tuple[Tuple[str, int], ...]  # (kind, layers of it), in the order the stack first has them
    names: Tuple[str, ...]  # the pool's leaves in their order: the order they ride the scans in
    indexed: Dict[str, str]  # leaf -> "page" | "slot"
    state: bool  # a sequence keeps a recurrent state, alone or beside K/V or latent pages: then no full page of one prompt may serve another (its state at that page's border is not kept)
    kv: bool  # a page holds `page_tokens` positions, so that a block table grows with its sequence
    paged: Optional[str]  # a leaf whose pages hold positions ([layers, pages, page_tokens, ...]: its third axis says how many); None without one


@functools.lru_cache(maxsize=None)
def cache_layout(cfg: TransformerConfig) -> CacheLayout:
    layers: Dict[str, int] = {}
    for repeats, members in stack_plan(cfg):
        for m in members:
            layers[m.kind] = layers.get(m.kind, 0) + repeats * m.layers
    rows = [KINDS[kind] for kind in layers]
    indexed = {name: row.indexed for row in rows for name in row.names}
    paged = next((row.names[0] for row in rows if not row.state), None)
    return CacheLayout(tuple(layers.items()), tuple(indexed), indexed, any(row.state for row in rows), paged is not None, paged)


def decode_paths(cfg: TransformerConfig, page_tokens: int) -> Dict[str, str]:
    """Which expression a decode step runs over the pages (`decode_attention`),
    over the state slots (`decode_state`) and over the window rings
    (`decode_window`): PagedLM.describe."""
    keys = {"page": "decode_attention", "slot": "decode_state"}
    rows = [KINDS[kind] for kind, _ in cache_layout(cfg).kinds]
    return {row.decode_key or keys[row.indexed]: row.decode_path(cfg, page_tokens) for row in rows}


def prefill_paths(cfg: TransformerConfig, page_tokens: int) -> Dict[str, str]:
    """Which expression a prefill chunk runs (`prefill_attention` over pages,
    `prefill_state` over slots), for the kinds that choose it apart from the
    decode step's: PagedLM.describe, beside `decode_paths`."""
    keys = {"page": "prefill_attention", "slot": "prefill_state"}
    rows = [KINDS[kind] for kind, _ in cache_layout(cfg).kinds]
    return {keys[row.indexed]: row.prefill_path(cfg, page_tokens) for row in rows if row.prefill_path}


def init_kv_pages(
    cfg: TransformerConfig, num_pages: int, page_tokens: int, state_slots: Optional[int] = None
) -> Dict[str, jax.Array]:
    """Allocates the paged pool, one tree whatever the model keeps:
    `cache_layout(cfg)`'s leaves, each kind's over its own layers ALONE (a
    KDA stack's `k` / `v` over its softmax layers, `s` / `tail` over its KDA
    layers). forward_prefill and forward_decode take the tree and hand it
    back under the same names. Page 0 is the trash page and slot 0 the trash
    slot (`state_slots`, or `cfg.state_slots`: of the slot-indexed leaves)."""
    pool = {}
    for kind, layers in cache_layout(cfg).kinds:
        row = KINDS[kind]
        pool.update(row.leaves(cfg.replace(n_layers=layers), num_pages if row.indexed == "page" else state_slots or cfg.state_slots, page_tokens))
    return pool


@functools.lru_cache(maxsize=None)
def _layer_weight_bytes(cfg: TransformerConfig) -> Tuple[int, int]:
    """(bytes of the layers' weights one pass of a chunk reads, the held experts
    whole; n_experts times the bytes one ROW multiplies: all of a dense layer, of
    a routed one its router, shared expert and n_experts_per_tok / n_experts of
    the experts it holds), from the leaves `init_params` draws: embedding, head
    and final norm are no chunk's pass."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))

    def size(tree) -> int:
        return sum(math.prod(leaf.shape) * leaf.dtype.itemsize for leaf in jax.tree_util.tree_leaves(tree))

    read = multiplied = 0
    for tree in {m.tree for _, members in stack_plan(cfg) for m in members}:
        riding, experts = _experts_in_place(shapes[tree])
        read += size(riding) + size(experts)
        multiplied += size(riding) * (cfg.n_experts or 1) + size(experts) * cfg.n_experts_per_tok
    return read, multiplied


def prefill_chunk_rows(cfg: TransformerConfig) -> int:
    """Rows of a chunk worth one pass of this model's weights: PREFILL_CHUNK_TOKENS
    (a dense stack's ridge, where a row multiplies every weight the pass reads)
    times what the pass reads over what a row multiplies, at most
    PREFILL_CHUNK_CAP. The config alone decides: static wherever it is asked."""
    read, multiplied = _layer_weight_bytes(cfg)
    return min(PREFILL_CHUNK_CAP, -(-PREFILL_CHUNK_TOKENS * read * (cfg.n_experts or 1) // multiplied))


def prefill_chunk_tokens(cfg: TransformerConfig, bucket_pages: int, page_tokens: int) -> Tuple[int, int]:
    """(positions one chunk of forward_prefill computes, the granule its first
    chunk is anchored to) for a bucket of that many pages. K/V pages: whole
    pages (`prefill_chunk_pages`), anchored at a page's start. A state alone:
    PREFILL_CHUNK_TOKENS positions inside its one page, anchored at the very
    position the state has reached."""
    if not cache_layout(cfg).kv:
        return min(PREFILL_CHUNK_TOKENS, bucket_pages * page_tokens), 1
    return prefill_chunk_pages(bucket_pages, page_tokens) * page_tokens, page_tokens


def prefill_chunk_pages(bucket_pages: int, page_tokens: int) -> int:
    """Pages of one chunk of forward_prefill for a bucket of that many
    pages: PREFILL_CHUNK_TOKENS' worth, or the whole of a smaller bucket
    (the largest divisor of the bucket not above it, so chunks tile it)."""
    from ..ops.paged_attention import largest_divisor

    return largest_divisor(bucket_pages, max(1, PREFILL_CHUNK_TOKENS // page_tokens))


def prefill_big_chunk_tokens(cfg: TransformerConfig, page_tokens: int) -> int:
    """Positions one BIG chunk computes (forward_prefill with `big_chunks`), or
    0 where the model has none: PREFILL_CHUNK_CAP rows, for a stack whose
    weights ask for that many in whole small chunks (`prefill_chunk_rows`
    rounded up to PREFILL_CHUNK_TOKENS). A dense stack's weights ask for no
    more than the floor; one that asks for two or three small chunks' rows
    walks small chunks (PREFILL_CHUNK_CAP's comment); a state alone keeps
    PREFILL_CHUNK_TOKENS positions whatever its weights ask (retention's
    chunked form takes the chunk as its block: `flops_per_token`)."""
    small = max(1, PREFILL_CHUNK_TOKENS // page_tokens) * page_tokens  # a small chunk, in whole pages
    big = PREFILL_CHUNK_CAP // small * small
    asked = -(-prefill_chunk_rows(cfg) // PREFILL_CHUNK_TOKENS) * PREFILL_CHUNK_TOKENS
    return big if cache_layout(cfg).kv and big > small and asked >= PREFILL_CHUNK_CAP else 0


def prefill_chunk_span(length, write_from, chunk_tokens: int, page_tokens: int, minimum=min, maximum=max):
    """(anchor, count) of the chunks forward_prefill computes: chunk i covers
    positions [anchor + i * chunk_tokens, anchor + (i + 1) * chunk_tokens).
    The anchor is where the cache ends: the page of `write_from`, so the
    count is the uncached span in chunks, rounded up:
    ceil((length - anchor) / chunk_tokens). The last position's chunk is
    always computed, its logits are the result: where the cache holds the
    whole prompt the anchor is the last position's page. Python ints (PagedLM
    counts computed tokens with it, and with a big chunk's size the big chunks
    of a span: all of them but the last, which holds the last position and is
    the small chunks' to walk), or traced scalars with jnp's minimum /
    maximum."""
    last = maximum(length - 1, 0)
    anchor = minimum(write_from, last) // page_tokens * page_tokens
    return anchor, (last - anchor) // chunk_tokens + 1


def forward_prefill(
    params: PyTree,
    tokens: jax.Array,
    cfg: TransformerConfig,
    kv_pages: Dict[str, jax.Array],
    block_table: jax.Array,
    length: jax.Array,
    write_from: jax.Array,
    slot=TRASH_PAGE,
    big_chunks=None,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """Prefill ONE sequence: computes what the cache does not hold and
    writes it into the paged pool.

    tokens [1, S] (padded to a bucket; pad is arbitrary token ids),
    block_table [P] page indices covering positions [0, P*page_tokens),
    length: scalar, true prompt length (<= S),
    write_from: scalar, first position to COMPUTE: the pages below it
      already hold this prompt's k/v (shared prefix pages the radix cache
      matched, written by their owner) and are read, never recomputed into
      and never rewritten. The radix cache shares whole pages, so it is a
      multiple of the page; 0 is a miss, which runs the same loop.
    slot: scalar, the sequence's state slot, of a model that keeps one
      (`KINDS`; the trash slot from a caller that names none).
    big_chunks: scalar, or None. Given, the call walks that many BIG chunks
      (`prefill_big_chunk_tokens`) from write_from on and nothing else, and
      returns no logits: a model whose weights ask for more rows a pass than
      PREFILL_CHUNK_TOKENS has the head of a long span computed so, and the
      rest of it by a second call with `write_from` moved behind them (what the
      big chunks left in pages, slots and rings is the cache that call reads).

    The uncached span is walked in chunks (`prefill_chunk_tokens`) with a
    dynamic trip count (`prefill_chunk_span`): the first chunk starts
    at write_from, wherever in the bucket that page lies, and the last is
    the one that holds the last position, so the work is the uncached
    suffix rounded up to chunks, not the bucket. A chunk passes through all
    layers, each through its kind's `chunk` form (`KINDS`), which writes
    what the layer keeps of it. No row below write_from is computed; the
    last chunk may run past the bucket's end, over padding made here.

    Returns (last-position logits [1, vocab] fp32, updated kv_pages).
    """
    layout = cache_layout(cfg)
    _, S = tokens.shape
    T = kv_pages[layout.paged].shape[2] if layout.kv else S // block_table.shape[0]
    C, granule = prefill_chunk_tokens(cfg, S // T, T) if big_chunks is None else (prefill_big_chunk_tokens(cfg, T), T)
    # What a chunk slices is padded by a chunk: the last one starts at a
    # page below the length, not at a multiple of C, and a dynamic slice
    # that ran past the end would be moved back silently.
    cos_t, sin_t = rope_tables(cfg, S + C)
    tokens = jnp.pad(tokens, ((0, 0), (0, C)))
    # A table that pages positions is sliced a chunk at a time, so it too is padded by a chunk.
    dest_table = jnp.pad(block_table, (0, C // T), constant_values=TRASH_PAGE) if layout.kv else None
    call = dict(block_table=block_table, dest_table=dest_table, length=length, write_from=write_from, slot=slot, rows=C, page_tokens=T)
    anchor, n_chunks = prefill_chunk_span(length, write_from, C, granule, jnp.minimum, jnp.maximum)
    if big_chunks is not None:
        n_chunks = big_chunks

    def chunk_step(i, carry):
        *pool, _ = carry
        c0 = anchor + i * C
        cos = lax.dynamic_slice_in_dim(cos_t, c0, C)
        sin = lax.dynamic_slice_in_dim(sin_t, c0, C)
        rope = {"window": rope_at(_window_rope_cfg(cfg), c0 + jnp.arange(C))} if cfg.window_rope_theta else {}
        x = _embed(params, lax.dynamic_slice_in_dim(tokens, c0, C, axis=1), cfg)
        ctx = dict(call, c0=c0)
        attend_in = {kind: KINDS[kind].chunk(cfg, ctx) for kind, _ in layout.kinds}

        # The pool rides both loops as a carry, written in place (as in
        # forward_decode): no copy of it is made a layer or a chunk.
        def layer(kind, carry, where, layer_params, experts):
            x, pool = carry[0], dict(zip(layout.names, carry[1:]))
            x, own = _block(x, layer_params, cfg, *_rope_switch(*rope.get(kind, (cos, sin)), where.rope_on), attend_in[kind](where, pool), experts=experts, kind=kind)
            pool.update(zip(KINDS[kind].names, own))
            return (x, *pool.values()), None

        (x, *pool), _ = _walk_stack(params, cfg, layer, (x, *pool), in_place=True)
        # the last position's row, if this is its chunk (the final one is)
        return (*pool, jnp.take(x[0], jnp.clip(length - 1 - c0, 0, C - 1), axis=0))

    h_last = jnp.zeros((cfg.d_model,), cfg.dtype)
    *pool, h_last = lax.fori_loop(0, n_chunks, chunk_step, (*(kv_pages[name] for name in layout.names), h_last))
    if big_chunks is not None:
        return None, dict(zip(layout.names, pool))
    h_last = _norm(h_last[None, :], params["final_norm"]["scale"], cfg)
    return _logits(params, h_last), dict(zip(layout.names, pool))


def forward_decode(
    params: PyTree,
    tokens: jax.Array,
    positions: jax.Array,
    cfg: TransformerConfig,
    kv_pages: Dict[str, jax.Array],
    block_tables: jax.Array,
    stats: bool = False,
) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    """One decode step for the whole slot batch, each layer through its
    kind's `step` form (`KINDS`).

    tokens [B] int32 (last emitted token per slot; ignored when inactive),
    positions [B] int32 (index the new token occupies; -1 => inactive slot),
    block_tables [B, P] page indices per slot (trash page for unused rows).

    Every active row takes its token at `positions` into what it keeps (its
    pages, its state) and attends over positions [0, pos]; returns (logits
    [B, vocab] fp32, updated kv_pages). Inactive slots write to the trash
    page or slot and produce garbage logits the scheduler ignores. Shapes
    are static in B/P/N: one jit serves every batch mix.
    With `stats`, a third: {"experts_touched": int32 scalar}, the distinct
    experts that the step's B rows chose, summed over the routed layers
    (each is a matrix triple the step has to read); 0 for a dense model.
    Under a share of the experts (`cfg.n_experts_held`) it counts the held
    ones, and a second counter, `held_picks`, how many of the rows' choices
    (B x n_experts_per_tok a routed layer) fell on them.
    """
    layout = cache_layout(cfg)
    P = block_tables.shape[1]
    active = positions >= 0
    pos = jnp.maximum(positions, 0)

    if layout.kv:
        T = kv_pages[layout.paged].shape[2]
        cos_t, sin_t = rope_tables(cfg, P * T)
        cos = jnp.take(cos_t, pos, axis=0)[:, None, :]  # [B, 1, rd/2]: each row its own position
        sin = jnp.take(sin_t, pos, axis=0)[:, None, :]
    else:  # no page says how many positions there are: the angles of each row's own
        T = None
        cos, sin = (t[:, None, :] for t in rope_at(cfg, pos))

    rope = {"window": tuple(t[:, None, :] for t in rope_at(_window_rope_cfg(cfg), pos))} if cfg.window_rope_theta else {}
    x = _embed(params, tokens, cfg)[:, None, :]  # [B,1,d]
    ctx = dict(block_tables=block_tables, pos=pos, active=active, page_tokens=T)
    attend_in = {kind: KINDS[kind].step(cfg, ctx) for kind, _ in layout.kinds}

    # The pool rides the layer scans as a CARRY: each layer writes its own
    # slice in place and the kernels read the pool where it lies. As
    # xs/ys every step would copy the whole pool out of the stacked array
    # and back in.
    def layer(kind, carry, where, layer_params, experts):
        x, pool = carry[0], dict(zip(layout.names, carry[1:]))
        x, own, *rows_per_expert = _block(
            x, layer_params, cfg, *_rope_switch(*rope.get(kind, (cos, sin)), where.rope_on), attend_in[kind](where, pool),
            stats="experts" if stats else "", experts=experts, kind=kind,
        )
        pool.update(zip(KINDS[kind].names, own))
        return (x, *pool.values()), (rows_per_expert[0] if stats else None)

    (x, *pool), segments = _walk_stack(params, cfg, layer, (x, *(kv_pages[name] for name in layout.names)), in_place=True)
    touched, held_picks = jnp.int32(0), jnp.int32(0)
    share = cfg.experts_held != cfg.n_experts
    for rows_per_expert in (y for ys in segments for y in ys if y is not None):  # [layers, E], or [repeats, layers, E], of routed layers
        if share:  # the held experts' columns
            rows_per_expert = rows_per_expert[..., cfg.first_expert : cfg.first_expert + cfg.experts_held]
            held_picks = held_picks + jnp.sum(rows_per_expert, dtype=jnp.int32)
        touched = touched + jnp.sum(rows_per_expert > 0, dtype=jnp.int32)
    x = _norm(x, params["final_norm"]["scale"], cfg)
    out = _logits(params, x[:, 0]), dict(zip(layout.names, pool))
    if stats and share:
        return (*out, {"experts_touched": touched, "held_picks": held_picks})
    return (*out, {"experts_touched": touched}) if stats else out
