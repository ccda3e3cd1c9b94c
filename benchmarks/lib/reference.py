"""The plain reference of the dense decoder: jax.numpy, float32, matmul
precision "highest", no kernels, no cache, no batching, one sequence at a
time. Written from the published description (pre-norm RMSNorm, rotary
embeddings on rotate-half pairs, grouped-query causal attention, gated-SiLU
MLP, untied head); it shares no code with ray_tpu/models/transformer.py and
reads only the layout of the weights (stacked layers, [in, out] matrices).
Weights are upcast one layer at a time so that it fits beside the engine.

`correct` rests on it: by loss for training (`sequence_nll`), by the
reference logit of each served token for serving (`served_token_margins`).
"""

from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp

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


def hidden_states(params, tokens, m: Dict):
    """tokens [s] int32 -> final-norm hidden states [s, d], float32."""
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["embedding"][tokens].astype(F32)
        blocks = params["blocks"]
        for layer in range(m["L"]):
            w = jax.tree_util.tree_map(lambda a: a[layer].astype(F32), blocks)
            hn = _rms_norm(x, w["attn_norm"]["scale"], m["eps"])
            s = hn.shape[0]
            q = _rope((hn @ w["attn"]["wq"]).reshape(s, m["h"], m["hd"]), m["theta"])
            k = _rope((hn @ w["attn"]["wk"]).reshape(s, m["kv"], m["hd"]), m["theta"])
            v = (hn @ w["attn"]["wv"]).reshape(s, m["kv"], m["hd"])
            x = x + _attention(q, k, v) @ w["attn"]["wo"]
            hn = _rms_norm(x, w["mlp_norm"]["scale"], m["eps"])
            x = x + (jax.nn.silu(hn @ w["mlp"]["w_gate"]) * (hn @ w["mlp"]["w_up"])) @ w["mlp"]["w_down"]
        return _rms_norm(x, params["final_norm"]["scale"], m["eps"])


def _head(params):
    head = params.get("lm_head")
    return params["embed"]["embedding"].T if head is None else head


def sequence_nll(params, tokens, m: Dict):
    """Mean next-token cross-entropy of ONE sequence (positions 0..s-2)."""
    with jax.default_matmul_precision("highest"):
        logits = hidden_states(params, tokens, m)[:-1] @ _head(params).astype(F32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tokens[1:, None], axis=-1))


def logits_at(params, tokens, positions, m: Dict):
    """Next-token logits [len(positions), V] after each of `positions` of ONE sequence."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, tokens, m)[positions] @ _head(params).astype(F32)


def served_token_margins(params, prompt: Sequence[int], served: Sequence[int], m: Dict, which: Sequence[int]) -> List[float]:
    """For served tokens number `which` (0 = the first token, from prefill;
    i > 0 = the i-th decode step through the paged cache): reference maximum
    logit at that position minus the reference logit of the token that was
    served, teacher-forced on the served tokens before it. 0 when the
    served token is the reference argmax; small when rounding flipped two
    near-equal logits; large when the served path computed something else."""
    seq = jnp.asarray(list(prompt) + list(served[: max(which)]), jnp.int32)
    pos = jnp.asarray([len(prompt) - 1 + i for i in which], jnp.int32)
    logits = jax.jit(logits_at, static_argnames=("m",))(params, seq, pos, m=Frozen(m))
    tok = jnp.asarray([served[i] for i in which], jnp.int32)
    margins = jnp.max(logits, axis=-1) - jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0]
    return [float(x) for x in margins]


class Frozen(dict):
    """The model's sizes as a dict usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, str(v)) for k, v in self.items())))
