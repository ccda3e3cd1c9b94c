"""Kimi Delta Attention (KDA): a linear-attention layer with a delta rule, a
per-channel decay and a short convolution (Kimi Linear, arXiv:2510.26692; the
linear layers of the Solar-Open2 family, models/transformer.py
`kda_per_period`). What a sequence keeps of its past in such a layer is one
state a head, `S` [d_k, d_v] float32, and the last `K - 1` rows of the q, k
and v projections before the convolution (the "tail"), both of a fixed size
whatever its length.

Per head, with q, k, v the projections after the convolution (`short_conv`),
q and k l2-normalised (`qk_norms`), g <= 0 the log-decay a key channel and
beta in (0, 2) (`gates`), all float32:

    Sd  = Diag(exp(g_t)) S_(t-1)
    u_t = beta_t (v_t - Sd^T k_t)
    S_t = Sd + k_t u_t^T
    o_t = S_t^T q_t

Three pure forms of the same numbers, matmuls at KDA_PRECISION (a state is
thousands of decayed additions into one array): `kda_step` (one token a row),
`kda_chunk` (C tokens of one sequence from a state to a state, in sub-chunks
of SUB_CHUNK rows; a whole sequence is one call from a zero state) and the
token-by-token recurrence itself, which `kda_step` under a scan is. The chunked
form of a sub-chunk from `S_0`, `G_t` the running sum of g inside it (every
exponent is <= 0, so nothing overflows however strong the decay):

    M_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)    s < t, else 0
    U    = (I + M)^-1 Diag(beta) (V - (K * exp(G)) S_0)
    A_ts = sum_c q_tc k_sc exp(G_tc - G_sc)           s <= t
    O    = (Q * exp(G)) S_0 + A U
    S_C  = Diag(exp(G_C)) S_0 + (K * exp(G_C - G))^T U

A padded row has g = 0 and beta = 0 and leaves the state alone.

`kda_decode` is the decode step's state update as one pallas TPU kernel a
layer: the pool `s` [layers, slots, heads, d_k, d_v] stays in HBM as it lies
and is aliased to the output; the layer, each row's slot and whether it is
live are scalar-prefetched and name the block a grid step takes, (row, a group
of HEAD_BLOCK heads) -> [HEAD_BLOCK, d_k, d_v], pipelined in and out by the
grid, so a state moves once in and once out. A row that is not live names the
trash slot's first block at every step and computes nothing. q, k, exp(g), v
and beta of the group's heads ride in as the rows of one [5 * HEAD_BLOCK, d_k]
tile; the three that scale S's rows (q, k, exp(g): d_k lies on S's sublanes)
are turned into columns by one transpose of the tile. Everything is on the
vector unit in float32: `k^T S` and `q^T S` are sums over S's rows.
`interpret=True` (selected when this process's backend is not a TPU) runs the
same kernel on the CPU for tests.
"""

from __future__ import annotations

import functools
import importlib
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

KERNEL_NAME = "kda_decode"
KDA_PRECISION = lax.Precision.HIGHEST
SUB_CHUNK = 64  # rows of one solve of the chunked form
L2_EPS = 1e-6
LANES = 128
HEAD_BLOCK = 8  # heads a grid step of the kernel takes: a block of 8 x 128 x 128 x 4 B = 512 KB


# ------------------------------------------------------ the layer's pieces


def short_conv(x, w, tail, n_valid=None):
    """The depthwise causal convolution and its SiLU: x [c, n] (the rows of
    one sequence, in order), w [n, K], tail [K - 1, n], the rows before x's
    first (zeros at a sequence's start) -> (silu(sum_j w[:, j] * x_(t-K+1+j))
    [c, n] float32, the tail after x's first `n_valid` rows (all c if None),
    in x's type)."""
    c, K = x.shape[0], w.shape[-1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=0)  # [K - 1 + c, n]
    wf, ef = w.astype(jnp.float32), ext.astype(jnp.float32)
    y = sum(wf[:, j] * ef[j : j + c] for j in range(K))
    new_tail = ext[c:] if n_valid is None else lax.dynamic_slice_in_dim(ext, n_valid, K - 1, axis=0)
    return jax.nn.silu(y), new_tail


def qk_norms(q, k):
    """q, k [..., heads, d_k] float32 after the convolution -> q l2-normalised
    over a head's channels and scaled by 1 / sqrt(d_k), k l2-normalised."""
    def l2norm(x):
        return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    return l2norm(q) / math.sqrt(q.shape[-1]), l2norm(k)


def gates(f, a_log, dt_bias, b_logit):
    """f [..., heads * d_k] float32 (the decay's projection), a_log [heads],
    dt_bias [heads * d_k], b_logit [..., heads] -> (g [..., heads, d_k] <= 0,
    beta [..., heads] in (0, 2))."""
    heads = a_log.shape[-1]
    soft = jax.nn.softplus(f + dt_bias.astype(jnp.float32)).reshape(*f.shape[:-1], heads, -1)
    g = -jnp.exp(a_log.astype(jnp.float32))[:, None] * soft
    return g, 2.0 * jax.nn.sigmoid(b_logit.astype(jnp.float32))


def gates_a_head(a, a_log, dt_bias, b_logit, d_k: int):
    """The gated-delta-rule form (Gated DeltaNet, arXiv:2412.06464): ONE decay
    a head. a [..., heads] float32 (the decay's logit), a_log, dt_bias
    [heads], b_logit [..., heads] -> (g [..., heads, d_k] <= 0, the head's
    log-decay on every one of its key channels, as the recurrence's three
    forms and the kernel take it; beta [..., heads] in (0, 1))."""
    g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(a + dt_bias.astype(jnp.float32))
    return jnp.broadcast_to(g[..., None], (*g.shape, d_k)), jax.nn.sigmoid(b_logit.astype(jnp.float32))


# ------------------------------------------------ the recurrence, three forms


def kda_step(q, k, v, g, beta, s):
    """One token a row: q, k [B, heads, d_k], v [B, heads, d_v], g [B, heads,
    d_k], beta [B, heads], s [B, heads, d_k, d_v], float32 -> (o [B, heads,
    d_v], the states after the token). The plain expression; `kda_decode` is
    its one-pass kernel over a pool."""
    dot = functools.partial(jnp.einsum, precision=KDA_PRECISION)
    sd = jnp.exp(g)[..., None] * s
    u = beta[..., None] * (v - dot("bhk,bhkv->bhv", k, sd))
    s = sd + k[..., None] * u[..., None, :]
    return dot("bhk,bhkv->bhv", q, s), s


def _sub_chunk(s0, xs):
    """SUB_CHUNK rows of one sequence by the chunked form: s0 [heads, d_k,
    d_v], xs = (q, k [C, heads, d_k], v [C, heads, d_v], g [C, heads, d_k],
    beta [C, heads]) -> (the state after them, o [C, heads, d_v])."""
    q, k, v, g, beta = xs
    C = q.shape[0]
    dot = functools.partial(jnp.einsum, precision=KDA_PRECISION)
    G = jnp.cumsum(g, axis=0)
    back = jnp.arange(C)[:, None] - jnp.arange(C)[None, :]  # t - s
    # exp(G_t - G_s) a channel for s <= t, 0 above the diagonal; consumed by
    # the two sums over channels below, which the compiler fuses it into.
    decay = jnp.exp(jnp.where((back >= 0)[:, :, None, None], G[:, None] - G[None, :], -jnp.inf))  # [t, s, heads, d_k]
    kk = jnp.sum(k[:, None] * k[None, :] * decay, axis=-1)  # [t, s, heads]
    qk = jnp.sum(q[:, None] * k[None, :] * decay, axis=-1)
    M = jnp.where((back > 0)[:, :, None], beta[:, None, :] * kk, 0.0)
    eG = jnp.exp(G)
    rhs = beta[..., None] * (v - dot("thk,hkv->thv", k * eG, s0))
    unit_lower = jnp.moveaxis(M, -1, 0) + jnp.eye(C, dtype=M.dtype)  # [heads, t, s]
    U = jax.scipy.linalg.solve_triangular(unit_lower, jnp.moveaxis(rhs, 1, 0), lower=True, unit_diagonal=True)  # [heads, s, d_v]
    o = dot("thk,hkv->thv", q * eG, s0) + dot("tsh,hsv->thv", qk, U)
    s1 = eG[-1][..., None] * s0 + dot("shk,hsv->hkv", k * jnp.exp(G[-1][None] - G), U)
    return s1, o


def kda_chunk(q, k, v, g, beta, s0, valid=None):
    """C rows of ONE sequence from the state before its first row to the
    state after its last valid one: q, k [C, heads, d_k], v [C, heads, d_v], g
    [C, heads, d_k], beta [C, heads], s0 [heads, d_k, d_v], float32 -> (o [C,
    heads, d_v], the state). `valid` [C] bool: rows past a prompt's length
    (padding: they follow every valid row) leave the state alone; their own
    outputs are arbitrary. A scan over sub-chunks of SUB_CHUNK rows."""
    C = q.shape[0]
    if valid is not None:
        g, beta = jnp.where(valid[:, None, None], g, 0.0), jnp.where(valid[:, None], beta, 0.0)
    sub = min(SUB_CHUNK, C)
    n = -(-C // sub)

    def split(t):  # zero rows behind: g = 0 and beta = 0 leave the state alone
        return jnp.pad(t, [(0, n * sub - C)] + [(0, 0)] * (t.ndim - 1)).reshape(n, sub, *t.shape[1:])

    s1, o = lax.scan(_sub_chunk, s0, tuple(split(t) for t in (q, k, v, g, beta)))
    return o.reshape(n * sub, *o.shape[2:])[:C], s1


def kda_recurrence(q, k, v, g, beta, s0):
    """`kda_chunk`'s numbers token by token (the published recurrence under a
    scan): the parity reference of the chunked form."""
    def one(s, xs):
        o, s = kda_step(*(t[None] for t in xs), s[None])
        return s[0], o[0]

    s1, o = lax.scan(one, s0, (q, k, v, g, beta))
    return o, s1


# ------------------------------------------------------------- the kernel


def _auto_interpret() -> bool:
    """The flash kernel's rule (its module is patched where a program is
    compiled for a described TPU from a CPU process: benchmarks/rehearse.py)."""
    return importlib.import_module("ray_tpu.ops.flash_attention")._auto_interpret()


def can_tile(n_heads: int, d_k: int, d_v: int) -> bool:
    """Whether the kernel takes these shapes: a head's state is d_k rows of
    one vector register's lanes, heads come in whole blocks. Shapes decide,
    nothing else does."""
    return d_k == LANES and d_v == LANES and n_heads % HEAD_BLOCK == 0


def _kernel(slots_ref, live_ref, layer_ref, x_ref, s_ref, o_ref, s_out):
    del slots_ref, layer_ref  # the index maps read them
    hb = HEAD_BLOCK

    @pl.when(live_ref[pl.program_id(0)] > 0)
    def _():
        x = x_ref[...]  # [5 * hb, d_k]: q, k, exp(g), v, beta of the block's heads
        # q, k and exp(g) scale S's rows: as columns, by one transpose of their rows.
        cols = jnp.concatenate([x[: 3 * hb], jnp.zeros((LANES - 3 * hb, LANES), jnp.float32)], axis=0).T
        out = []
        for h in range(hb):
            q_col, k_col, decay = (cols[:, i * hb + h : i * hb + h + 1] for i in range(3))
            v_row, beta_row = x[3 * hb + h : 3 * hb + h + 1], x[4 * hb + h : 4 * hb + h + 1]
            sd = s_ref[h] * decay
            u = beta_row * (v_row - jnp.sum(sd * k_col, axis=0, keepdims=True))
            s_new = sd + k_col * u
            s_out[h] = s_new
            out.append(jnp.sum(s_new * q_col, axis=0, keepdims=True))
        o_ref[...] = jnp.concatenate(out, axis=0)


def kda_decode(q, k, v, g, beta, s, layer, slots, live, *, interpret: Optional[bool] = None):
    """One token a row against the states of a pool, in place.

    q, k [B, heads, d_k] (q scaled, both normalised: `qk_norms`), v [B, heads,
    d_v], g [B, heads, d_k], beta [B, heads], float32; s [layers, slots,
    heads, d_k, d_v] float32, the pool; layer: scalar; slots [B] int32, each
    row's state (distinct among live rows); live [B] bool. Returns (o [B,
    heads, d_v] float32, s): the pool with the live rows' states of that layer
    advanced by their token. A row that is not live leaves every slot but the
    trash slot (0) as it was; its o is arbitrary."""
    B, H, dk = q.shape
    dv = v.shape[-1]
    if not can_tile(H, dk, dv) or s.shape[2:] != (H, dk, dv):
        raise ValueError(f"kda_decode does not tile {H} heads of {dk} x {dv} over a pool of {s.shape}")
    if interpret is None:
        interpret = _auto_interpret()
    hb, f32 = HEAD_BLOCK, jnp.float32

    def blocks(t):  # [B, H, 128] -> [B, H / hb, hb, 128]
        return t.astype(f32).reshape(B, H // hb, hb, LANES)

    beta_rows = jnp.broadcast_to(beta.astype(f32)[..., None], (B, H, LANES))
    x = jnp.concatenate([blocks(t) for t in (q, k, jnp.exp(g.astype(f32)), v, beta_rows)], axis=2)  # [B, H / hb, 5 hb, 128]
    live = live.astype(jnp.int32)
    slots = jnp.where(live > 0, slots.astype(jnp.int32), 0)
    layer = jnp.asarray(layer, jnp.int32).reshape(1)

    def per_row(b, j, slots, live, layer):
        return (b, j, 0, 0)

    def s_block(b, j, slots, live, layer):
        return (layer[0], slots[b], j * live[b], 0, 0)

    o, s = pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B, H // hb),
            in_specs=[
                pl.BlockSpec((None, None, 5 * hb, LANES), per_row),
                pl.BlockSpec((None, None, hb, dk, dv), s_block),
            ],
            out_specs=[
                pl.BlockSpec((None, None, hb, LANES), per_row),
                pl.BlockSpec((None, None, hb, dk, dv), s_block),
            ],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, H // hb, hb, LANES), f32), jax.ShapeDtypeStruct(s.shape, s.dtype)],
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(slots, live, layer, x, s)
    return o.reshape(B, H, dv), s
