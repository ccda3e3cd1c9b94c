"""Compiles for a DESCRIBED TPU v5e (no chip attached): what the chip's
compiler refuses, it refuses here, at no chip time. Interpret mode cannot
see a slice that cuts a tile or a kernel that needs too much VMEM.

The topology is described inside a fixture of THIS file and of no other
(one process may hold the TPU's library; see the on-chip-measurement
guide): nothing here touches it at import. Nothing runs, so these tests say
nothing about results or times.
"""

import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import transformer as tfm
from ray_tpu.ops import paged_attention as pa


@pytest.fixture(scope="module")
def one_chip():
    import os

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels pick interpret mode from this process's backend (CPU)."""
    import ray_tpu.ops.flash_attention  # noqa: F401

    monkeypatch.setattr(sys.modules["ray_tpu.ops.flash_attention"], "_auto_interpret", lambda: False)


def _sds(one_chip):
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


@pytest.mark.parametrize(
    "heads,kv_heads,dtype",
    [(32, 32, jnp.bfloat16), (32, 8, jnp.bfloat16), (16, 16, jnp.bfloat16), (32, 32, jnp.float32)],
    ids=["deepseek_mha", "mistral_gqa", "olmoe", "float32_pool"],
)
def test_paged_attention_kernel_compiles_at_the_cells_widths(one_chip, heads, kv_heads, dtype):
    """16 slots x 256 pages of 16 tokens over a pool of 1 024 pages, head_dim
    128: the serving cells' decode shapes, as a Mosaic kernel with its name."""
    sds = _sds(one_chip)
    B, P, T, hd, N, L = 16, 256, 16, 128, 1024, 8
    pool = sds((L, N, T, kv_heads * hd), dtype)

    def f(q, kp, vp, layer, bt, lengths):
        return pa.paged_attention(q, kp, vp, layer, bt, lengths, n_kv_heads=kv_heads, interpret=False)

    text = jax.jit(f).lower(
        sds((B, heads, hd), dtype), pool, pool, sds((), jnp.int32), sds((B, P), jnp.int32), sds((B,), jnp.int32)
    ).compile().as_text()
    assert "tpu_custom_call" in text and pa.KERNEL_NAME in text


@pytest.mark.parametrize(
    "heads,kv_heads", [(32, 32), (32, 8), (16, 16)], ids=["deepseek_mha", "mistral_gqa", "olmoe"]
)
def test_paged_prefill_kernel_compiles_at_the_cells_widths(one_chip, heads, kv_heads):
    """One chunk of forward_prefill (PREFILL_CHUNK_TOKENS rows) over a table
    of 256 pages of 16 tokens, head_dim 128, with the blocks the program
    picks: a Mosaic kernel with its name, inside the VMEM it asks for."""
    sds = _sds(one_chip)
    C, P, T, hd, N, L = tfm.PREFILL_CHUNK_TOKENS, 256, 16, 128, 1024, 8
    pool = sds((L, N, T, kv_heads * hd), jnp.bfloat16)

    def f(q, kp, vp, layer, bt, start, length):
        return pa.paged_prefill_attention(q, kp, vp, layer, bt, start, length, n_kv_heads=kv_heads, interpret=False)

    scalar = sds((), jnp.int32)
    text = jax.jit(f).lower(
        sds((C, heads, hd), jnp.bfloat16), pool, pool, scalar, sds((P,), jnp.int32), scalar, scalar
    ).compile().as_text()
    assert "tpu_custom_call" in text and pa.PREFILL_KERNEL_NAME in text


SERVING = dict(vocab_size=1024, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=32, d_ff=1024, attn_impl="full")
# Trinity's attention: GQA 32:4, heads narrower than d_model / n_heads, a qk-norm over each head
GQA_PER_HEAD_NORM = dict(
    SERVING, d_model=2048, n_kv_heads=4, d_head=128, qk_norm=True, qk_norm_per_head=True
)
SLOTS, TABLE_PAGES, PAGE_TOKENS, POOL_PAGES = 16, 256, 16, 1024


def _compile_paged(one_chip, cfg, step: str):
    """forward_decode (SLOTS slots) or forward_prefill (the bucket of
    TABLE_PAGES pages) of `cfg`, pool donated, compiled for the described chip."""
    sds = _sds(one_chip)
    B, P, T, N = SLOTS, TABLE_PAGES, PAGE_TOKENS, POOL_PAGES

    def shapes(make):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))

    params = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = shapes(lambda: tfm.init_kv_pages(cfg, N, T))
    scalar = sds((), jnp.int32)
    if step == "decode":
        assert tfm.paged_attention_path(cfg, T) == "paged_kernel"

        def decode(params, tokens, positions, kv, bts):
            return tfm.forward_decode(params, tokens, positions, cfg, kv, bts)

        return jax.jit(decode, donate_argnums=(3,)).lower(
            params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, P), jnp.int32)
        ).compile()

    def prefill(params, tokens, kv, table, length, write_from):
        return tfm.forward_prefill(params, tokens, cfg, kv, table, length, write_from)

    return jax.jit(prefill, donate_argnums=(2,)).lower(
        params, sds((1, P * T), jnp.int32), kv, sds((P,), jnp.int32), scalar, scalar
    ).compile()


def _pool_bytes(cfg):
    return 2 * cfg.n_layers * POOL_PAGES * PAGE_TOKENS * cfg.n_kv_heads * cfg.head_dim * 2


def test_prefill_executable_updates_the_pool_in_place(one_chip, mosaic):
    """The prefill executable of the largest bucket at the serving widths
    (2 layers, small vocab): the donated pool is aliased to the output and
    no second pool is among the temporaries (before PR 31 the pool rode the
    layer scan as xs / ys: 2.3-2.7 GiB of them at 8 layers)."""
    cfg = tfm.TransformerConfig(**SERVING)
    compiled = _compile_paged(one_chip, cfg, "prefill")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _pool_bytes(cfg)
    assert mem.temp_size_in_bytes < _pool_bytes(cfg) // 2
    assert pa.PREFILL_KERNEL_NAME in compiled.as_text()


def test_decode_step_updates_the_pool_in_place(one_chip, mosaic):
    """The decode executable at the serving widths (2 layers, small vocab):
    the donated pool is aliased to the output and the step's temporaries are
    a small fraction of it, i.e. no gathered table and no second pool."""
    cfg = tfm.TransformerConfig(**SERVING)
    compiled = _compile_paged(one_chip, cfg, "decode")
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _pool_bytes(cfg)
    assert mem.temp_size_in_bytes < _pool_bytes(cfg) // 8
    assert pa.KERNEL_NAME in compiled.as_text()


def _moved_attention_weights(text: str, cfg) -> list:
    """The ops of a compiled program's text whose RESULT is an attention
    weight of `cfg`, one layer's or the whole stack's, made by a `copy` or by
    a fusion that slices: a weight moved before it is multiplied."""
    d, q, kv = cfg.d_model, cfg.n_heads * cfg.head_dim, cfg.n_kv_heads * cfg.head_dim
    weights = {f"{n},{a},{b}" for n in (1, cfg.n_layers) for a, b in ((d, q), (d, kv), (q, d))}
    found = []
    for name, dims in re.findall(r"%([\w.\-]+) = bf16\[([\d,]+)\]\S* (?:copy|fusion)\(", text):
        if dims in weights and (name.startswith("copy") or "slice" in name):
            found.append(f"{name} bf16[{dims}]")
    return found


@pytest.mark.parametrize("step", ["decode", "prefill"])
@pytest.mark.parametrize("widths", [SERVING, GQA_PER_HEAD_NORM], ids=["mha", "gqa_per_head_norm"])
def test_paged_steps_read_the_attention_weights_where_they_lie(one_chip, mosaic, widths, step):
    """In a call of fewer rows than the weight has, q and k keep the shape
    their projections give them through qk-norm and rope (`_block` splits
    them into heads for `attend` alone), so no dot has a head-shaped result
    to lay its weight out for: the executables hold no
    copy and no slice fusion that yields a layer's `wq` / `wk` / `wv` / `wo`
    or a stack of them (before PR 39: a slice and a transpose of `wq` and
    `wk` every decode layer-step, both stacks transposed once a prefill call:
    2 x 64 MiB of temporaries at these 2 layers)."""
    cfg = tfm.TransformerConfig(**widths)
    compiled = _compile_paged(one_chip, cfg, step)
    assert _moved_attention_weights(compiled.as_text(), cfg) == []
    if step == "prefill":
        assert compiled.memory_analysis().temp_size_in_bytes < 64 * 2**20


# Trinity-Mini's routed FFN (128 experts of width 1024 over d 2048, top-8 by sigmoid, a shared expert) behind one dense
# layer, two routed layers, GQA 32:4 x 128 and a small vocabulary.
ROUTED = dict(
    vocab_size=2048, d_model=2048, n_layers=3, n_heads=32, n_kv_heads=4, d_head=128, d_ff=1024, n_experts=128, n_experts_per_tok=8,
    norm_topk_prob=True, router_score="sigmoid", route_scale=2.826, d_ff_shared=1024, n_dense_layers=1, d_ff_dense=6144,
    max_seq_len=8192, attn_impl="naive", remat=False,
)


def _copies_over(text, elements):
    """(dtype, dims) of the compiled text's `copy` results of more than that many elements."""
    return [m for m in re.findall(r"= (\w+)\[([\d,]+)\]\S* copy\(", text) if np.prod([int(n) for n in m[1].split(",")]) > elements]


def test_a_routed_models_chunk_takes_the_grouped_kernels_on_the_stack_in_place_and_its_decode_step_none(one_chip, mosaic):
    """At Trinity-Mini's widths: the prefill bucket's 256-row chunk holds the
    fused gate-up kernel and the down kernel once each (the routed layers are
    one scan body), reads the experts where they lie (no copy of a layer's
    [128, 2048, 1024] slice, 537 MB, which a kernel handed `_layer_of`'s
    slice would make every layer of every chunk) and holds no product of
    every expert by every row; the 16-row decode step holds neither kernel
    and keeps its every-expert products."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg = tfm.TransformerConfig(**ROUTED)
    E, f, rows = cfg.n_experts, cfg.d_ff, tfm.PREFILL_CHUNK_TOKENS
    assert tfm.experts_grouped_at(rows) and not tfm.experts_grouped_at(SLOTS)
    prefill, decode = (_compile_paged(one_chip, cfg, step).as_text() for step in ("prefill", "decode"))
    for name in (gm.SWIGLU_KERNEL_NAME, gm.MATMUL_KERNEL_NAME):
        assert len(re.findall(rf"custom_call_target=\"tpu_custom_call\".*{name}", prefill)) == 1, name
        assert name not in decode
    assert not _copies_over(prefill, 2**24)
    assert f"[{E},{rows},{f}]" not in prefill and f"[{E},{SLOTS},{f}]" in decode


def test_a_routed_models_big_chunk_compiles_at_1024_rows_and_keeps_the_pool_in_place(one_chip, mosaic):
    """At Trinity-Mini's widths the weights a pass reads are 8.5 times what a
    row multiplies: `forward_prefill` with `big_chunks` walks 1 024-row chunks
    (PR 62). Mosaic takes the paged prefill kernel at four blocks of 256 rows
    and both grouped kernels at 8 192 (row, expert) pairs, once each a scan
    body; the pool is aliased and no layer's experts are copied out."""
    from ray_tpu.ops import grouped_matmul as gm

    cfg = tfm.TransformerConfig(**ROUTED)
    assert tfm.prefill_big_chunk_tokens(cfg, PAGE_TOKENS) == tfm.PREFILL_CHUNK_CAP == 1024 and tfm.prefill_big_chunk_tokens(tfm.TransformerConfig(), PAGE_TOKENS) == 0
    sds = _sds(one_chip)
    shapes = lambda make: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))  # noqa: E731
    params, kv = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)), shapes(lambda: tfm.init_kv_pages(cfg, POOL_PAGES, PAGE_TOKENS))
    scalar = sds((), jnp.int32)
    compiled = jax.jit(lambda p, t, kv, bt, n, w, chunks: tfm.forward_prefill(p, t, cfg, kv, bt, n, w, big_chunks=chunks)[1], donate_argnums=(2,)).lower(
        params, sds((1, TABLE_PAGES * PAGE_TOKENS), jnp.int32), kv, sds((TABLE_PAGES,), jnp.int32), scalar, scalar, scalar).compile()
    text, mem = compiled.as_text(), compiled.memory_analysis()
    for name in (pa.PREFILL_KERNEL_NAME, gm.SWIGLU_KERNEL_NAME, gm.MATMUL_KERNEL_NAME):
        assert len(re.findall(rf"custom_call_target=\"tpu_custom_call\".*{name}", text)) >= 1, name
    assert f"[{cfg.n_experts},1024,{cfg.d_ff}]" not in text and not _copies_over(text, 2**24)
    assert mem.alias_size_in_bytes >= sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(kv))


@pytest.mark.parametrize("step,pages", [("decode", None), ("prefill", 2), ("prefill", 8)])
def test_paged_executables_carry_their_names_into_the_module(one_chip, step, pages):
    """PagedLM's jitted closures are named for what they are, so a device
    trace's `XLA Modules` line tells decode from each prefill bucket
    (before PR 40 both were `jit_step`): the lowered module and the program
    compiled for the described chip carry `jit_llm_decode` /
    `jit_llm_prefill_p<pages>`."""
    from ray_tpu.serve.llm.model import PagedLM

    lm = PagedLM(num_pages=16, page_tokens=4, max_slots=2, max_pages_per_seq=8)
    sds = _sds(one_chip)
    shaped = lambda tree: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params, kv, scalar = shaped(lm.params), shaped(lm.kv), sds((), jnp.int32)
    if step == "decode":
        name = "jit_llm_decode"
        lowered = lm._get_decode().lower(params, sds((2,), jnp.int32), sds((2,), jnp.int32), kv, sds((2, 8), jnp.int32), sds(lm._prev.shape, jnp.int32))
    else:
        name = f"jit_llm_prefill_p{pages}"
        lowered = lm._get_prefill(pages).lower(params, sds((1, pages * 4), jnp.int32), kv, sds((pages,), jnp.int32), scalar, scalar)
    assert f"module @{name} " in lowered.as_text()
    assert f"HloModule {name}," in lowered.compile().as_text()


# ------------------------------------------------ power retention (PR 42)

# Brumby-14B-Base's widths at 2 layers and a small vocabulary: GQA 40:8 x 128, retention of degree 2.
RETENTION = dict(
    vocab_size=2048, d_model=5120, n_layers=2, n_heads=40, n_kv_heads=8, d_head=128, d_ff=17408, max_seq_len=32768,
    rope_theta=1e6, norm_eps=1e-6, qk_norm=True, qk_norm_per_head=True, retention_degree=2, attn_impl="naive", remat=False,
)
STATE_SLOTS, STATE_PAGE_TOKENS = 32, 4096


def _compile_state(one_chip, step: str):
    """forward_decode (32 rows) or forward_prefill (the one bucket: a page of
    4 096 positions) of the retention widths over a pool of 33 states, donated."""
    cfg = tfm.TransformerConfig(**RETENTION)
    sds = _sds(one_chip)
    shapes = lambda make: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))  # noqa: E731
    params = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = shapes(lambda: tfm.init_kv_pages(cfg, STATE_SLOTS + 1, STATE_PAGE_TOKENS))
    B, scalar = STATE_SLOTS, sds((), jnp.int32)
    if step == "decode":
        return cfg, jax.jit(lambda p, t, pos, kv, bts: tfm.forward_decode(p, t, pos, cfg, kv, bts), donate_argnums=(3,)).lower(
            params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, 1), jnp.int32)).compile()
    return cfg, jax.jit(lambda p, t, kv, bt, n, w: tfm.forward_prefill(p, t, cfg, kv, bt, n, w), donate_argnums=(2,)).lower(
        params, sds((1, STATE_PAGE_TOKENS), jnp.int32), kv, sds((1,), jnp.int32), scalar, scalar).compile()


def _state_pool_bytes(cfg):
    D = tfm.retention_state_dim(cfg.head_dim)
    return cfg.n_layers * (STATE_SLOTS + 1) * cfg.n_kv_heads * D * (cfg.head_dim + 1) * 4


def test_retention_decode_step_is_one_kernel_a_layer_over_the_pool_in_place(one_chip, mosaic):
    """The decode step at Brumby's widths: the state update is the Mosaic
    kernel under its name, the donated pool of states (2.3 GB at 2 layers) is
    aliased to the output, and no copy of it, nor a gathered batch of states,
    is among the temporaries."""
    from ray_tpu.ops import power_retention

    cfg, compiled = _compile_state(one_chip, "decode")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert power_retention.can_tile(cfg.n_heads, cfg.n_kv_heads, cfg.head_dim)
    assert power_retention.KERNEL_NAME in text and "tpu_custom_call" in text
    assert mem.alias_size_in_bytes >= _state_pool_bytes(cfg)
    assert mem.temp_size_in_bytes < 64 * 2**20


def test_retention_prefill_bucket_is_one_kernel_a_layer_over_the_pool_in_place(one_chip, mosaic):
    """The one prefill bucket (a page of 4 096 positions walked in 256-row
    chunks): a chunk's retention is the Mosaic kernel under its name, the
    pool aliased, and neither phi of a chunk's queries (341 MB) nor a copy of
    one sequence's state (34 MB) is among the temporaries."""
    from ray_tpu.ops import power_retention

    cfg, compiled = _compile_state(one_chip, "prefill")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert tfm.prefill_paths(cfg, STATE_PAGE_TOKENS) == {"prefill_attention": "retention_kernel"}
    assert power_retention.PREFILL_KERNEL_NAME in text and "tpu_custom_call" in text
    assert mem.alias_size_in_bytes >= _state_pool_bytes(cfg)
    assert mem.temp_size_in_bytes < 64 * 2**20


def test_a_state_models_executables_carry_names_of_their_own(one_chip):
    from ray_tpu.serve.llm.model import PagedLM

    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, retention_degree=2, n_kv_heads=2)
    lm = PagedLM(cfg, num_pages=3, page_tokens=32, max_slots=2, max_pages_per_seq=1)
    sds = _sds(one_chip)
    shaped = lambda tree: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params, kv, scalar = shaped(lm.params), shaped(lm.kv), sds((), jnp.int32)
    decode = lm._get_decode().lower(params, sds((2,), jnp.int32), sds((2,), jnp.int32), kv, sds((2, 1), jnp.int32), sds(lm._prev.shape, jnp.int32))
    prefill = lm._get_prefill(1).lower(params, sds((1, 32), jnp.int32), kv, sds((1,), jnp.int32), scalar, scalar)
    assert "module @jit_llm_decode_state " in decode.as_text() and "module @jit_llm_prefill_state_p1 " in prefill.as_text()
    assert "HloModule jit_llm_decode_state," in decode.compile().as_text()


# ------------------------------------------------ a KDA stack (PR 44)

# Solar-Open2's widths at one period, 8 of 64 experts held and a small vocabulary: GQA 64:8 x 128 gated without rope,
# three KDA layers of 64 heads x 128 x 128, experts of width 1280.
KDA_STACK = dict(
    vocab_size=2048, d_model=4096, n_layers=4, n_heads=64, n_kv_heads=8, d_head=128, d_ff=1280, n_experts=64, n_experts_per_tok=8,
    norm_topk_prob=True, router_score="sigmoid", d_ff_shared=1280, n_experts_held=8, first_expert=8, kda_per_period=3, attn_gate=True,
    rope_layers=(False,) * 4, max_seq_len=8192, attn_impl="naive", remat=False,
)
KDA_SLOTS, KDA_PAGES, KDA_PAGE_TOKENS, KDA_TABLE = 16, 129, 128, 8


def _compile_kda(one_chip, step: str):
    """forward_decode (16 rows) or forward_prefill (a bucket of 4 pages) of
    the KDA stack over a pool of 129 K/V pages and 17 state slots, donated."""
    cfg = tfm.TransformerConfig(**KDA_STACK)
    sds = _sds(one_chip)
    shapes = lambda make: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))  # noqa: E731
    params = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = shapes(lambda: tfm.init_kv_pages(cfg, KDA_PAGES, KDA_PAGE_TOKENS, KDA_SLOTS + 1))
    B, scalar = KDA_SLOTS, sds((), jnp.int32)
    if step == "decode":
        return cfg, kv, jax.jit(lambda p, t, pos, kv, bts: tfm.forward_decode(p, t, pos, cfg, kv, bts), donate_argnums=(3,)).lower(
            params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, KDA_TABLE), jnp.int32)).compile()
    return cfg, kv, jax.jit(lambda p, t, kv, bt, n, w, slot: tfm.forward_prefill(p, t, cfg, kv, bt, n, w, slot), donate_argnums=(2,)).lower(
        params, sds((1, 4 * KDA_PAGE_TOKENS), jnp.int32), kv, sds((4,), jnp.int32), scalar, scalar, scalar).compile()


def _pool_nbytes(kv):
    return sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree_util.tree_leaves(kv))


def test_kda_decode_step_is_one_kernel_a_layer_over_both_pools_in_place(one_chip, mosaic):
    """The decode step at Solar-Open2's widths: the state update is the Mosaic
    kernel under its name beside the paged-attention kernel, both pools (K/V
    pages and 17 x 12.6 MB of states) are aliased to the output, no copy of
    either is among the temporaries, the expert stacks are read where they
    lie, and `wq` is not re-laid-out for a head-shaped result (with no rope
    between the projection and the split, that takes a barrier: `_block`)."""
    from ray_tpu.ops import kda

    cfg, kv, compiled = _compile_kda(one_chip, "decode")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert kda.can_tile(cfg.n_heads, cfg.head_dim, cfg.head_dim)
    assert kda.KERNEL_NAME in text and pa.KERNEL_NAME in text and text.count("tpu_custom_call") == 2
    assert mem.alias_size_in_bytes >= _pool_nbytes(kv)
    assert mem.temp_size_in_bytes < 64 * 2**20
    assert not _copies_over(text, 2**22)


def test_kda_prefill_bucket_updates_both_pools_in_place(one_chip, mosaic):
    """A prefill bucket of four pages walked in 256-row chunks: both pools
    aliased; the chunked form's [rows, rows, heads, channels] decays are fused
    into the sums over channels that consume them and never stored (a
    sub-chunk's would be 134 MB)."""
    cfg, kv, compiled = _compile_kda(one_chip, "prefill")
    mem, text = compiled.memory_analysis(), compiled.as_text()
    assert pa.PREFILL_KERNEL_NAME in text
    assert mem.alias_size_in_bytes >= _pool_nbytes(kv)
    assert mem.temp_size_in_bytes < 128 * 2**20
    # The chunk's expert products are the two grouped kernels of each of the period's two scan bodies (the softmax
    # layer's, the KDA layers'), on the stacks where they lie: no layer's [held, 4096, 1280] slice (84 MB) is copied
    # out for them, and no product of every held expert by every row [held, 256, 1280] is left.
    from ray_tpu.ops import grouped_matmul as gm

    assert tfm.experts_grouped_at(tfm.PREFILL_CHUNK_TOKENS)
    assert len(re.findall(rf"custom_call_target=\"tpu_custom_call\".*{gm.SWIGLU_KERNEL_NAME}", text)) == 2
    assert len(re.findall(rf"custom_call_target=\"tpu_custom_call\".*{gm.MATMUL_KERNEL_NAME}", text)) == 2
    assert not _copies_over(text, 2**22)
    assert f"[{cfg.experts_held},{tfm.PREFILL_CHUNK_TOKENS},{cfg.d_ff}]" not in text


def test_a_kda_stacks_executables_carry_names_of_their_own(one_chip):
    from ray_tpu.serve.llm.model import PagedLM

    cfg = tfm.tiny(attn_impl="naive", dtype=jnp.float32, n_layers=4, kda_per_period=3, rope_layers=(False,) * 4, n_kv_heads=2)
    lm = PagedLM(cfg, num_pages=9, page_tokens=8, max_slots=2, max_pages_per_seq=4)
    sds = _sds(one_chip)
    shaped = lambda tree: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), tree)  # noqa: E731
    params, kv, scalar = shaped(lm.params), shaped(lm.kv), sds((), jnp.int32)
    decode = lm._get_decode().lower(params, sds((2,), jnp.int32), sds((2,), jnp.int32), kv, sds((2, 4), jnp.int32), sds(lm._prev.shape, jnp.int32))
    prefill = lm._get_prefill(2).lower(params, sds((1, 16), jnp.int32), kv, sds((2,), jnp.int32), scalar, scalar, scalar)
    assert "module @jit_llm_decode_hybrid " in decode.as_text() and "module @jit_llm_prefill_hybrid_p2 " in prefill.as_text()
    assert "HloModule jit_llm_decode_hybrid," in decode.compile().as_text()


# ------------------------------------------------ latent attention (PR 50)

# dots.vlm1.inst's language model at published widths, one dense and one routed layer, 16 of 256 experts held, a small vocabulary.
LATENT = dict(
    vocab_size=2048, d_model=7168, n_layers=2, n_heads=128, n_kv_heads=128, d_head=192, d_ff=2048, max_seq_len=163840, norm_eps=1e-6,
    kv_lora_rank=512, q_lora_rank=1536, qk_nope_dim=128, qk_rope_dim=64, v_head_dim=128, rope_scaling=("yarn", 40.0, 4096.0, 32.0, 1.0, 1.0, 1.0),
    n_experts=256, n_experts_per_tok=8, norm_topk_prob=True, router_score="sigmoid", route_scale=2.5, d_ff_shared=2048,
    n_dense_layers=1, d_ff_dense=18432, n_experts_held=16, n_group=8, topk_group=4, attn_impl="naive", remat=False,
)
LATENT_SLOTS, LATENT_TABLE, LATENT_PAGE, LATENT_POOL = 32, 196, 128, 512


@pytest.mark.parametrize("kernel", ["decode", "prefill"])
def test_latent_kernels_compile_at_the_cells_widths(one_chip, kernel):
    """32 slots x 196 pages of 128 positions, 128 heads over rows of 640 lanes
    of which 512 are the value: the new cell's shapes, as Mosaic kernels with
    their names."""
    from ray_tpu.ops import latent_attention as la

    sds = _sds(one_chip)
    B, P, T, H, W, c = LATENT_SLOTS, LATENT_TABLE, LATENT_PAGE, 128, 640, 512
    pool, scalar = sds((5, LATENT_POOL, T, W), jnp.bfloat16), sds((), jnp.int32)
    if kernel == "decode":
        f = lambda q, pool, layer, bt, n: la.paged_latent_attention(q, pool, layer, bt, n, scale=0.135, v_width=c, interpret=False)  # noqa: E731
        text = jax.jit(f).lower(sds((B, H, W), jnp.bfloat16), pool, scalar, sds((B, P), jnp.int32), sds((B,), jnp.int32)).compile().as_text()
        assert la.KERNEL_NAME in text
    else:
        f = lambda q, pool, layer, bt, start, n: la.paged_latent_prefill_attention(q, pool, layer, bt, start, n, scale=0.135, v_width=c, interpret=False)  # noqa: E731
        text = jax.jit(f).lower(sds((256, H, W), jnp.bfloat16), pool, scalar, sds((P,), jnp.int32), scalar, scalar).compile().as_text()
        assert la.PREFILL_KERNEL_NAME in text
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("step", ["decode", "prefill"])
def test_a_latent_stacks_steps_run_the_latent_kernels_over_the_pool_in_place(one_chip, mosaic, step):
    """The decode step and the largest prefill bucket of a latent stack at
    published widths: the latent kernel of that step by name and not the
    other's nor the softmax kernels', the donated pool aliased to the output
    with no second pool among the temporaries, and no copy of a weight: a
    decode step moves nothing of 4 M elements or more (`w_uk`, `w_uv`, `wq_b`,
    `wo` and the expert stacks are read where they lie: without the barrier in
    `_mla_mixer` it re-laid `wq_b` out, 75 MB a layer a step); a chunk moves
    its own rows (the absorbed queries and outputs, 256 x 128 x 512) and
    nothing as large as `wq_b`."""
    from ray_tpu.ops import latent_attention as la

    cfg = tfm.TransformerConfig(**LATENT)
    sds = _sds(one_chip)
    B, P, T, N = LATENT_SLOTS, LATENT_TABLE, LATENT_PAGE, LATENT_POOL
    shapes = lambda make: jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))  # noqa: E731
    params, kv, scalar = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg)), shapes(lambda: tfm.init_kv_pages(cfg, N, T)), sds((), jnp.int32)
    assert tfm.decode_paths(cfg, T) == {"decode_attention": "latent_kernel"} and kv["ckv"].shape == (2, N, T, 640)
    if step == "decode":
        f = lambda params, tokens, positions, kv, bts: tfm.forward_decode(params, tokens, positions, cfg, kv, bts)  # noqa: E731
        compiled = jax.jit(f, donate_argnums=(3,)).lower(params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, P), jnp.int32)).compile()
        here, other = la.KERNEL_NAME, la.PREFILL_KERNEL_NAME
    else:
        f = lambda params, tokens, kv, table, length, write_from: tfm.forward_prefill(params, tokens, cfg, kv, table, length, write_from)  # noqa: E731
        compiled = jax.jit(f, donate_argnums=(2,)).lower(params, sds((1, P * T), jnp.int32), kv, sds((P,), jnp.int32), scalar, scalar).compile()
        here, other = la.PREFILL_KERNEL_NAME, la.KERNEL_NAME
    text, mem, pool_bytes = compiled.as_text(), compiled.memory_analysis(), 2 * N * T * 640 * 2
    assert here in text and other not in text and pa.KERNEL_NAME not in text and pa.PREFILL_KERNEL_NAME not in text
    assert mem.alias_size_in_bytes >= pool_bytes and mem.temp_size_in_bytes < pool_bytes * (1 if step == "decode" else 4)
    assert not _copies_over(text, 2**22 if step == "decode" else 2**25)


@pytest.mark.parametrize("batch,heads,kv_heads", [(3, 32, 8), (4, 16, 16)], ids=["mistral_gqa", "olmoe"])
def test_flash_kernels_compile_at_the_training_cells_shapes(one_chip, mosaic, batch, heads, kv_heads):
    """The three causal flash kernels (forward; dq; dk, dv) at the training
    cells' attention, q [batch, 4096, heads, 128] in bfloat16, with the tiles
    the module picks from the shape (square blocks whose diagonal is walked
    in sub-tiles): Mosaic takes each, inside the VMEM a kernel may use, and
    the three results keep the signatures `flash_attn_roofline*` tells the
    kinds apart by (benchmarks/metrics/flash_attn_roofline.json)."""
    import json
    import os

    import ray_tpu.ops.flash_attention  # noqa: F401 - the package exports the function under the module's name

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    sds = _sds(one_chip)
    s, hd = 4096, 128
    block_q, block_k, sub = fa._pick_blocks(s, hd, jnp.bfloat16, None, None, False)
    assert block_q == block_k and sub and block_q >= 2 * sub and fa.causal_work_ratio(s, block_q, block_k, sub) < 1.13
    q, kv = sds((batch, s, heads, hd), jnp.bfloat16), sds((batch, s, kv_heads, hd), jnp.bfloat16)

    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, kv, kv).compile().as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", "flash_attn_roofline.json")) as f:
        kinds = json.load(f)["args"]["kinds"]
    found = [next((kind for kind, pattern in kinds.items() if re.search(pattern, line)), None) for line in calls]
    assert sorted(found) == ["dkv", "dq", "fwd"], found
