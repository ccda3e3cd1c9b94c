"""Compiles for a DESCRIBED TPU v5e (no chip attached): what the chip's
compiler refuses, it refuses here, at no chip time. Interpret mode cannot
see a slice that cuts a tile or a kernel that needs too much VMEM.

The topology is described inside a fixture of THIS file and of no other
(one process may hold the TPU's library; see the on-chip-measurement
guide): nothing here touches it at import. Nothing runs, so these tests say
nothing about results or times.
"""

import sys

import jax
import jax.numpy as jnp
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


def test_prefill_executable_updates_the_pool_in_place(one_chip, mosaic):
    """The prefill executable of the largest bucket at the serving widths
    (2 layers, small vocab): the donated pool is aliased to the output and
    no second pool is among the temporaries (before PR 31 the pool rode the
    layer scan as xs / ys: 2.3-2.7 GiB of them at 8 layers)."""
    sds = _sds(one_chip)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=32, d_ff=1024, attn_impl="full"
    )
    P, T, N = 256, 16, 1024

    def shapes(make):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))

    params = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = shapes(lambda: tfm.init_kv_pages(cfg, N, T))

    def step(params, tokens, kv, table, length, write_from):
        return tfm.forward_prefill(params, tokens, cfg, kv, table, length, write_from)

    scalar = sds((), jnp.int32)
    compiled = jax.jit(step, donate_argnums=(2,)).lower(
        params, sds((1, P * T), jnp.int32), kv, sds((P,), jnp.int32), scalar, scalar
    ).compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * cfg.n_layers * N * T * cfg.n_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 2
    assert pa.PREFILL_KERNEL_NAME in compiled.as_text()


def test_decode_step_updates_the_pool_in_place(one_chip, mosaic):
    """The decode executable at the serving widths (2 layers, small vocab):
    the donated pool is aliased to the output and the step's temporaries are
    a small fraction of it, i.e. no gathered table and no second pool."""
    sds = _sds(one_chip)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=4096, n_layers=2, n_heads=32, n_kv_heads=32, d_ff=1024, attn_impl="full"
    )
    B, P, T, N = 16, 256, 16, 1024
    assert tfm.paged_attention_path(cfg, T) == "paged_kernel"

    def shapes(make):
        return jax.tree_util.tree_map(lambda x: sds(x.shape, x.dtype), jax.eval_shape(make))

    params = shapes(lambda: tfm.init_params(jax.random.PRNGKey(0), cfg))
    kv = shapes(lambda: tfm.init_kv_pages(cfg, N, T))

    def step(params, tokens, positions, kv, bts):
        return tfm.forward_decode(params, tokens, positions, cfg, kv, bts)

    compiled = jax.jit(step, donate_argnums=(3,)).lower(
        params, sds((B,), jnp.int32), sds((B,), jnp.int32), kv, sds((B, P), jnp.int32)
    ).compile()
    mem = compiled.memory_analysis()
    pool_bytes = 2 * cfg.n_layers * N * T * cfg.n_kv_heads * cfg.head_dim * 2
    assert mem.alias_size_in_bytes >= pool_bytes
    assert mem.temp_size_in_bytes < pool_bytes // 8
    assert pa.KERNEL_NAME in compiled.as_text()
