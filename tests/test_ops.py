"""Oracle tests for the pallas kernels in ray_tpu.ops.

Run in pallas interpret mode on the CPU backend (same kernel code that
compiles on TPU) against the unfused attention_reference, at `highest`
matmul precision so the comparison is not dominated by the platform's
reduced-precision matmul default.
"""

import jax
import jax.numpy as jnp
import pytest

import sys

import ray_tpu.ops.flash_attention  # noqa: F401 - the package exports the function under the module's name
from ray_tpu.ops.flash_attention import flash_attention, flash_attention_with_lse
from ray_tpu.parallel.ring_attention import attention_reference

fa = sys.modules["ray_tpu.ops.flash_attention"]


@pytest.fixture(autouse=True)
def _exact_matmuls():
    old = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", "highest")
    yield
    jax.config.update("jax_default_matmul_precision", old)


def _qkv(b=2, s=256, h=4, d=64, kv_heads=None, dtype=jnp.float32):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (b, s, h, d), dtype)
    k = jax.random.normal(ks[1], (b, s, kv_heads or h, d), dtype)
    v = jax.random.normal(ks[2], (b, s, kv_heads or h, d), dtype)
    return q, k, v


# (block_q, block_k, s): the bodies a call can take. Square blocks of two sub-tiles or more walk their diagonal
# blocks as a triangle of 128-wide sub-tiles and mask only there (the path the training cells take at 1024 : 256);
# a block of one sub-tile is one masked tile, blocks that are not square mask by position: both as before PR 55.
TILES = {
    "one_tile_blocks": (128, 128, 256),
    "sub_tiled": (256, 256, 512),
    "sub_tiled_4x4": (256, 256, 1024),
    "uneven_blocks": (256, 128, 512),
    "uneven_blocks_wide_k": (128, 256, 512),
}
tiles = pytest.mark.parametrize("tile", sorted(TILES))


def _flash(tile, causal=True):
    block_q, block_k, _ = TILES[tile]
    return lambda q, k, v: flash_attention(q, k, v, causal=causal, block_q=block_q, block_k=block_k)


def _expanded_reference(rep, causal=True):
    return lambda q, k, v: attention_reference(q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2), causal=causal)


def _square_loss(fn):
    return lambda *args: jnp.sum(fn(*args) ** 2)


def _worst(got, want):
    return float(jnp.max(jnp.abs(got - want))) / float(jnp.max(jnp.abs(want)))


@tiles
def test_the_tiles_take_the_body_they_name(tile):
    block_q, block_k, s = TILES[tile]
    got = fa._pick_blocks(s, 64, jnp.float32, block_q, block_k, True)
    assert got == (block_q, block_k, 128 if tile.startswith("sub_tiled") else None)


@tiles
@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_reference(causal, tile):
    q, k, v = _qkv(b=1, s=TILES[tile][2], h=2)
    o = _flash(tile, causal)(q, k, v)
    ref = attention_reference(q, k, v, causal=causal)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


def test_flash_multiblock_row():
    # q block spans several k blocks: exercises the online-softmax carry.
    q, k, v = _qkv(b=1, s=512, h=2)
    o = flash_attention(q, k, v, causal=True, block_q=256, block_k=128)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


@pytest.mark.parametrize("tile,kv_heads", [("one_tile_blocks", 2), ("sub_tiled", 1), ("uneven_blocks", 1)])
def test_flash_gqa(tile, kv_heads):
    q, k, v = _qkv(b=1, s=TILES[tile][2], h=4, kv_heads=kv_heads)
    o = _flash(tile)(q, k, v)
    ref = _expanded_reference(4 // kv_heads)(q, k, v)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


@tiles
@pytest.mark.parametrize("wrt", ["q", "k", "v"])
def test_flash_grads_match_reference(wrt, tile):
    q, k, v = _qkv(b=1, s=TILES[tile][2], h=2)
    argnum = "qkv".index(wrt)
    g_flash = jax.grad(_square_loss(_flash(tile)), argnums=argnum)(q, k, v)
    g_ref = jax.grad(_square_loss(lambda q, k, v: attention_reference(q, k, v, causal=True)), argnums=argnum)(q, k, v)
    assert _worst(g_flash, g_ref) < 1e-4


@pytest.mark.parametrize("tile,kv_heads", [("one_tile_blocks", 2), ("sub_tiled", 1), ("uneven_blocks", 1)])
@pytest.mark.parametrize("wrt", ["q", "k", "v"])
def test_flash_gqa_grads_match_reference(wrt, tile, kv_heads):
    """GQA backward: the kernel sums dk/dv over the query heads sharing
    each kv head (BlockSpec-indexed, no materialized repeat); oracle is
    autodiff through an explicit jnp.repeat. `rep` 4 walks the dk/dv kernel's
    inner axis over four heads' q blocks, the skipped ones naming the first
    block the head computes."""
    q, k, v = _qkv(b=1, s=TILES[tile][2], h=4, kv_heads=kv_heads)
    argnum = "qkv".index(wrt)
    g_flash = jax.grad(_square_loss(_flash(tile)), argnums=argnum)(q, k, v)
    g_ref = jax.grad(_square_loss(_expanded_reference(4 // kv_heads)), argnums=argnum)(q, k, v)
    assert g_flash.shape == g_ref.shape
    assert _worst(g_flash, g_ref) < 1e-4


@pytest.mark.parametrize("tile", ["one_tile_blocks", "sub_tiled", "uneven_blocks"])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_with_lse_carries_an_upstream_dlse(causal, tile):
    """Ring attention's case: the loss reads the logsumexp too, so the
    backward kernels get a delta with dlse folded in."""
    block_q, block_k, s = TILES[tile]
    q, k, v = _qkv(b=1, s=s, h=2)

    def loss(fn):
        def f(q, k, v):
            o, lse = fn(q, k, v)
            return jnp.sum(o.astype(jnp.float32) ** 2) + jnp.sum(jnp.sin(lse))

        return f

    fused = loss(lambda q, k, v: flash_attention_with_lse(q, k, v, causal=causal, block_q=block_q, block_k=block_k))
    plain = loss(lambda q, k, v: fa.reference_attention_with_lse(q, k, v, causal=causal, scale=q.shape[-1] ** -0.5))
    assert abs(float(fused(q, k, v)) - float(plain(q, k, v))) < 1e-3 * abs(float(plain(q, k, v)))
    for got, want in zip(jax.grad(fused, argnums=(0, 1, 2))(q, k, v), jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
        assert _worst(got, want) < 1e-4


def test_the_work_a_causal_call_does_over_the_triangle_it_needs():
    """The counter that says how far the sub-tiled diagonal engages: at the
    training cells' shapes a head computed 10 blocks of 16 for the 8 the
    triangle holds; with 256-wide sub-tiles a diagonal block costs 10 / 16."""
    assert fa.causal_work_ratio(4096, 1024, 1024, None) == 1.25
    assert fa.causal_work_ratio(4096, 1024, 1024, 256) == 1.0625
    assert fa.causal_work_ratio(4096, 1024, 512, 256) == 1.25  # blocks that are not square are computed whole
    assert fa.causal_work_ratio(512, 256, 256, 128) == 1.25 and fa.causal_work_ratio(128, 128, 128, None) == 2.0
    assert fa._pick_blocks(4096, 128, jnp.bfloat16, None, None, False) == (fa.BLOCK, fa.BLOCK, fa.SUB)


@pytest.mark.parametrize("block_q,block_k", [(256, 256), (256, 128), (128, 256)])
def test_a_skipped_causal_step_names_the_block_already_there(block_q, block_k):
    """A grid step above the diagonal computes nothing; its index maps name
    the block the neighbouring computing step holds, so nothing is copied
    for it. Non-causal maps walk every block."""
    s, h, h_kv = 1024, 4, 2
    nq, nk = s // block_q, s // block_k
    kv_map, q_map = fa._kv_index(h, h_kv, True, block_q, block_k), fa._q_index(h, h_kv, nq, True, block_q, block_k)
    for i in range(nq):
        last = min(nk - 1, (i * block_q + block_q - 1) // block_k)
        assert [int(kv_map(5, i, j)[1]) for j in range(nk)] == [min(j, last) for j in range(nk)]
    for j in range(nk):
        first = (j * block_k) // block_q
        named = [tuple(int(x) for x in q_map(3, j, t)) for t in range(2 * nq)]
        assert named == [(4 + 2 + t // nq, max(t % nq, first), 0) for t in range(2 * nq)]  # batch 1, kv head 1, its two query heads
    assert [int(fa._kv_index(h, h_kv, False, block_q, block_k)(5, 0, j)[1]) for j in range(nk)] == list(range(nk))
    assert [int(fa._q_index(h, h_kv, nq, False, block_q, block_k)(3, nk - 1, t)[1]) for t in range(nq)] == list(range(nq))


def test_the_kernels_tiles_come_from_the_shape_and_from_no_environment():
    import inspect

    source = inspect.getsource(fa)
    assert "environ" not in source and "RAY_TPU_" not in source
    assert fa._pick_blocks(4096, 128, jnp.bfloat16, None, None, False) == (1024, 1024, 256)
    assert fa._pick_blocks(512, 128, jnp.bfloat16, None, None, False) == (512, 512, 128)
    assert fa._pick_blocks(128, 64, jnp.float32, None, None, True) == (128, 128, None)  # too short to sub-tile
    assert fa._pick_blocks(100, 64, jnp.float32, None, None, True) is None
    with pytest.raises(ValueError, match="cannot tile"):
        fa._pick_blocks(100, 64, jnp.float32, None, None, False)


def test_flash_odd_shape_falls_back():
    # Sequence not tileable by 8: wrapper must fall back to the unfused path.
    q, k, v = _qkv(s=100)
    o = flash_attention(q, k, v, causal=True)
    ref = attention_reference(q, k, v, causal=True)
    assert float(jnp.max(jnp.abs(o - ref))) < 2e-5


def test_flash_under_jit_and_grad():
    q, k, v = _qkv(s=128)

    @jax.jit
    def step(q, k, v):
        return jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)

    g = step(q, k, v)
    assert g.shape == q.shape and bool(jnp.all(jnp.isfinite(g)))
