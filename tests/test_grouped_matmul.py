"""ops/grouped_matmul.py, interpreted on the CPU: rows sorted by expert times
each row's own expert of one layer of a stack that stays where it lies,
against `lax.ragged_dot` on that layer's slice and against a loop over the
experts in float32. float32 operands at matmul precision "highest" on both
sides, so what differs is the order of a sum.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

from ray_tpu.ops import grouped_matmul as gm

TILE = 16
LAYERS, K, N = 3, 32, 512
CASES = {
    # group sizes a case; m, the rows (more than their sum: the rest lie behind the last group, as under a share)
    "sizes_0_1_tile-1_tile_tile+1": ([0, 1, TILE - 1, TILE, TILE + 1, 0], 3 * TILE + 1),
    "one_group_holds_every_row": ([0, 0, 70, 0], 70),
    "every_group_a_few_rows": ([5, 3, 7, 2, 6, 4, 1, 8], 36),
    "a_share_rows_behind_the_last_group": ([9, 0, 20, 3], 75),
    "m_no_multiple_of_the_tile_last_group_cut_by_it": ([TILE, 2 * TILE + 5], 3 * TILE + 5),
    "groups_larger_than_a_tile_each": ([40, 33, 50], 128),
    "no_row_on_any_expert": ([0, 0, 0], 20),
    "first_and_last_groups_empty": ([0, 0, 17, 30, 0, 0], 47),
}


def operands(sizes, m, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    E = len(sizes)
    xs = jax.random.normal(ks[0], (m, K), jnp.float32)
    gate, up = (jax.random.normal(k, (LAYERS, E, K, N), jnp.float32) * K**-0.5 for k in ks[1:3])
    down = jax.random.normal(ks[3], (LAYERS, E, N, K), jnp.float32) * N**-0.5
    return xs, gate, up, down, jnp.asarray(sizes, jnp.int32)


def expert_loop(xs, w, sizes):
    """Each group's rows times its expert, one expert at a time; rows behind the last group 0."""
    out, start = np.zeros((xs.shape[0], w.shape[-1]), np.float32), 0
    for e, size in enumerate(np.asarray(sizes)):
        out[start : start + size] = np.asarray(xs[start : start + size], np.float32) @ np.asarray(w[e], np.float32)
        start += size
    return out


@pytest.mark.parametrize("layer,blocks", [(0, 1), (2, 2)], ids=["layer_0_all_of_N_a_block", "layer_2_two_blocks_of_N"])
@pytest.mark.parametrize("case", list(CASES))
def test_each_row_is_multiplied_by_its_own_expert_of_the_layer_named(monkeypatch, case, layer, blocks):
    """The down product and the fused gate-up-activation, of layer `layer` of
    a [layers, E, ., .] stack, over the rows of every group: `lax.ragged_dot`'s
    numbers on the layer's slice and a per-expert loop's. Rows behind the
    last group are not compared: no expert is theirs. With the weight blocks'
    bytes cut so that the gate product walks N in two blocks (the fused one
    then in four), every touched expert is fetched once a block."""
    monkeypatch.setattr(gm, "BLOCK_BYTES", K * N * 4 // blocks)
    assert gm.block_cols(K, N, 4) == N // blocks and gm.block_cols(K, N, 4, 2) == N // blocks // 2
    sizes, m = CASES[case]
    xs, gate, up, down, group_sizes = operands(sizes, m, seed=len(case))
    total = sum(sizes)
    plan = gm.visits(group_sizes, m, TILE)
    assert int(plan.total[0]) <= plan.tile.shape[0] == -(-m // TILE) + len(sizes) - 1
    with jax.default_matmul_precision("highest"):
        got = gm.grouped_matmul(xs, gate, jnp.int32(layer), plan, tile_rows=TILE)
        ragged = lax.ragged_dot(xs, gate[layer], group_sizes)
        act = gm.grouped_swiglu(xs, gate, up, jnp.int32(layer), plan, tile_rows=TILE)
        ys = gm.grouped_matmul(act, down, jnp.int32(layer), plan, tile_rows=TILE)
    assert got.shape == (m, N) and got.dtype == gate.dtype and ys.shape == (m, K)
    np.testing.assert_allclose(got[:total], ragged[:total], rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(got[:total], expert_loop(xs, gate[layer], sizes)[:total], rtol=1e-5, atol=1e-5)
    g, u = expert_loop(xs, gate[layer], sizes), expert_loop(xs, up[layer], sizes)
    want = expert_loop(g / (1 + np.exp(-g)) * u, down[layer], sizes)
    np.testing.assert_allclose(ys[:total], want[:total], rtol=1e-4, atol=1e-5)
    # a tile that a group reaches holds 0 behind its last group's rows, not what was in memory
    reached = min(m, -(-total // TILE) * TILE)
    assert not np.asarray(got[total:reached]).any()


def test_results_come_in_the_stacks_type_from_a_float32_sum():
    """bfloat16 operands: the product is summed in float32 and rounded once,
    as `lax.ragged_dot(..., preferred_element_type=bfloat16)` rounds it; gate
    and up are each rounded before the activation, as two products would."""
    sizes, m = CASES["every_group_a_few_rows"]
    xs, gate, up, _down, group_sizes = (a.astype(jnp.bfloat16) if a.dtype == jnp.float32 else a for a in operands(sizes, m, seed=1))
    plan = gm.visits(group_sizes, m, TILE)
    got = gm.grouped_matmul(xs, gate, jnp.int32(1), plan, tile_rows=TILE)
    want = lax.ragged_dot(xs, gate[1], group_sizes, preferred_element_type=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    act = gm.grouped_swiglu(xs, gate, up, jnp.int32(1), plan, tile_rows=TILE)
    u = lax.ragged_dot(xs, up[1], group_sizes, preferred_element_type=jnp.bfloat16)
    np.testing.assert_array_equal(
        np.asarray(act, np.float32), np.asarray((jax.nn.silu(want.astype(jnp.float32)) * u.astype(jnp.float32)).astype(jnp.bfloat16), np.float32)
    )


def test_an_expert_is_visited_once_a_tile_its_rows_lie_in_and_an_empty_one_never():
    """The visits in order of expert, and the touched experts in order: what
    the kernel's transfers read, so an expert's block is asked for once a
    block of N and an empty group names none."""
    sizes = [0, 1, TILE - 1, TILE, TILE + 1, 0]
    plan = gm.visits(jnp.asarray(sizes, jnp.int32), 3 * TILE + 1, TILE)
    total, runs = int(plan.total[0]), int(plan.runs[0])
    tile, lo, hi, run = (np.asarray(a)[:total].tolist() for a in plan[:4])
    assert runs == 4 and np.asarray(plan.expert)[:runs].tolist() == [1, 2, 3, 4]
    assert run == [0, 1, 2, 3, 3] and tile == [0, 0, 1, 2, 3]
    assert lo == [0, 1, TILE, 2 * TILE, 2 * TILE] and hi == [1, TILE, 2 * TILE, 3 * TILE + 1, 3 * TILE + 1]
    # behind the last real visit the grid repeats it: the row and result tiles stay as they are
    assert set(np.asarray(plan.tile)[total:].tolist()) <= {3}
    none = gm.visits(jnp.zeros((3,), jnp.int32), 20, TILE)
    assert int(none.total[0]) == int(none.runs[0]) == 0 and np.asarray(none.tile).tolist() == [0] * 4


def test_a_weight_block_is_whole_lanes_inside_its_bytes_or_all_of_a_narrow_matrix():
    assert gm.block_cols(2048, 1024, 2, 2) == 512 and gm.block_cols(1024, 2048, 2) == 2048  # Trinity: gate and up 2 MB each, down 4 MB
    assert gm.block_cols(4096, 1280, 2, 2) == 256 and gm.block_cols(1280, 4096, 2) == 1024  # Solar-Open2
    assert gm.block_cols(32, 96, 4) == 96  # no whole lanes: the matrix's width
    with pytest.raises(ValueError, match="an expert stack is"):
        gm.grouped_matmul(jnp.zeros((8, 16)), jnp.zeros((2, 4, 32, 16)), 0, gm.visits(jnp.asarray([8, 0, 0, 0]), 8, 8), tile_rows=8)
