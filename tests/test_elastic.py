"""Elastic world-size training: resharded restore + ZeRO-sharded optimizer.

Done-criteria of the elastic PR:
  (a) the reshardable checkpoint format round-trips bitwise across world
      sizes: save@N -> restore@M -> save@M -> restore@N for N,M in
      {1, 2, 4} (params AND optimizer state);
  (b) the ZeRO-sharded optimizer update matches the unsharded update
      step-for-step, and per-chip optimizer state shrinks >= ~2x at
      world 4;
  (c) capacity renegotiation: _wait_for_capacity is event-driven
      (node_events), its timeout either downsizes (elastic) or fails
      fast with CapacityTimeoutError — never a doomed attempt;
  (d) the chaos acceptance e2e: injected node loss with NO replacement ->
      same-step resume at N-1 with the world-size-correct loss
      trajectory -> grow-back to target when capacity returns;
  (e) cgraph gangs resize through member death (ElasticGraph).

All tests run under JAX_PLATFORMS=cpu on the virtual 8-device mesh with
deterministic seeds. Cluster-backed tests share ONE module-scoped boot.
"""

import itertools
import threading
import time

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu import exceptions as exc
from ray_tpu.core import runtime_base
from ray_tpu.core.cluster_runtime import Cluster


def _wait_for(pred, timeout=20.0, interval=0.05):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# =================================== (a) reshardable checkpoint round trips
def _mixed_tree():
    import ml_dtypes

    rng = np.random.default_rng(0)
    return {
        "w": rng.standard_normal((13, 7)).astype(np.float32),
        "emb": rng.standard_normal((5, 9)).astype(ml_dtypes.bfloat16),
        "nested": {
            "scale": np.ones((11,), np.float32),
            "count": np.int32(42),  # scalar leaf: smaller than any world
        },
    }


def _assert_tree_bitwise(a, b):
    import jax

    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def test_shard_bounds_exhaustive_partition():
    from ray_tpu.train import elastic_checkpoint as ec

    for size, world in itertools.product((0, 1, 5, 16, 17), (1, 2, 3, 4, 7)):
        spans = [ec.shard_bounds(size, world, r) for r in range(world)]
        assert spans[0][0] == 0 and spans[-1][1] == size
        for (a0, a1), (b0, b1) in zip(spans, spans[1:]):
            assert a1 == b0  # contiguous, no gap, no overlap
    with pytest.raises(ValueError):
        ec.shard_bounds(8, 2, 2)


def test_reshard_roundtrip_bitwise(tmp_path):
    """save@N -> restore@M -> save@M -> restore@N is bitwise-identical for
    params and optimizer state across N,M in {1, 2, 4}."""
    import optax

    from ray_tpu.train import elastic_checkpoint as ec

    params = _mixed_tree()
    opt_state = optax.adamw(1e-3).init(
        {k: v for k, v in params.items() if k != "nested"}
    )
    for n, m in itertools.product((1, 2, 4), (1, 2, 4)):
        d_n = str(tmp_path / f"ck_{n}_{m}_n")
        for r in range(n):
            ec.save_state(
                d_n, params, opt_state, step=7, world_size=n, rank=r,
                meta={"n": n},
            )
        # restore@M (shard view), then save@M from the full restore and
        # restore@N again — the full chain the ISSUE names.
        for r in range(m):
            slices, manifest = ec.load_shard(d_n, world_size=m, rank=r, kind="params")
            assert manifest["world_size"] == n
            for s in slices:
                assert s.flags["C_CONTIGUOUS"] or s.size == 0
        d_m = str(tmp_path / f"ck_{n}_{m}_m")
        ec.reshard(d_n, d_m, m, kind="params")
        ec.reshard(d_n, d_m, m, kind="opt")
        state_m = ec.load_state(d_m)
        assert state_m["step"] == 7 and state_m["saved_world_size"] == m
        _assert_tree_bitwise(state_m["params"], params)
        _assert_tree_bitwise(state_m["opt_state"], opt_state)
        d_back = str(tmp_path / f"ck_{n}_{m}_back")
        ec.reshard(d_m, d_back, n, kind="params")
        ec.reshard(d_m, d_back, n, kind="opt")
        state_n = ec.load_state(d_back)
        _assert_tree_bitwise(state_n["params"], params)
        _assert_tree_bitwise(state_n["opt_state"], opt_state)


def test_elastic_checkpoint_partial_rank_save_assembles(tmp_path):
    """Each rank writes only its own shard file; the union restores the
    full tree (what a real gang does — no rank holds the manifest alone)."""
    from ray_tpu.train import elastic_checkpoint as ec

    tree = _mixed_tree()
    d = str(tmp_path / "gang")
    for r in (2, 0, 1):  # ranks save in any order
        ec.save_shards(d, tree, world_size=3, rank=r, step=3)
    out, manifest = ec.load_full(d)
    assert manifest["step"] == 3
    _assert_tree_bitwise(out, tree)


# ====================================== (b) ZeRO-sharded optimizer numerics
def _mesh(n):
    import jax
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices("cpu")[:n]), ("data",))


# The toy problem's leaves by how ZeroSharder cuts them at n = 2 and 4: "flat"
# has no inner dimension divisible by n (every leaf flattened, padded and cut
# into runs, as before PR 57), "sliced" has leaves cut along a dimension (a
# stacked [L, d, f], a 2-D [vocab, d]) beside a 1-D and a scalar that stay flat.
TOY_SHAPES = {
    "flat": {"w": (13, 7), "b": (5,)},
    "sliced": {"w": (13, 8), "b": (4,), "u": (3, 8, 12)},
}
TOY_KINDS = sorted(TOY_SHAPES)


def _toy_problem(kind="flat"):
    import jax
    import jax.numpy as jnp

    shapes = TOY_SHAPES[kind]
    cols = shapes["w"][1]
    params = {
        "w": jax.random.normal(jax.random.PRNGKey(0), shapes["w"], jnp.float32),
        "b": jnp.zeros(shapes["b"], jnp.float32),
        "s": jnp.float32(2.0),
    }
    if "u" in shapes:
        params["u"] = jax.random.normal(jax.random.PRNGKey(3), shapes["u"], jnp.float32)

    def loss_fn(p, batch):
        x, y = batch
        h = x @ p["w"]
        if "u" in p:  # a stack of layers: h <- h + tanh(h u_l) u_l^T
            for layer in range(p["u"].shape[0]):
                h = h + jnp.tanh(h @ p["u"][layer]) @ p["u"][layer].T
        pred = h @ jnp.ones((cols,), jnp.float32) + p["b"].sum() * p["s"]
        return jnp.mean((pred - y) ** 2)

    x = jax.random.normal(jax.random.PRNGKey(1), (16, 13))
    y = jax.random.normal(jax.random.PRNGKey(2), (16,))
    return params, loss_fn, x, y


# (shape, the dimension it is cut along at n = 2 and at n = 4; None = flat)
PARTITION_CASES = {
    "stacked_3d": ((3, 8, 12), 2, 2),
    "stacked_3d_first_inner": ((3, 8, 6), 2, 1),
    "matrix_2d": ((16, 8), 1, 1),
    "vector_1d": ((8,), None, None),
    "not_divisible": ((13, 7), None, None),
    "scalar": ((), None, None),
}


@pytest.mark.parametrize("n", [2, 4])
@pytest.mark.parametrize("case", sorted(PARTITION_CASES))
def test_zero_partition_round_trips_exactly(case, n):
    """What a chip owns of a leaf is decided by the leaf's shape and n alone,
    and every view of it agrees: the global vector `flatten` makes is the
    chips' flattened shards one after another, a sliced leaf's shard is its
    slice along the cut dimension, `unflatten` and `to_logical` undo it bit
    for bit (a permutation and a zero pad: no arithmetic)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import zero

    shape, dim_at_2, dim_at_4 = PARTITION_CASES[case]
    size = int(np.prod(shape)) if shape else 1
    leaf = jnp.arange(1, size + 1, dtype=jnp.float32).reshape(shape)
    tree = {"only": leaf}
    sharder = zero.ZeroSharder(tree, _mesh(n), "data")
    dim = dim_at_2 if n == 2 else dim_at_4
    assert sharder.dims == [dim]
    flat = sharder.flatten(tree)["0"]
    m = sharder.padded[0] // n
    assert flat.shape == (sharder.padded[0],) and sharder.shard_struct()["0"].shape == (m,)
    shards = [np.asarray(flat[r * m : (r + 1) * m]) for r in range(n)]
    if dim is None:
        want = np.concatenate([np.asarray(leaf).reshape(-1), np.zeros(sharder.padded[0] - size, np.float32)])
        np.testing.assert_array_equal(np.concatenate(shards), want)
    else:
        k = shape[dim] // n
        for r, shard in enumerate(shards):
            np.testing.assert_array_equal(shard, np.asarray(jax.lax.slice_in_dim(leaf, r * k, (r + 1) * k, axis=dim)).reshape(-1))
    np.testing.assert_array_equal(np.asarray(sharder.unflatten({"0": flat})["only"]), np.asarray(leaf))
    # the optimizer state's two views: {"0": moment} is what optax builds over the shard dict
    logical = {"mu": {"0": np.asarray(leaf)}, "count": np.int32(3)}
    placed = sharder.from_logical(logical)
    assert placed["mu"]["0"].shape == (sharder.padded[0],)
    np.testing.assert_array_equal(np.asarray(placed["mu"]["0"]), np.asarray(flat))
    back = sharder.to_logical(placed)
    assert back["mu"]["0"].shape == shape and int(back["count"]) == 3
    np.testing.assert_array_equal(back["mu"]["0"], np.asarray(leaf))


def test_zero_step_lowers_to_a_reduce_scatter_of_the_leaf_in_its_own_shape():
    """The gradient goes to the collective as the backward pass left it: the
    lowered step holds a `reduce_scatter` whose operand has the leaf's shape
    and whose result is the slice along the cut dimension, for every sliced
    leaf, and no whole gradient is flattened before one (the TPU compiler
    rewrites a reduce-scatter of a flattened vector to all-reduce + slice:
    train/zero.py's docstring)."""
    import re

    import optax

    from ray_tpu.train import zero

    params, loss_fn, x, y = _toy_problem("sliced")
    tx = optax.adamw(1e-2)
    mesh = _mesh(4)
    step, sharder = zero.build_zero_step(loss_fn, tx, params, mesh, axis="data", donate=False)
    opt = zero.init_opt_state(tx, params, mesh, axis="data")
    text = step.lower(params, opt, (x, y)).as_text()
    found = re.findall(
        r'"stablehlo\.reduce_scatter"\(%\w+\) <\{[^}]*scatter_dimension = (\d+) : i64[^}]*\}> \(\{.*?\}\) : \(tensor<([0-9x]*)xf32>\) -> tensor<([0-9x]*)xf32>',
        text, flags=re.S,
    )
    got = sorted((tuple(map(int, a.split("x"))), int(d), tuple(map(int, b.split("x")))) for d, a, b in found)
    want = []
    for shape, dim, padded in zip(sharder.shapes, sharder.dims, sharder.padded):
        if dim is None:
            want.append(((padded,), 0, (padded // 4,)))
        else:
            want.append((shape, dim, shape[:dim] + (shape[dim] // 4,) + shape[dim + 1 :]))
    assert got == sorted(want)
    sliced = [w for w in want if len(w[0]) > 1]
    assert sorted(s for s, _d, _r in sliced) == [(3, 8, 12), (13, 8)]
    # the same for the updated parameters: gathered along the cut dimension, in the leaf's shape
    gathered = re.findall(r'"stablehlo\.all_gather"\(%\w+\) <\{all_gather_dim = (\d+) : i64[^}]*\}> : \(tensor<[0-9x]*xf32>\) -> tensor<([0-9x]*)xf32>', text)
    assert sorted((tuple(map(int, s.split("x"))), int(d)) for d, s in gathered) == sorted((w[0], w[1]) for w in want)


@pytest.mark.parametrize("kind", TOY_KINDS)
def test_zero_update_matches_unsharded_step_for_step(kind):
    """Identical grads through the sharded update vs plain tx.update must
    agree to float32 ulp over multiple steps (elementwise adam math,
    just sliced)."""
    import jax
    import optax

    from ray_tpu.train import zero

    params, loss_fn, x, y = _toy_problem(kind)
    tx = optax.adamw(1e-2)
    mesh = _mesh(4)
    update, sharder = zero.build_zero_update(tx, params, mesh, axis="data")
    opt_sharded = zero.init_opt_state(tx, params, mesh, axis="data")
    opt_ref = tx.init(params)
    p_sharded = p_ref = params
    for step in range(4):
        grads = jax.grad(lambda p: loss_fn(p, (x, y)))(p_ref)
        p_sharded, opt_sharded = update(p_sharded, opt_sharded, grads)
        u, opt_ref = tx.update(grads, opt_ref, p_ref)
        p_ref = optax.apply_updates(p_ref, u)
        for k in params:
            np.testing.assert_allclose(
                np.asarray(p_sharded[k]), np.asarray(p_ref[k]),
                rtol=0, atol=5e-7,  # <= a few float32 ulps from XLA fusion
                err_msg=f"step {step} leaf {k}",
            )


@pytest.mark.parametrize("kind", TOY_KINDS)
def test_zero_fused_step_trajectory_and_bytes(kind):
    """The fused step (reduce_scatter local grads -> shard update ->
    all_gather) tracks the unsharded DP step, and per-chip optimizer
    state is >= ~2x smaller at world 4 (acceptance criterion)."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.train import zero

    params, loss_fn, x, y = _toy_problem(kind)
    tx = optax.adamw(1e-2)
    mesh = _mesh(4)
    step, _ = zero.build_zero_step(loss_fn, tx, params, mesh, axis="data", donate=False)
    opt_z = zero.init_opt_state(tx, params, mesh, axis="data")
    opt_full = tx.init(params)

    import jax.numpy as jnp

    @jax.jit
    def ref_step(p, o, b):
        l, g = jax.value_and_grad(loss_fn)(p, b)
        u, o = tx.update(g, o, p)
        return optax.apply_updates(p, u), o, l

    batch = (
        jax.device_put(x, NamedSharding(mesh, P("data"))),
        jax.device_put(y, NamedSharding(mesh, P("data"))),
    )
    pz, pu = params, params
    for _ in range(3):
        pz, opt_z, lz = step(pz, opt_z, batch)
        pu, opt_full, lu = ref_step(pu, opt_full, (x, y))
        np.testing.assert_allclose(float(lz), float(lu), rtol=1e-5)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(pz[k]), np.asarray(pu[k]), rtol=1e-3, atol=1e-4
        )
    full_bytes = zero.per_device_bytes(opt_full)
    shard_bytes = zero.per_device_bytes(opt_z)
    assert shard_bytes * 2 <= full_bytes, (full_bytes, shard_bytes)


@pytest.mark.parametrize("kind", TOY_KINDS)
def test_zero_logical_state_reshards_across_worlds(tmp_path, kind):
    """Optimizer state saved through the elastic format at world 4
    restores at world 2 and continues the SAME trajectory (reshard is
    exact: the pad region provably stays zero)."""
    import jax
    import optax

    from ray_tpu.train import elastic_checkpoint as ec, zero

    params, loss_fn, x, y = _toy_problem(kind)
    tx = optax.adamw(1e-2)
    mesh4, mesh2 = _mesh(4), _mesh(2)
    upd4, sh4 = zero.build_zero_update(tx, params, mesh4, axis="data")
    opt4 = zero.init_opt_state(tx, params, mesh4, axis="data")
    grads = jax.grad(lambda p: loss_fn(p, (x, y)))(params)
    p1, opt4 = upd4(params, opt4, grads)

    # checkpoint the LOGICAL state at world 4, restore at world 2
    d = str(tmp_path / "zero_ck")
    ec.save_state(d, p1, sh4.to_logical(opt4), step=1, world_size=1, rank=0)
    state = ec.load_state(d)
    sh2 = zero.ZeroSharder(params, mesh2, "data")
    opt2 = sh2.from_logical(state["opt_state"])
    from jax.sharding import NamedSharding, PartitionSpec as P

    p1_at2 = jax.tree_util.tree_map(
        lambda a: jax.device_put(np.asarray(a), NamedSharding(mesh2, P())),
        state["params"],
    )
    upd2, _ = zero.build_zero_update(tx, params, mesh2, axis="data")
    p2_resharded, opt2 = upd2(p1_at2, opt2, grads)
    p2_straight, opt4 = upd4(p1, opt4, grads)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p2_resharded[k]), np.asarray(p2_straight[k]),
            rtol=0, atol=5e-7,
        )


def test_transformer_build_train_step_zero_parity():
    """models.transformer.build_train_step(zero_axis=...) — the model-level
    entry point — identical loss trajectory to the unsharded step."""
    import jax
    import jax.numpy as jnp
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import transformer as tfm
    from ray_tpu.train import zero

    mesh = _mesh(4)
    cfg = tfm.tiny(dtype=jnp.float32)
    tx = optax.adamw(1e-3)
    init_z, step_z = tfm.build_train_step(cfg, tx, mesh, zero_axis="data", donate=False)
    init_u, step_u = tfm.build_train_step(cfg, tx, mesh, donate=False)
    pz, oz = init_z(jax.random.PRNGKey(0))
    pu, ou = init_u(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size)
    tz = jax.device_put(tokens, NamedSharding(mesh, P("data")))
    for _ in range(3):
        pz, oz, lz = step_z(pz, oz, tz)
        pu, ou, lu = step_u(pu, ou, tokens)
        np.testing.assert_allclose(float(lz), float(lu), rtol=1e-5)
    assert zero.per_device_bytes(oz) * 2 <= zero.per_device_bytes(ou)


def test_goodput_degraded_category_weighting():
    from ray_tpu.observability import goodput as g

    clock = [0.0]
    acct = g.GoodputAccountant(clock=lambda: clock[0])
    acct.begin(g.PRODUCTIVE)
    clock[0] = 10.0
    acct.set_weight(g.DEGRADED, 0.5)
    acct.begin(g.DEGRADED)
    clock[0] = 20.0
    acct.finish()
    snap = acct.snapshot()
    assert snap["seconds"]["productive"] == 10.0
    assert snap["seconds"]["degraded"] == 10.0
    # 10s at 1.0 + 10s at 0.5 over 20s total
    assert abs(snap["goodput"] - 0.75) < 1e-9
    with pytest.raises(ValueError):
        acct.set_weight("bogus", 1.0)


# =========================== (c)+(d)+(e) cluster-backed: ONE shared boot
@pytest.fixture(scope="module")
def elastic_cluster():
    rt.shutdown()
    cluster = Cluster(num_cpus=2)
    runtime = cluster.runtime()
    runtime_base.set_runtime(runtime)
    yield cluster, runtime
    rt.shutdown()


def test_node_added_event_and_capacity_wait(elastic_cluster):
    """_wait_for_capacity is event-driven: a node join publishes
    node_added on node_events and wakes the waiter; an infeasible wait
    times out False instead of launching a doomed attempt."""
    cluster, runtime = elastic_cluster
    from ray_tpu.train import JaxTrainer, ScalingConfig
    from ray_tpu.utils.node_events import NodeEventWatcher

    trainer = JaxTrainer(
        lambda config: None,
        scaling_config=ScalingConfig(
            num_workers=1, resources_per_worker={"cap_probe": 1.0}
        ),
    )
    assert trainer._feasible_workers() == 0
    t0 = time.monotonic()
    assert trainer._wait_for_capacity(1, timeout_s=0.8) is False
    assert time.monotonic() - t0 < 5.0

    watcher = NodeEventWatcher(runtime._gcs)
    added = {}

    def add_soon():
        time.sleep(0.4)
        added["node"] = cluster.add_node(num_cpus=1, resources={"cap_probe": 1.0})

    threading.Thread(target=add_soon, daemon=True).start()
    assert trainer._wait_for_capacity(1, timeout_s=20.0) is True
    assert trainer._feasible_workers() >= 1
    assert _wait_for(lambda: added.get("node") in watcher.added, timeout=10)
    watcher.stop()


def test_renegotiate_downsizes_or_fails_fast(elastic_cluster):
    """The _wait_for_capacity timeout path: elastic runs downsize to the
    largest feasible world; non-elastic (or below-floor) runs get the
    typed CapacityTimeoutError instead of burning a retry."""
    from ray_tpu.train import JaxTrainer, ScalingConfig

    # head (2 CPU) + cap_probe node (1 CPU) are up; want 50 CPU workers.
    elastic = JaxTrainer(
        lambda config: None,
        scaling_config=ScalingConfig(
            num_workers=50, elastic=True, min_workers=1,
            resources_per_worker={"CPU": 1.0}, capacity_wait_s=0.5,
        ),
    )
    elastic._world_size = 50
    assert elastic._renegotiate_capacity(0.5) is True
    assert 1 <= elastic._world_size < 50  # largest feasible, below target

    rigid = JaxTrainer(
        lambda config: None,
        scaling_config=ScalingConfig(
            num_workers=50, resources_per_worker={"CPU": 1.0},
            capacity_wait_s=0.5,
        ),
    )
    rigid._world_size = 50
    assert rigid._renegotiate_capacity(0.5) is False
    err = rigid._capacity_error
    assert isinstance(err, exc.CapacityTimeoutError)
    assert err.needed == 50 and err.feasible >= 1

    floor = JaxTrainer(
        lambda config: None,
        scaling_config=ScalingConfig(
            num_workers=50, elastic=True, min_workers=40,
            resources_per_worker={"CPU": 1.0}, capacity_wait_s=0.5,
        ),
    )
    floor._world_size = 50
    assert floor._renegotiate_capacity(0.5) is False
    assert floor._capacity_error.min_workers == 40


def test_cgraph_elastic_gang_resize(elastic_cluster):
    """(e) a compiled allreduce gang loses a member for good (no
    max_restarts): ElasticGraph re-forms at world N-1, collective edges
    re-bound; grow() folds a replacement back in."""
    from ray_tpu import cgraph
    from ray_tpu.dag import InputNode, MultiOutputNode

    @rt.remote(max_restarts=0, num_cpus=0.1)
    class Member:
        def __init__(self, base):
            self.base = float(base)

        def shard(self, x):
            return np.full(8, float(x) + self.base, dtype=np.float64)

        def first(self, arr):
            return float(arr[0])

    def build(actors):
        with InputNode() as inp:
            shards = [a.shard.bind(inp) for a in actors]
            reduced = cgraph.allreduce.bind(shards)
            return MultiOutputNode(
                [a.first.bind(r) for a, r in zip(actors, reduced)]
            )

    members = [Member.remote(b) for b in (1, 2, 3)]
    rt.get([m.first.remote(np.zeros(1)) for m in members], timeout=60)
    eg = cgraph.ElasticGraph(build, members, min_actors=2, rebuild_timeout=90.0)
    try:
        assert eg.run(0, timeout=30) == [6.0, 6.0, 6.0]
        rt.kill(members[1])
        # the GCS must see it DEAD before resize will drop it
        from ray_tpu.utils import state

        assert _wait_for(
            lambda: any(
                a["state"] == "DEAD"
                and a["actor_id"] == members[1]._actor_id.hex()
                for a in state.list_actors()
            ),
            timeout=30,
        )
        out = eg.run(0, timeout=30)
        assert eg.world_size == 2
        assert out == [4.0, 4.0]  # bases 1+3 at x=0, re-reduced at world 2
        replacement = Member.remote(5)
        rt.get(replacement.first.remote(np.zeros(1)), timeout=60)
        assert eg.grow([replacement]) == 3
        assert eg.run(1, timeout=30) == [12.0, 12.0, 12.0]  # (1+1)+(1+3)+(1+5)
    finally:
        eg.teardown()


# ------------------------------------------------- (d) the acceptance e2e
def _elastic_train_loop(n_steps: int, step_sleep: float = 0.05):
    def loop(config):
        from ray_tpu import train

        ctx = train.get_context()
        world = ctx.get_world_size()
        w = 1.0
        start = 0
        history = []
        ckpt = train.get_checkpoint()
        if ckpt is not None:
            d = ckpt.to_dict()
            start = d["step"] + 1
            w = d["w"]
            history = list(d["history"])
        for step in range(start, n_steps):
            # World-size-dependent deterministic recurrence: the resumed
            # trajectory must match a reference run AT THAT WORLD SIZE.
            w = round(w * 0.9 + 0.1 / world, 12)
            history.append((step, w, world))
            train.report(
                {"loss": w, "step": step, "world": world},
                checkpoint=train.Checkpoint.from_dict(
                    {"step": step, "w": w, "history": history}
                ),
            )
            if train.drain_requested():
                return  # final checkpoint already reported: clean drain
            time.sleep(step_sleep)

    return loop


def _replay_reference(history, n_steps):
    """Replays the recurrence with the RECORDED world sizes — the golden
    trajectory a reference run at each world size would produce."""
    w = 1.0
    for i, (step, value, world) in enumerate(history):
        assert step == i, f"gap/repeat at {i}: {history[i]}"
        w = round(w * 0.9 + 0.1 / world, 12)
        assert value == w, f"step {i} diverged: {value} != {w} at world {world}"
    assert len(history) == n_steps


@pytest.mark.chaos
def test_elastic_preemption_downsize_growback_e2e(elastic_cluster, tmp_path):
    """THE acceptance e2e: a 2-worker gang loses a node to a preemption
    with NO replacement inside the wait budget -> elastic downsize, SAME
    step, world-1-correct loss trajectory, degraded goodput accounted ->
    capacity returns -> grow-back to world 2 at a checkpoint boundary."""
    from ray_tpu.autoscaler_v2 import RAY_RUNNING, InstanceManager, LocalNodeProvider
    from ray_tpu.observability import flight_recorder as frec
    from ray_tpu.train import FailureConfig, JaxTrainer, RunConfig, ScalingConfig

    cluster, runtime = elastic_cluster
    stop = threading.Event()
    pause = threading.Event()
    try:
        provider = LocalNodeProvider(cluster, num_cpus_per_node=2.0)
        mgr = InstanceManager(
            provider,
            gcs=runtime._gcs,
            shape={"cpus": 2.0, "resources": {"train_slot": 1.0}},
        )
        mgr.set_target(2)

        def reconcile_loop():
            while not stop.is_set():
                if not pause.is_set():
                    mgr.reconcile()
                time.sleep(0.05)

        threading.Thread(target=reconcile_loop, daemon=True).start()
        assert _wait_for(
            lambda: mgr.counts().get(RAY_RUNNING, 0) >= 2, timeout=90
        ), "provider nodes never joined"

        n_steps = 150
        trial_dir = tmp_path / "exp" / "elastic_e2e"

        def ckpt_count():
            try:
                import os

                return len(
                    [d for d in os.listdir(trial_dir) if d.startswith("checkpoint_")]
                )
            except OSError:
                return 0

        from ray_tpu.utils import state

        def metric(name, **tags):
            total = 0.0
            for m in state.internal_metrics():
                if m["name"] != name:
                    continue
                if tags and any(m.get("tags", {}).get(k) != v for k, v in tags.items()):
                    continue
                total += m["value"]
            return total

        # Deltas, not absolutes: earlier tests in this module (the
        # renegotiation units) already bumped these counters.
        downsize_before = metric(
            "raytpu_train_elastic_resizes_total", direction="downsize"
        )
        growback_before = metric(
            "raytpu_train_elastic_resizes_total", direction="growback"
        )
        restored_before = metric("raytpu_checkpoints_restored_total")

        def orchestrate():
            # Preempt one gang host once training has visibly progressed;
            # the PAUSED reconciler models "no replacement capacity".
            if not _wait_for(lambda: ckpt_count() >= 2, timeout=90):
                return
            pause.set()
            with provider._lock:
                victims = [
                    cid
                    for cid, rec in provider._instances.items()
                    if rec["status"] == "running"
                ]
            provider.inject_preemption(victims[0], deadline_s=1.5)
            # Once the trainer downsized, "the autoscaler delivers
            # capacity": resume the reconciler, which replaces the lost
            # instance (target is still 2).
            if not _wait_for(
                lambda: metric(
                    "raytpu_train_elastic_resizes_total", direction="downsize"
                )
                > downsize_before,
                timeout=90,
            ):
                return
            pause.clear()

        threading.Thread(target=orchestrate, daemon=True).start()

        run_start_us = time.time_ns() // 1000
        trainer = JaxTrainer(
            _elastic_train_loop(n_steps),
            scaling_config=ScalingConfig(
                num_workers=2,
                elastic=True,
                min_workers=1,
                capacity_wait_s=3.0,
                resources_per_worker={"train_slot": 1.0},
            ),
            run_config=RunConfig(
                name="elastic_e2e",
                storage_path=str(tmp_path / "exp"),
                failure_config=FailureConfig(max_failures=1),
            ),
        )
        result = trainer.fit()
        assert result.error is None, f"run did not recover: {result.error!r}"
        final = result.checkpoint.to_dict()
        assert final["step"] == n_steps - 1

        history = [tuple(h) for h in final["history"]]
        _replay_reference(history, n_steps)
        worlds = [h[2] for h in history]
        assert worlds[0] == 2, "run must start at target world"
        assert 1 in worlds, "downsize to world 1 never happened"
        assert worlds[-1] == 2, "grow-back to world 2 never happened"
        # one contiguous degraded window: 2..2 1..1 2..2
        first_one, last_one = worlds.index(1), len(worlds) - 1 - worlds[::-1].index(1)
        assert set(worlds[first_one : last_one + 1]) == {1}

        # Accounting: degraded seconds on the ledger, goodput < 1, both
        # resize directions counted, world-size gauge back at target.
        assert result.metrics["goodput_seconds"]["degraded"] > 0
        assert result.metrics["goodput"] < 1.0
        assert (
            metric("raytpu_train_elastic_resizes_total", direction="downsize")
            > downsize_before
        )
        assert (
            metric("raytpu_train_elastic_resizes_total", direction="growback")
            > growback_before
        )
        assert metric("raytpu_checkpoints_restored_total") >= restored_before + 2

        # Flight-ring ordering: preempt -> drain -> downsize -> growback.
        # Dump to a private dir: the session default may hold rings from
        # earlier tests whose older events would skew the min-ts ordering.
        from ray_tpu.observability import perfetto

        flight_dir = tmp_path / "flight"
        flight_dir.mkdir()
        frec.RECORDER.dump(
            path=str(flight_dir / "flight_e2e.json"), reason="test: elastic e2e"
        )
        # The driver ring is process-wide: restrict to THIS run's window
        # (earlier tests in the module recorded elastic events too).
        events = [
            e
            for e in perfetto.flight_events(frec.collect(str(flight_dir)))
            if e["ts"] >= run_start_us
        ]
        names = [e["name"] for e in events]
        for expected in (
            "chaos.preempt",
            "train.drain",
            "train.restore",
            "train.elastic_downsize",
            "train.elastic_growback",
        ):
            assert expected in names, f"{expected} missing from {sorted(set(names))}"
        ts = {n: min(e["ts"] for e in events if e["name"] == n) for n in set(names)}
        assert (
            ts["chaos.preempt"]
            <= ts["train.drain"]
            <= ts["train.elastic_downsize"]
            <= ts["train.elastic_growback"]
        )
    finally:
        stop.set()
        pause.clear()
