"""Lazy DAG API (reference: python/ray/dag — bind/execute/MultiOutputNode,
compiled plan reuse)."""

import pytest

import ray_tpu as rt
from ray_tpu.dag import InputNode, MultiOutputNode


# Module-scoped: one cluster serves every test (each creates its own
# actors/graphs; compiled graphs tear down per test).
@pytest.fixture(scope="module")
def rt_cluster():
    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    yield rt
    rt.shutdown()


def test_function_dag_chain(rt_cluster):
    @rt.remote
    def double(x):
        return x * 2

    @rt.remote
    def add(a, b):
        return a + b

    with InputNode() as inp:
        dag = add.bind(double.bind(inp), double.bind(inp))
    ref = dag.execute(5)
    assert rt.get(ref, timeout=60) == 20


def test_actor_dag_and_compile_reuse(rt_cluster):
    @rt.remote
    class Counter:
        def __init__(self):
            self.total = 0

        def add(self, x):
            self.total += x
            return self.total

    a = Counter.remote()
    with InputNode() as inp:
        dag = a.add.bind(inp)
    compiled = dag.compile()
    assert rt.get(compiled.execute(3), timeout=60) == 3
    assert rt.get(compiled.execute(4), timeout=60) == 7  # same actor state


def test_multi_output(rt_cluster):
    @rt.remote
    def inc(x):
        return x + 1

    @rt.remote
    def dec(x):
        return x - 1

    with InputNode() as inp:
        dag = MultiOutputNode([inc.bind(inp), dec.bind(inp)])
    refs = dag.execute(10)
    assert rt.get(refs, timeout=60) == [11, 9]


def test_intermediate_values_stay_in_object_plane(rt_cluster):
    """Upstream results reach downstream tasks as ObjectRefs — the driver
    never materializes intermediate values."""
    import numpy as np

    @rt.remote
    def big():
        return np.ones(1 << 20, dtype=np.float32)

    @rt.remote
    def total(arr):
        return float(arr.sum())

    dag = total.bind(big.bind())
    assert rt.get(dag.execute(), timeout=60) == float(1 << 20)


def test_channel_compiled_dag_pipeline(rt_cluster):
    """3-stage actor pipeline over preallocated channels: steady-state
    execute() submits ZERO tasks (reference: compiled_dag_node.py:664 —
    the aDAG contract) and beats the per-submit compiled plan on
    throughput."""
    import time as _time

    from ray_tpu.core import runtime_base

    @rt.remote
    class Stage:
        def __init__(self, k):
            self.k = k

        def apply(self, x):
            return x + self.k

    s1, s2, s3 = Stage.remote(1), Stage.remote(10), Stage.remote(100)
    with InputNode() as inp:
        dag = s3.apply.bind(s2.apply.bind(s1.apply.bind(inp)))

    cdag = dag.experimental_compile()
    try:
        # Correctness + statefulness across executions.
        assert rt.get(cdag.execute(0)) == 111
        assert cdag.execute(5).get(timeout=30) == 116

        # Zero task submission in steady state: count submits at the
        # runtime boundary while executing.
        runtime = runtime_base.current_runtime()
        counted = {"n": 0}
        orig_submit, orig_actor = runtime.submit_task, runtime.submit_actor_task

        def count_submit(spec):
            counted["n"] += 1
            return orig_submit(spec)

        def count_actor(spec):
            counted["n"] += 1
            return orig_actor(spec)

        runtime.submit_task = count_submit
        runtime.submit_actor_task = count_actor
        try:
            n = 100
            t0 = _time.monotonic()
            refs = [cdag.execute(i) for i in range(n)]
            outs = [r.get(timeout=60) for r in refs]
            chan_dt = _time.monotonic() - t0
        finally:
            runtime.submit_task = orig_submit
            runtime.submit_actor_task = orig_actor
        assert outs == [111 + i for i in range(n)]
        assert counted["n"] == 0, f"expected zero submissions, saw {counted['n']}"

        # Throughput comparison is advisory here (the shared 1-core box
        # makes hard wall-clock ratios flaky). The zero-submission assert
        # above IS the contract.
        legacy = dag.compile()
        t0 = _time.monotonic()
        legacy_refs = [legacy.execute(i) for i in range(n)]
        rt.get(legacy_refs, timeout=120)
        legacy_dt = _time.monotonic() - t0
        print(f"channel DAG {n / chan_dt:.0f}/s vs legacy {n / legacy_dt:.0f}/s")
        assert chan_dt < legacy_dt, (
            f"channel DAG {chan_dt:.3f}s slower than per-submit {legacy_dt:.3f}s"
        )
    finally:
        cdag.teardown()


def test_channel_dag_multi_output_and_errors(rt_cluster):
    @rt.remote
    class Worker:
        def ok(self, x):
            return x * 2

        def boom(self, x):
            if x == 3:
                raise ValueError("x was three")
            return x

    a, b = Worker.remote(), Worker.remote()
    with InputNode() as inp:
        dag = MultiOutputNode([a.ok.bind(inp), b.boom.bind(inp)])
    cdag = dag.experimental_compile()
    try:
        assert rt.get(cdag.execute(2)) == [4, 2]
        with pytest.raises(ValueError, match="x was three"):
            rt.get(cdag.execute(3))
        # The pipeline survives the error: next execution works.
        assert rt.get(cdag.execute(4)) == [8, 4]
    finally:
        cdag.teardown()
