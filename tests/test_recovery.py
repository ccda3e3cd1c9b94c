"""Ownership, reference counting, task retries, lineage reconstruction.

The round-3 done-criteria for the owner-side task manager (reference:
src/ray/core_worker/reference_count.h:64, task_manager.h:250-256 retries,
:388-402 lineage, object_recovery_manager.h:41):
  (a) pool bytes_in_use returns to baseline after the last ref drops,
  (b) a task on a killed node is retried elsewhere and get() succeeds,
  (c) a 2-deep lineage chain reconstructs a lost intermediate.
"""

import os
import time

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.core.cluster_runtime import Cluster, ClusterRuntime
from ray_tpu.core import runtime_base


@pytest.fixture
def rt_cluster():
    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    yield rt
    rt.shutdown()


@pytest.fixture
def two_node():
    """A 2-node cluster where the second node holds the 'spot' resource."""
    rt.shutdown()
    cluster = Cluster(num_cpus=2)
    runtime = cluster.runtime()
    runtime_base.set_runtime(runtime)
    spot_node = cluster.add_node(num_cpus=2, resources={"spot": 1.0})
    yield cluster, runtime, spot_node
    rt.shutdown()


def _wait_for(pred, timeout=15.0, interval=0.1):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ------------------------------------------------------------------ (a)
def test_refcount_frees_pool_memory(rt_cluster):
    runtime = runtime_base.current_runtime()
    store = runtime._store
    # Settle: let any startup objects flush.
    time.sleep(0.3)
    baseline = store.bytes_in_use()

    ref = rt.put(np.zeros(4 << 20, dtype=np.uint8))  # 4 MiB
    assert store.bytes_in_use() >= baseline + (4 << 20)
    del ref
    assert _wait_for(lambda: store.bytes_in_use() <= baseline + (64 << 10)), (
        f"pool did not return to baseline: {store.bytes_in_use()} vs {baseline}"
    )


def test_refcount_task_outputs_freed(rt_cluster):
    runtime = runtime_base.current_runtime()
    store = runtime._store

    @rt.remote
    def big():
        return np.ones(2 << 20, dtype=np.uint8)

    time.sleep(0.3)
    baseline = store.bytes_in_use()
    refs = [big.remote() for _ in range(4)]
    vals = rt.get(refs)
    assert all(v.nbytes == (2 << 20) for v in vals)
    del vals
    del refs
    assert _wait_for(lambda: store.bytes_in_use() <= baseline + (256 << 10)), (
        f"task outputs not freed: {store.bytes_in_use()} vs baseline {baseline}"
    )


def test_inflight_args_pinned(rt_cluster):
    """Dropping the caller's ref to an argument of an in-flight task must
    not free it (submitted-task pinning)."""

    @rt.remote
    def slow_identity(x):
        time.sleep(0.5)
        return x

    ref = rt.put(np.arange(1024, dtype=np.int32))
    out = slow_identity.remote(ref)
    del ref  # only the in-flight task holds it now
    val = rt.get(out)
    assert val.sum() == np.arange(1024).sum()


def test_borrowed_ref_defers_owner_free(rt_cluster):
    """An actor that stores a borrowed ObjectRef keeps the object alive
    after the owner (driver) drops its last local ref."""

    @rt.remote
    class Holder:
        def __init__(self):
            self.ref = None

        def hold(self, box):
            self.ref = box[0]  # ObjectRef passed by value inside a list

        def read(self):
            return int(rt.get(self.ref).sum())

    h = Holder.remote()
    ref = rt.put(np.ones(1000, dtype=np.int64))
    rt.get(h.hold.remote([ref]))
    time.sleep(0.3)  # let the borrow registration flush
    del ref  # owner drops its last ref; borrow must defer the free
    time.sleep(0.5)
    assert rt.get(h.read.remote(), timeout=10) == 1000


# ------------------------------------------------------------------ (b)
def test_worker_death_retries(rt_cluster, tmp_path):
    marker = str(tmp_path / "attempt")

    @rt.remote
    def flaky():
        if not os.path.exists(marker):
            with open(marker, "w") as f:
                f.write("1")
            os._exit(1)  # simulated worker crash on first attempt
        return 42

    assert rt.get(flaky.remote(), timeout=30) == 42


def test_worker_death_no_retries_raises(rt_cluster):
    @rt.remote(max_retries=0)
    def die():
        os._exit(1)

    from ray_tpu import exceptions as exc

    with pytest.raises(exc.WorkerCrashedError):
        rt.get(die.remote(), timeout=30)


@pytest.mark.parametrize("how,said", [("sigkill", "killed by SIGKILL"), ("exit3", "exit code 3")])
def test_a_dead_actor_says_how_its_process_ended(rt_cluster, how, said):
    """The error the caller sees names the signal or the exit code (the
    zygote that reaped the worker knows it, the raylet asks), with the last
    lines the process wrote, and keeps both across the object plane's pickle
    (PERF.md section 7 (0): a replica died as "Actor ... died: actor died")."""
    import pickle
    import signal

    from ray_tpu import exceptions as exc

    @rt.remote
    class Victim:
        def pid(self):
            return os.getpid()

        def leave(self, code):
            print("leaving on purpose", flush=True)
            os._exit(code)

    a = Victim.remote()
    pid = rt.get(a.pid.remote(), timeout=30)
    if how == "sigkill":
        os.kill(pid, signal.SIGKILL)
    else:
        a.leave.remote(3)
    seen = []

    def died():
        try:
            rt.get(a.pid.remote(), timeout=5)
        except exc.ActorDiedError as e:
            seen.append(e)
            return True
        except Exception:  # what was in flight when the process went: the raylet's RuntimeError
            return False
        return False

    assert _wait_for(died, timeout=30)
    err = seen[-1]
    assert said in str(err) and said in err.reason, str(err)
    if how == "exit3":
        assert "leaving on purpose" in err.reason
    again = pickle.loads(pickle.dumps(err))
    assert str(again) == str(err) and again.reason == err.reason and again.actor_id_hex == err.actor_id_hex


def test_node_death_task_retried_elsewhere(two_node, tmp_path):
    cluster, runtime, spot_node = two_node
    marker = str(tmp_path / "slow_marker")

    @rt.remote(resources={"spot": 1.0})
    def compute(path):
        # Slow only on the first execution so the test can kill the node
        # mid-flight; the retry (on the replacement node) is fast.
        if not os.path.exists(path):
            with open(path, "w") as f:
                f.write("1")
            time.sleep(8.0)
        return "done"

    ref = compute.remote(marker)
    assert _wait_for(lambda: os.path.exists(marker), timeout=10)
    cluster.remove_node(spot_node)  # kill mid-task
    cluster.add_node(num_cpus=2, resources={"spot": 1.0})
    assert rt.get(ref, timeout=40) == "done"


# ------------------------------------------------------------------ (c)
def test_lineage_reconstruction_two_deep(two_node):
    cluster, runtime, spot_node = two_node

    @rt.remote(resources={"spot": 0.4})
    def produce():
        return np.full(1000, 7, dtype=np.int64)

    @rt.remote(resources={"spot": 0.4})
    def transform(x):
        return x * 2

    a = produce.remote()
    b = transform.remote(a)
    # Let both finish on the spot node WITHOUT pulling results to the head
    # node, then kill it: both objects are lost and must be reconstructed
    # from lineage.
    ready, _ = rt.wait([b], num_returns=1, timeout=20)
    assert ready
    cluster.remove_node(spot_node)
    cluster.add_node(num_cpus=2, resources={"spot": 1.0})
    val = rt.get(b, timeout=60)
    assert val.sum() == 7 * 2 * 1000


def test_eager_free_non_escaped_put(rt_cluster):
    """An object whose ref never left the process is freed from the pool
    synchronously on last-ref drop (no GCS grace roundtrip) — the basis of
    the hot put/del allocator reuse path."""
    import numpy as np

    from ray_tpu.core.runtime_base import current_runtime

    rt = rt_cluster
    store = current_runtime()._store
    baseline = store.bytes_in_use()
    ref = rt.put(np.zeros(8 << 20, dtype=np.uint8))
    assert store.bytes_in_use() >= baseline + (8 << 20)
    del ref
    # No waiting: the delete happened in remove_local_ref itself.
    assert store.bytes_in_use() <= baseline + (64 << 10)


def test_escaped_put_ref_not_eagerly_freed(rt_cluster):
    """A ref that was shipped to a task keeps its object alive through the
    GCS borrow-grace path; the value stays fetchable mid-flight."""
    import numpy as np

    rt = rt_cluster

    @rt.remote
    def consume(x):
        import time as _t

        _t.sleep(0.5)
        return float(x.sum())

    arr = np.ones(1 << 20, dtype=np.float32)
    ref = rt.put(arr)
    out_ref = consume.remote(ref)
    del ref  # the task (maybe not yet started) still needs the object
    assert rt.get(out_ref, timeout=60) == float(1 << 20)


def test_actor_pool(rt_cluster):
    from ray_tpu.utils import ActorPool

    rt = rt_cluster

    @rt.remote
    class Doubler:
        def work(self, x):
            return 2 * x

    pool = ActorPool([Doubler.remote() for _ in range(2)])
    assert list(pool.map(lambda a, v: a.work.remote(v), range(6))) == [0, 2, 4, 6, 8, 10]
    assert sorted(pool.map_unordered(lambda a, v: a.work.remote(v), range(4))) == [0, 2, 4, 6]
    # submit/get_next interleave
    pool.submit(lambda a, v: a.work.remote(v), 21)
    assert pool.get_next(timeout=60) == 42
    assert not pool.has_next()


def test_distributed_queue(rt_cluster):
    from ray_tpu.utils import Empty, Queue

    rt = rt_cluster
    q = Queue(maxsize=4)

    @rt.remote
    def producer(q, n):
        for i in range(n):
            q.put(i)
        return True

    @rt.remote
    def consumer(q, n):
        return [q.get(timeout=30) for _ in range(n)]

    p = producer.remote(q, 8)
    got = rt.get(consumer.remote(q, 8), timeout=60)
    assert got == list(range(8))
    assert rt.get(p, timeout=30) is True
    assert q.empty()
    import pytest as _pytest

    with _pytest.raises(Empty):
        q.get_nowait()
    q.put_nowait(99)
    assert q.qsize() == 1 and q.get_nowait() == 99
    q.shutdown()
