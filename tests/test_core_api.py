"""Core API tests: tasks, objects, actors in local mode.

Modeled on the reference's core smoke tests
(reference: python/ray/tests/test_basic.py, test_actor.py).
"""

import time

import numpy as np
import pytest

import ray_tpu as rt
from ray_tpu.exceptions import ActorDiedError, GetTimeoutError, TaskError


def test_put_get(rt_local):
    ref = rt.put(42)
    assert rt.get(ref) == 42
    arr = np.arange(100000, dtype=np.float32)
    ref2 = rt.put(arr)
    np.testing.assert_array_equal(rt.get(ref2), arr)


def test_simple_task(rt_local):
    @rt.remote
    def add(a, b):
        return a + b

    assert rt.get(add.remote(1, 2)) == 3


def test_task_with_options(rt_local):
    @rt.remote(num_cpus=2)
    def f():
        return "ok"

    assert rt.get(f.options(num_cpus=1).remote()) == "ok"


def test_task_dependencies(rt_local):
    @rt.remote
    def inc(x):
        return x + 1

    ref = inc.remote(0)
    for _ in range(10):
        ref = inc.remote(ref)
    assert rt.get(ref) == 11


def test_object_ref_args_mixed(rt_local):
    @rt.remote
    def combine(a, b, c=0):
        return a + b + c

    assert rt.get(combine.remote(rt.put(1), 2, c=rt.put(3))) == 6


def test_multiple_returns(rt_local):
    @rt.remote(num_returns=3)
    def three():
        return 1, 2, 3

    a, b, c = three.remote()
    assert rt.get([a, b, c]) == [1, 2, 3]


def test_task_error_propagates(rt_local):
    @rt.remote
    def boom():
        raise ValueError("kapow")

    with pytest.raises(TaskError, match="kapow"):
        rt.get(boom.remote())

    @rt.remote
    def dependent(x):
        return x

    # Errors flow through dependencies, like the reference's RayTaskError.
    with pytest.raises(TaskError, match="kapow"):
        rt.get(dependent.remote(boom.remote()))


def test_get_timeout(rt_local):
    @rt.remote
    def slow():
        time.sleep(5)
        return 1

    with pytest.raises(GetTimeoutError):
        rt.get(slow.remote(), timeout=0.1)


def test_wait(rt_local):
    @rt.remote
    def sleepy(t):
        time.sleep(t)
        return t

    fast = sleepy.remote(0.01)
    slow = sleepy.remote(5.0)
    ready, pending = rt.wait([fast, slow], num_returns=1, timeout=2.0)
    assert ready == [fast] and pending == [slow]


def test_actor_basic(rt_local):
    @rt.remote
    class Counter:
        def __init__(self, start=0):
            self.n = start

        def inc(self, k=1):
            self.n += k
            return self.n

        def value(self):
            return self.n

    c = Counter.remote(10)
    refs = [c.inc.remote() for _ in range(5)]
    assert rt.get(refs) == [11, 12, 13, 14, 15]  # FIFO ordering
    assert rt.get(c.value.remote()) == 15


def test_actor_error_and_death(rt_local):
    @rt.remote
    class A:
        def ok(self):
            return 1

        def fail(self):
            raise RuntimeError("nope")

    a = A.remote()
    with pytest.raises(TaskError, match="nope"):
        rt.get(a.fail.remote())
    assert rt.get(a.ok.remote()) == 1  # survives method errors

    rt.kill(a)
    with pytest.raises(ActorDiedError):
        rt.get(a.ok.remote())


def test_named_actor(rt_local):
    @rt.remote
    class Registry:
        def ping(self):
            return "pong"

    Registry.options(name="reg").remote()
    h = rt.get_actor("reg")
    assert rt.get(h.ping.remote()) == "pong"
    with pytest.raises(ValueError):
        rt.get_actor("missing")


def test_actor_handle_passing(rt_local):
    @rt.remote
    class Store:
        def __init__(self):
            self.v = {}

        def set(self, k, v):
            self.v[k] = v
            return True

        def get(self, k):
            return self.v.get(k)

    @rt.remote
    def writer(store, k, v):
        return rt.get(store.set.remote(k, v))

    s = Store.remote()
    assert rt.get(writer.remote(s, "x", 99))
    assert rt.get(s.get.remote("x")) == 99


def test_nested_tasks(rt_local):
    @rt.remote
    def leaf(x):
        return x * 2

    @rt.remote
    def parent(x):
        return rt.get(leaf.remote(x)) + 1

    assert rt.get(parent.remote(10)) == 21


def test_cluster_resources(rt_local):
    res = rt.cluster_resources()
    assert res["CPU"] == 8


def test_reinit_guard(rt_local):
    with pytest.raises(RuntimeError):
        rt.init(local_mode=True)
    rt.init(local_mode=True, ignore_reinit_error=True)


def test_actor_max_concurrency(rt_local):
    @rt.remote(max_concurrency=4)
    class Par:
        def slow(self):
            time.sleep(0.2)
            return 1

    p = Par.remote()
    t0 = time.monotonic()
    rt.get([p.slow.remote() for _ in range(4)])
    assert time.monotonic() - t0 < 0.7  # ran concurrently


class TestStreamingReturns:
    """num_returns="streaming" generator tasks (reference:
    python/ray/_raylet.pyx:281 ObjectRefGenerator)."""

    def test_task_stream(self, rt_cluster):
        rt = rt_cluster

        @rt.remote(num_returns="streaming")
        def gen(n):
            for i in range(n):
                yield i * 10

        assert [rt.get(r) for r in gen.remote(5)] == [0, 10, 20, 30, 40]

    def test_empty_stream(self, rt_cluster):
        rt = rt_cluster

        @rt.remote(num_returns="streaming")
        def empty():
            return
            yield  # pragma: no cover

        assert list(empty.remote()) == []

    def test_mid_stream_error_surfaces_at_index(self, rt_cluster):
        import pytest as _pytest

        rt = rt_cluster

        @rt.remote(num_returns="streaming")
        def bad():
            yield 1
            raise ValueError("boom")

        it = iter(bad.remote())
        assert rt.get(next(it)) == 1
        with _pytest.raises(Exception, match="boom"):
            rt.get(next(it))

    def test_actor_stream(self, rt_cluster):
        rt = rt_cluster

        @rt.remote
        class A:
            def stream(self, n):
                for i in range(n):
                    yield i + 100

        a = A.remote()
        g = a.stream.options(num_returns="streaming").remote(3)
        assert [rt.get(r) for r in g] == [100, 101, 102]

    def test_stream_is_incremental(self, rt_cluster):
        import time as _time

        rt = rt_cluster

        @rt.remote(num_returns="streaming")
        def slow():
            for i in range(3):
                _time.sleep(0.4)
                yield i

        t0 = _time.monotonic()
        it = iter(slow.remote())
        rt.get(next(it))
        t_first = _time.monotonic() - t0
        list(it)
        t_all = _time.monotonic() - t0
        assert t_first < t_all - 0.3, (t_first, t_all)

    def test_large_items_via_store(self, rt_cluster):
        import numpy as np

        rt = rt_cluster

        @rt.remote(num_returns="streaming")
        def big(n):
            for i in range(n):
                yield np.full(300_000, i, dtype=np.float64)  # > inline cap

        vals = [rt.get(r) for r in big.remote(3)]
        assert [float(v[0]) for v in vals] == [0.0, 1.0, 2.0]


# ---- how a blocked stream consumer learns that its item has landed ----
@pytest.fixture
def stream_sources(rt_cluster):
    rt = rt_cluster

    def _stamped(n, big):
        """Yields n items 30 ms apart, each carrying time.monotonic() at its
        yield (one clock for every process of a host). Defined here so that
        it travels to the workers by value."""
        for _ in range(n):
            time.sleep(0.03)
            t = time.monotonic()
            yield np.full(300_000, t) if big else t  # 2.4 MB: above the inline cap

    @rt.remote(num_returns="streaming")
    def gen(n, big):
        yield from _stamped(n, big)

    @rt.remote
    class Source:
        def __init__(self):
            self.t_end = None

        def stream(self, n, big):
            yield from _stamped(n, big)
            self.t_end = time.monotonic()

        def stall(self, seconds):
            yield 0
            time.sleep(seconds)
            yield 1

        def ended_at(self):
            return self.t_end

    a = Source.remote()
    rt.get(a.ended_at.remote())  # the handle's direct channel is up
    actor_stream = a.stream.options(num_returns="streaming").remote
    return {"rt": rt, "task": gen.remote, "actor": actor_stream, "source": a}


def _delivery_delays_ms(rt, start, big):
    delays = []
    for ref in start(8, big):
        v = rt.get(ref)
        delays.append((time.monotonic() - (float(v[0]) if big else v)) * 1e3)
    assert len(delays) == 8
    return delays


class TestStreamWake:
    """An item is in the consumer's hands when it lands, not at the next
    tick of a timer (the parent of PR 25 gave ~100 ms median, ~200 ms max)."""

    @pytest.mark.parametrize("big", [False, True], ids=["inline", "above_inline_cap"])
    @pytest.mark.parametrize("kind", ["task", "actor"])
    def test_items_reach_a_waiting_consumer_at_once(self, stream_sources, kind, big):
        rt, start = stream_sources["rt"], stream_sources[kind]
        _delivery_delays_ms(rt, start, big)  # worker, lease and imports warm
        # The bound is on the mechanism, not on this machine's load: the
        # best of three attempts has to meet it.
        attempts = [_delivery_delays_ms(rt, start, big) for _ in range(3)]
        best = min(attempts, key=lambda d: (float(np.median(d)), max(d)))
        assert float(np.median(best)) < 25.0 and max(best) < 150.0, attempts

    def test_end_of_stream_is_seen_when_the_generator_returns(self, stream_sources):
        rt, a = stream_sources["rt"], stream_sources["source"]
        lags = []
        for _ in range(3):
            for ref in stream_sources["actor"](3, False):
                rt.get(ref)
            seen = time.monotonic()  # StopIteration: the header's ack woke the call
            lags.append((seen - rt.get(a.ended_at.remote())) * 1e3)
        assert min(lags) < 50.0, lags

    def test_stream_next_timeout_on_a_stalled_producer(self, stream_sources, stream_next_counts):
        from ray_tpu.core.runtime_base import current_runtime

        rt, a = stream_sources["rt"], stream_sources["source"]
        gen = a.stall.options(num_returns="streaming").remote(3.0)
        assert rt.get(next(gen)) == 0
        before = stream_next_counts()
        t0 = time.monotonic()
        with pytest.raises(GetTimeoutError):
            current_runtime().stream_next(gen._task_id, 1, timeout=0.3)
        assert 0.3 <= time.monotonic() - t0 < 0.6
        # The caller's deadline is not the alarm for a lost wake-up.
        ended = {k: v - before[k] for k, v in stream_next_counts().items() if v - before[k]}
        assert ended == {"timeout": 1}

    def test_two_consumers_of_one_stream_share_its_header(self, stream_sources):
        """Nothing serialises stream_next on one task: two blocked calls
        wait on the same header id, each is woken by its own item, and one
        leaving does not unregister the other."""
        import threading

        from ray_tpu.core.runtime_base import current_runtime

        rt, a, runtime = stream_sources["rt"], stream_sources["source"], current_runtime()
        gen = a.stream.options(num_returns="streaming").remote(3, False)
        got = {}

        def take(index):
            try:
                t0 = time.monotonic()
                oid = runtime.stream_next(gen._task_id, index, timeout=5.0)
                got[index] = (oid, time.monotonic() - t0)
            except BaseException as e:  # noqa: BLE001
                got[index] = (e, None)

        threads = [threading.Thread(target=take, args=(i,), daemon=True) for i in (0, 1, 2, 3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=10.0)
        assert not any(t.is_alive() for t in threads)
        assert all(got[i][0] is not None and not isinstance(got[i][0], BaseException)
                   for i in (0, 1, 2)), got
        assert got[3][0] is None  # index 3 is the end of a 3-item stream
        # Items are 30 ms apart: nobody waited for a 2 s net.
        assert max(waited for _, waited in got.values()) < 1.0, got
        assert not runtime._ack_waiters

    def test_a_dropped_wake_up_is_caught_by_the_net(self, stream_sources, stream_next_counts):
        """The ack of item 1 fills the memory store but wakes nobody: the
        call's own period of silence ends the wait."""
        from ray_tpu.core.ids import ObjectID
        from ray_tpu.core.runtime_base import current_runtime

        rt, runtime = stream_sources["rt"], current_runtime()
        real_sealed, real_waiters = runtime._fast_sealed, runtime._ack_waiters
        dropped = []

        def sealed_dropping_one_notify(sealed, inline=None):
            # Return index 2 is item 1 (index 0 is the header).
            item_1 = any(ObjectID.from_hex(h).return_index() == 2 for h in (inline or ()))
            if item_1 and not dropped:
                dropped.append(True)
                runtime._ack_waiters = {}  # this ack finds nobody to wake
                try:
                    return real_sealed(sealed, inline)
                finally:
                    runtime._ack_waiters = real_waiters
            return real_sealed(sealed, inline)

        runtime._fast_sealed = sealed_dropping_one_notify
        try:
            # A new actor: its direct connection binds the patched method.
            @rt.remote
            class Fresh:
                def stream(self):
                    yield 0
                    time.sleep(0.3)  # the consumer is waiting by now
                    yield 1  # its wake-up is dropped ...
                    time.sleep(3.0)  # ... and nothing else wakes the consumer
                    yield 2

            before = stream_next_counts()
            gen = Fresh.remote().stream.options(num_returns="streaming").remote()
            assert rt.get(next(gen)) == 0
            t0 = time.monotonic()
            assert rt.get(next(gen)) == 1
            waited = time.monotonic() - t0
            assert [rt.get(r) for r in gen] == [2]
        finally:
            del runtime._fast_sealed
        # One period of silence (2 s): not the next ack (3 s), not a hang.
        assert dropped and 1.0 < waited < 2.9, waited
        after = stream_next_counts()
        assert after["poll"] - before["poll"] == 1

    def test_killed_producer_ends_the_stream_with_an_error(self, stream_sources):
        import threading

        rt, a = stream_sources["rt"], stream_sources["source"]
        gen = a.stall.options(num_returns="streaming").remote(30.0)
        assert rt.get(next(gen)) == 0
        outcome = []

        def consume():
            try:
                outcome.append(rt.get(next(gen)))
            except BaseException as e:  # noqa: BLE001
                outcome.append(e)

        t = threading.Thread(target=consume, daemon=True)
        t.start()
        time.sleep(0.2)  # the consumer is blocked in stream_next
        rt.kill(a)
        t.join(timeout=10.0)
        assert not t.is_alive(), "stream_next hung after its producer was killed"
        assert isinstance(outcome[0], Exception), outcome


# ---- a stream whose items are sealed on ANOTHER node's store ----
@pytest.fixture(scope="module")
def far_stream():
    """Two nodes; the streaming actor is pinned to the one the driver is
    not on, and its items (200 KB, above the inline cap) are sealed in that
    node's store. The ack says so, and only the raylet can pull them in."""
    from ray_tpu.core import runtime_base
    from ray_tpu.core.cluster_runtime import Cluster

    rt.shutdown()
    cluster = Cluster(num_cpus=2, num_workers=2)
    cluster.add_node(num_cpus=2, resources={"far": 1.0})
    runtime = cluster.runtime()
    runtime_base.set_runtime(runtime)

    @rt.remote(resources={"far": 0.1})
    class Far:
        def stream(self, n, gap):
            for i in range(n):
                time.sleep(gap)
                yield np.full(25_000, float(i))

        def ready(self):
            return True

    a = Far.remote()
    rt.get(a.ready.remote())  # the handle's direct channel is up
    yield runtime, a.stream.options(num_returns="streaming").remote
    rt.shutdown()


@pytest.mark.parametrize(
    "head_start, gap",
    [(1.0, 0.0), (0.0, 0.1)],
    ids=["consumer_starts_after_the_producer_finished", "consumer_keeps_pace"],
)
def test_stream_items_sealed_on_another_node_are_pulled(far_stream, stream_next_counts,
                                                       head_start, gap):
    runtime, start = far_stream
    before = stream_next_counts()
    gen = start(3, gap)
    time.sleep(head_start)  # the header has landed: the whole stream is "remote"
    t0 = time.monotonic()
    oids = []
    while True:
        oid = runtime.stream_next(gen._task_id, len(oids), timeout=8.0)
        if oid is None:
            break
        oids.append(oid)
    waited = time.monotonic() - t0
    assert [float(v[0]) for v in runtime.get(oids)] == [0.0, 1.0, 2.0]
    # A pull is an RPC and a copy, not a period of silence (2 s) an item.
    assert waited < 3 * gap + 2.0, waited
    ended = {k: v - before[k] for k, v in stream_next_counts().items() if v - before[k]}
    assert ended.get("raylet", 0) >= 3 and not ended.get("poll") and not ended.get("timeout"), ended
