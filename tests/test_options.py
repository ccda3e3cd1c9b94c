"""Honest scheduling options on the CLUSTER path: max_concurrency
(threaded + async actors), cancel(), runtime_env (env_vars/working_dir).
max_retries is covered by tests/test_recovery.py.

Reference: actor_scheduling_queue.h / concurrency_group_manager.h /
fiber.h (concurrency), core_worker CancelTask, runtime_env agent."""

import os
import time

import pytest

import ray_tpu as rt
from ray_tpu import exceptions as exc


# Module-scoped: one cluster boot for the whole file (assertions here
# are cumulative-tolerant: >= counts and any() lookups).
@pytest.fixture(scope="module")
def rt_cluster():
    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    yield rt
    rt.shutdown()


def test_threaded_actor_max_concurrency(rt_cluster):
    @rt.remote(max_concurrency=4)
    class Sleeper:
        def nap(self, t):
            time.sleep(t)
            return os.getpid()

    a = Sleeper.remote()
    rt.get(a.nap.remote(0.01), timeout=60)  # wait out worker spawn/imports
    t0 = time.monotonic()
    refs = [a.nap.remote(0.5) for _ in range(4)]
    pids = rt.get(refs, timeout=30)
    elapsed = time.monotonic() - t0
    # Serial execution would take >= 2s; concurrent should be ~0.5s.
    assert elapsed < 1.5, f"naps did not overlap: {elapsed:.2f}s"
    assert len(set(pids)) == 1  # all in the one actor process


def test_async_actor_concurrency(rt_cluster):
    @rt.remote(max_concurrency=8)
    class AsyncActor:
        async def nap(self, t):
            import asyncio

            await asyncio.sleep(t)
            return "done"

    a = AsyncActor.remote()
    rt.get(a.nap.remote(0.01), timeout=60)  # wait out worker spawn/imports
    t0 = time.monotonic()
    out = rt.get([a.nap.remote(0.5) for _ in range(8)], timeout=30)
    elapsed = time.monotonic() - t0
    assert out == ["done"] * 8
    assert elapsed < 2.0, f"async naps did not overlap: {elapsed:.2f}s"


def test_cancel_running_task(rt_cluster):
    @rt.remote
    def warm():
        return 1

    rt.get(warm.remote(), timeout=60)  # worker pool up

    @rt.remote
    def sleeper():
        time.sleep(60)
        return "never"

    ref = sleeper.remote()
    time.sleep(1.0)  # let it dispatch
    rt.cancel(ref)
    with pytest.raises(exc.TaskCancelledError):
        rt.get(ref, timeout=15)


def test_cancel_queued_task(rt_cluster, tmp_path):
    marker = str(tmp_path / "hog_started")

    @rt.remote(num_cpus=4)
    def hog(path):
        with open(path, "w") as f:
            f.write("1")
        time.sleep(3)
        return "hogged"

    @rt.remote(num_cpus=4)
    def queued():
        return "ran"

    h = hog.remote(marker)
    # The premise is "q sits queued BEHIND the hog": prove the hog is
    # actually executing (CPUs held) before submitting q — dispatch
    # ordering between two same-demand submissions is not guaranteed,
    # and a q that sneaks in first finishes before the cancel lands
    # (the old ~15% module-context flake).
    deadline = time.monotonic() + 20
    while not os.path.exists(marker):
        assert time.monotonic() < deadline, "hog never started"
        time.sleep(0.05)
    q = queued.remote()  # cannot start while hog holds all CPUs
    time.sleep(0.3)
    rt.cancel(q)
    with pytest.raises(exc.TaskCancelledError):
        rt.get(q, timeout=15)
    assert rt.get(h, timeout=30) == "hogged"


def test_cancel_force_kills_worker(rt_cluster):
    @rt.remote
    def warm():
        return 1

    rt.get(warm.remote(), timeout=60)

    @rt.remote
    def stubborn():
        while True:  # ignores SIGINT-based cancellation paths
            try:
                time.sleep(60)
            except KeyboardInterrupt:
                continue

    ref = stubborn.remote()
    time.sleep(1.0)
    rt.cancel(ref, force=True)
    with pytest.raises((exc.TaskCancelledError, exc.WorkerCrashedError)):
        rt.get(ref, timeout=20)


def test_runtime_env_env_vars(rt_cluster):
    @rt.remote(runtime_env={"env_vars": {"MY_FLAG": "hello"}})
    def read_env():
        return os.environ.get("MY_FLAG")

    @rt.remote
    def read_plain():
        return os.environ.get("MY_FLAG")

    assert rt.get(read_env.remote(), timeout=30) == "hello"
    assert rt.get(read_plain.remote(), timeout=30) is None


def test_runtime_env_working_dir(rt_cluster, tmp_path):
    mod = tmp_path / "wd_module.py"
    mod.write_text("VALUE = 'from-working-dir'\n")

    @rt.remote(runtime_env={"working_dir": str(tmp_path)})
    def use_module():
        import wd_module

        return wd_module.VALUE, os.getcwd()

    value, cwd = rt.get(use_module.remote(), timeout=30)
    assert value == "from-working-dir"
    # working_dir ships as a content-addressed package and extracts into
    # the node cache — the worker runs in the EXTRACTED copy, not the
    # driver's original path (reference: working_dir URIs, packaging.py).
    assert cwd != str(tmp_path)
    assert os.path.exists(os.path.join(cwd, "wd_module.py"))


def test_runtime_env_actor(rt_cluster):
    @rt.remote(runtime_env={"env_vars": {"ACTOR_ENV": "yes"}})
    class EnvActor:
        def read(self):
            return os.environ.get("ACTOR_ENV")

    a = EnvActor.remote()
    assert rt.get(a.read.remote(), timeout=30) == "yes"


def test_gc_inside_the_ref_lock_does_not_deadlock(rt_cluster):
    """Any allocation under the owner's ref-count lock can start a cyclic GC
    pass on that thread; a collected ObjectRef's __del__ takes the lock again
    (seen: _record_submission -> GC -> remove_local_ref hung this file)."""
    import gc
    import threading

    from ray_tpu.core import runtime_base

    runtime = runtime_base.current_runtime()
    done = threading.Event()

    def collect_under_the_lock():
        cycle = [rt.put("x")]
        cycle.append(cycle)  # only the cyclic collector can free the ref
        del cycle
        with runtime._ref_lock:
            gc.collect()
        done.set()

    threading.Thread(target=collect_under_the_lock, daemon=True).start()
    assert done.wait(timeout=20), "ObjectRef.__del__ deadlocked on _ref_lock during GC"


def test_runtime_env_unsupported_field_raises(rt_cluster):
    """Keys with no registered plugin fail loudly at submission (conda and
    image_uri ARE supported since the plugin ABC landed)."""

    @rt.remote(runtime_env={"no_such_plugin": 1})
    def f():
        return 1

    with pytest.raises(ValueError, match="no plugin"):
        f.remote()


class TestConcurrencyGroups:
    """Named per-method concurrency groups (reference:
    src/ray/core_worker/transport/concurrency_group_manager.h:34)."""

    def _run(self, rt_mod):
        import time as _time

        @rt_mod.remote(max_concurrency=1, concurrency_groups={"io": 3, "compute": 1})
        class Mixed:
            def __init__(self):
                self.log = []

            @rt_mod.method(concurrency_group="io")
            def fetch(self, i):
                self.log.append(("start", i, _time.monotonic()))
                _time.sleep(0.5)
                self.log.append(("end", i, _time.monotonic()))
                return i

            @rt_mod.method(concurrency_group="compute")
            def crunch(self, i):
                _time.sleep(0.3)
                return i

            def events(self):
                return list(self.log)

        a = Mixed.remote()
        rt_mod.get(a.events.remote(), timeout=60)  # wait out worker spawn
        t0 = _time.monotonic()
        # Three io calls with width 3 overlap: wall ~0.5s, not 1.5s.
        out = rt_mod.get([a.fetch.remote(i) for i in range(3)], timeout=60)
        io_wall = _time.monotonic() - t0
        assert sorted(out) == [0, 1, 2]
        assert io_wall < 1.2, f"io group did not run concurrently: {io_wall:.2f}s"
        # compute group width 1: two calls serialize (~0.6s+).
        t0 = _time.monotonic()
        rt_mod.get([a.crunch.remote(i) for i in range(2)], timeout=60)
        compute_wall = _time.monotonic() - t0
        assert compute_wall >= 0.55, f"compute group overlapped: {compute_wall:.2f}s"

    # cluster mode FIRST: rt_local boots a local-mode runtime, which
    # shuts down the module-scoped cluster fixture — nothing may use
    # rt_cluster after a local-mode test in this file.
    def test_cluster_mode(self, rt_cluster):
        self._run(rt_cluster)

    def test_local_mode(self, rt_local):
        self._run(rt_local)
