"""The teardown contract (core/proctree.py): when `rt.shutdown()` or
`Cluster.shutdown()` returns, no process the session started is alive:
GCS, raylet, zygote, parked pre-forks, idle and busy workers, cold-spawned
workers. It holds when the raylet is stalled for longer than any wait of
the old teardown (2 s `stop` + 3 s `wait`), or is already dead."""

import os
import signal
import threading
import time

import pytest

import ray_tpu as rt
from ray_tpu.core import proctree, runtime_base


@pytest.fixture(autouse=True)
def time_limit():
    def over(signum, frame):
        raise TimeoutError("a teardown test ran over its 90 s limit")

    old = signal.signal(signal.SIGALRM, over)
    signal.alarm(90)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)
        rt.shutdown()


def _cmdline(proc) -> str:
    try:
        with open(f"/proc/{proc.pid}/cmdline", "rb") as f:
            return f.read().replace(b"\0", b" ").decode()
    except OSError:
        return ""


def _ppid(pid: int) -> int:
    with open(f"/proc/{pid}/stat", "rb") as f:
        return int(f.read().rsplit(b") ", 1)[1].split()[1])


def _busy_session(want: str):
    """A session with an actor, a handful of finished tasks and a warm
    pool, once a process whose command line holds `want` is up. Returns
    (runtime, every process of the session, the head raylet's Popen)."""
    rt.shutdown()
    rt.init(num_cpus=2, num_workers=1, object_store_memory=192 << 20)
    runtime = runtime_base.current_runtime()

    @rt.remote
    class A:
        def pid(self):
            return os.getpid()

    @rt.remote
    def f(i):
        return i

    a = A.remote()
    rt.get(a.pid.remote(), timeout=120)
    assert rt.get([f.remote(i) for i in range(6)], timeout=120) == list(range(6))
    deadline = time.monotonic() + 120
    while True:
        procs = proctree.session_procs(runtime._session_dir)
        lines = [_cmdline(p) for p in procs]
        if sum(want in line for line in lines) >= 2:
            break
        assert time.monotonic() < deadline, f"no two {want!r} processes: {lines}"
        time.sleep(0.1)
    cluster = runtime._cluster
    # gcs + raylet + the two above, at the least
    assert len(procs) >= 4, lines
    return runtime, procs, cluster._node_procs[cluster.head_node_id]


def _assert_none_alive(runtime, procs):
    alive = [(p.pid, _cmdline(p)) for p in procs if p.poll() is None]
    assert not alive, f"alive when shutdown returned: {alive}"
    left = proctree.session_procs(runtime._session_dir)
    assert not left, [(p.pid, _cmdline(p)) for p in left]


@pytest.mark.parametrize("case", ["quiet", "raylet_stalled", "cluster_shutdown", "raylet_killed"])
def test_shutdown_leaves_no_process(case):
    # "core.zygote": the zygote and its fork children (parked, idle, busy)
    runtime, procs, raylet = _busy_session("ray_tpu.core.zygote")
    timer = None
    if case == "raylet_stalled":
        # Longer than the old teardown waited in all (2 s + 0.1 s + 3 s):
        # it then killed the raylet before its `stop` ran and returned
        # with the zygote and every worker alive.
        os.kill(raylet.pid, signal.SIGSTOP)
        timer = threading.Timer(6.0, os.kill, (raylet.pid, signal.SIGCONT))
        timer.start()
    elif case == "raylet_killed":
        # Only the net is left to act: nobody runs the raylet's `stop`, and
        # a zygote that is stalled runs no ppid watchdog either (the old
        # teardown left it and its children to that watchdog).
        (zygote,) = [
            p.pid for p in procs
            if "ray_tpu.core.zygote" in _cmdline(p) and _ppid(p.pid) == raylet.pid
        ]
        raylet.kill()
        raylet.wait(timeout=30)
        os.kill(zygote, signal.SIGSTOP)
    try:
        if case == "cluster_shutdown":
            runtime._cluster.shutdown()  # what `atexit` runs too
        else:
            rt.shutdown()
        _assert_none_alive(runtime, procs)
    finally:
        if timer is not None:
            timer.cancel()
            timer.join(timeout=10)


def test_shutdown_ends_cold_spawned_workers(monkeypatch):
    """A worker from the `Popen` fallback has no zygote above it and no
    parent-death tie: its raylet ends it, or the net does."""
    monkeypatch.setenv("RAY_TPU_WORKER_ZYGOTE", "0")  # read by the raylet it starts
    runtime, procs, _raylet = _busy_session("ray_tpu.core.worker_proc")
    assert not any("ray_tpu.core.zygote" in _cmdline(p) for p in procs)
    rt.shutdown()
    _assert_none_alive(runtime, procs)
