"""Who ends a session's processes, and when the ending is over.

One chain, each level ending and reaping its own children, and one net
under it:

- a worker is ended by its raylet (`RayletService.stop` -> `end`), the
  zygote and through it the parked pre-forks by the pool manager
  (`WorkerPoolManager.stop`);
- the daemons (GCS, raylets) are ended by whoever started them, through
  `end_session`: `ClusterRuntime.shutdown`, `Cluster.shutdown` / its
  `atexit` hook and `ray-tpu stop` all call it and nothing else;
- what a dead or stalled daemon left behind is found by `session_procs`
  and SIGKILLed by `end_session` before it returns.

When `end_session` returns, no process of the session is alive.
`PR_SET_PDEATHSIG` and the zygote's ppid watchdog (core/zygote.py) stay
as the cover for a driver that was itself SIGKILLed; no normal shutdown
relies on them.

Imports nothing heavy: the zygote's fork children and the CLI use it.
"""

from __future__ import annotations

import glob
import os
import signal
import socket
import time
from typing import Any, Iterable, List

from .zygote import PidHandle

# The bounds of the chain. This host is on record for freezing every
# process for 7-8 s when a TPU runtime starts (PERF.md §6, PR 21): a wait
# shorter than that reads a stall as a refusal. None is settable.
CHILD_EXIT_S = 10.0  # a level's children, after SIGTERM and again after SIGKILL
DAEMON_STOP_S = 30.0  # a raylet's `stop`: it may spend 2 x CHILD_EXIT_S on its own children
# What a worker that has read `stop` from its mailbox gets to leave by
# itself before the SIGTERM (the wait ends when the last one is gone).
GRACE_S = 0.1
_POLL_S = 0.002


def wait_gone(procs: Iterable[Any], bound_s: float) -> List[Any]:
    """Waits until every proc (`Popen` or `PidHandle`: `poll()`; a `Popen`
    is reaped by it) is gone, or `bound_s` is over. Returns those alive."""
    alive = [p for p in procs if p.poll() is None]
    deadline = time.monotonic() + bound_s
    while alive and time.monotonic() < deadline:
        time.sleep(_POLL_S)
        alive = [p for p in alive if p.poll() is None]
    return alive


def end(procs: Iterable[Any], sig: int = signal.SIGTERM) -> List[Any]:
    """Ends processes and waits for them: `sig`, CHILD_EXIT_S, then SIGKILL
    to what is left and CHILD_EXIT_S again. Returns what outlived even
    that (a process in uninterruptible sleep; nothing can end it)."""
    procs = [p for p in procs if p.poll() is None]
    for p in procs:
        p.send_signal(sig)
    left = wait_gone(procs, CHILD_EXIT_S)
    for p in left:
        p.kill()
    return wait_gone(left, CHILD_EXIT_S)


def session_procs(session_dir: str) -> List[PidHandle]:
    """Every live process of the session but the caller, found from
    outside by a sweep of /proc for a command line that names a path under
    `session_dir`. Chosen over pid files because it needs nothing of the
    process it finds: a daemon's argv carries its socket, a cold-spawned
    worker's the raylet's socket, and a zygote's fork child (parked, idle
    or busy: it never execs) keeps the zygote's command line, which names
    the zygote's socket. /proc/<pid>/environ would miss the fork children:
    it shows the environment at exec, not what an assignment set later."""
    needle = os.path.join(session_dir, "").encode()
    me = os.getpid()
    found = []
    for name in os.listdir("/proc"):
        if not name.isdigit() or int(name) == me:
            continue
        try:
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmdline = f.read()
        except OSError:
            continue  # gone since the listing
        if needle in cmdline:
            proc = PidHandle(int(name))
            if proc.poll() is None:
                found.append(proc)
    return found


def uds_accepts(sock_path: str) -> bool:
    """Whether a daemon listens on the socket file (a dead one's refuses)."""
    s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    s.settimeout(0.2)
    try:
        s.connect(sock_path)
        return True
    except OSError:
        return False
    finally:
        s.close()


def end_session(session_dir: str, daemons: Iterable[Any]) -> None:
    """THE teardown of a session's processes. `daemons` are the GCS and
    raylet processes as `Popen` (the caller's children: reaped here) or
    `PidHandle` (`ray-tpu stop`, another process's children).

    1. Each raylet that answers is asked to `stop`: it ends and reaps its
       workers and its zygote before it replies. Then the GCS.
    2. The daemons are ended and reaped.
    3. Whatever of the session is still alive (children of a raylet that
       was dead, wedged or killed before its `stop` ran) is SIGKILLed and
       waited for. Costs one /proc listing when there is nothing."""
    from .rpc import RpcClient

    socks = sorted(glob.glob(os.path.join(session_dir, "raylet_*.sock")))
    socks.append(os.path.join(session_dir, "gcs.sock"))
    for sock in socks:
        if not (os.path.exists(sock) and uds_accepts(sock)):
            continue
        try:
            client = RpcClient(sock, connect_timeout=1.0)
            try:
                client.call("stop", timeout=DAEMON_STOP_S)
            finally:
                client.close()
        except Exception:  # lint: swallow-ok(a daemon that cannot answer is ended below, its children by the sweep)
            pass
    end(daemons)
    end(session_procs(session_dir), signal.SIGKILL)
