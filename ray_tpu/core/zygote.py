"""Zygote: fork pre-warmed worker processes in milliseconds.

Re-design of the reference's worker-startup optimizations (reference:
worker_pool.h prestarted idle workers + the forking of
default_worker.py). EVERY fresh python process pays interpreter start-up
plus the worker stack's imports before a worker can
poll for work — the dominant cost of actor creation and pool growth. The
zygote pays that cost ONCE: a single-threaded daemon that pre-imports
the worker stack, listens on a UDS, and `fork()`s a ready worker per
request (~10 ms). Fork safety holds because the zygote is strictly
single-threaded; it imports neither jax nor numpy (whose BLAS pool starts
threads) and never initializes a backend — a chip opened here would be
taken from every worker it forks.

Two fork tiers serve a spawn request:

- **Parked pre-forks** (the warm path): the daemon keeps a standing pool
  of ALREADY-FORKED children, each blocked on a private pipe waiting for
  its assignment (argv/env/log paths). A pop is one pipe write — the
  fork itself (page-table copy of the multi-hundred-MB pre-imported
  image, the 10-17 ms the launch profile pinned on worker_spawn) was
  paid asynchronously at refill time. The raylet's pool manager sizes
  this pool from its demand signal (`{"pool": N}` requests).
- **Cold fork** (the miss path): fork-on-demand, exactly the original
  behavior, when the parked pool is empty.

Batched spawns (`{"batch": [...]}`) cost one socket round trip for N
workers — a launch storm's forks coalesce instead of serializing on
per-request UDS round trips.

Workers needing a different interpreter (pip/conda venvs) or a container
prefix cannot fork from here; the raylet falls back to a normal spawn
for those.

Protocol (one JSON line per request/reply over the UDS):
  {"argv": [...], "env": {...}, "out": path, "err": path}
      -> {"pid": N, "warm": bool}
  {"batch": [spawn_req, ...]}   -> {"spawns": [{"pid": N, "warm": b}|null]}
  {"pool": N}                   -> {"parked": N_now, "forked": K}
  {"stats": true}               -> {"parked": N, "pid": zygote_pid}
  {"reset": true}               -> {"drained": K}   (parked children exit)
  {"exit_code": pid}            -> {"code": N | null}  (how a reaped child ended)
  {"stop": true}                -> (daemon exits; parked die via pdeathsig)
"""

from __future__ import annotations

import json
import os
import signal
import socket
import sys
from typing import Dict, List, Optional, Tuple


# PR_SET_PDEATHSIG, pre-bound at import so set_pdeathsig() does no
# allocation/import work — it must be safe as a Popen preexec_fn (which
# runs between fork and exec in a possibly-threaded parent).
_PRCTL = None
try:
    import ctypes as _ctypes

    _PRCTL = _ctypes.CDLL(None, use_errno=True).prctl
except Exception:  # non-linux / no libc: stays a no-op
    _PRCTL = None
_PR_SET_PDEATHSIG = 1


def set_pdeathsig(sig: int = signal.SIGTERM) -> None:
    """Best-effort parent-death signal (VERDICT advice #2 — a killed
    raylet must not leak warm-pool workers). The signal fires when the
    parent THREAD that forked dies, so this is only armed where the
    forking side is the single-threaded zygote main thread; the zygote's
    own tie to the raylet is the ppid watchdog in main() (a Popen from a
    transient raylet thread would otherwise kill the child the moment
    that thread exits). No-op where prctl is unavailable; cleared by
    fork, so every fork child re-arms it."""
    if _PRCTL is None:
        return
    try:
        _PRCTL(_PR_SET_PDEATHSIG, int(sig), 0, 0, 0)
    except Exception:  # lint: swallow-ok(prctl unavailable; ppid watchdog is the fallback)
        pass


_EXIT_CODES: Dict[int, int] = {}  # pid -> exit code (negative: the signal), the last few hundred reaped
_EXIT_CODES_KEPT = 256


def _reap(signum, frame):
    """Collect any exited children so they don't linger as zombies (the
    raylet detects death via os.kill(pid, 0) => ESRCH after the reap), and
    remember how each ended: the raylet asks (`exit_code`) when it writes a
    dead worker's post-mortem, since only the parent can know."""
    try:
        while True:
            pid, status = os.waitpid(-1, os.WNOHANG)
            if pid == 0:
                break
            _EXIT_CODES[pid] = os.waitstatus_to_exitcode(status)
            while len(_EXIT_CODES) > _EXIT_CODES_KEPT:
                del _EXIT_CODES[next(iter(_EXIT_CODES))]
    except ChildProcessError:
        pass


_CHILD_CLOSE = []  # sockets the fork child must not inherit
# Parked pre-forked children: [(pid, assignment_pipe_write_fd)]. Every
# fork child closes all CURRENT parked write-ends immediately (see
# _close_inherited), so each parked child's pipe has exactly ONE writer —
# the zygote — and closing that fd is a reliable EOF/exit signal.
_PARKED: List[Tuple[int, int]] = []


def _close_inherited() -> None:
    """Drops fds a fresh fork child must not keep: the UDS listener (an
    inherited live backlog would make post-zygote-death clients block in
    connect instead of failing fast), accepted conns, and the parked
    siblings' assignment-pipe write ends (a stray writer would defeat the
    close-means-exit contract of the parked pool)."""
    for s in _CHILD_CLOSE:
        try:
            s.close()
        except OSError:
            pass
    for _pid, wfd in _PARKED:
        try:
            os.close(wfd)
        except OSError:
            pass


def _child_exec(req: dict) -> None:
    """Runs in the fork child: applies the spawn assignment (log
    redirects, environment, argv) and becomes the worker. Never
    returns."""
    try:
        out = os.open(req["out"], os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        err = os.open(req["err"], os.O_WRONLY | os.O_CREAT | os.O_APPEND, 0o644)
        os.dup2(out, 1)
        os.dup2(err, 2)
        os.close(out)
        os.close(err)
        os.environ.clear()
        os.environ.update(req["env"])
        signal.signal(signal.SIGCHLD, signal.SIG_DFL)
        from ray_tpu.core import worker_proc

        worker_proc.main(req["argv"])
        os._exit(0)
    except SystemExit as e:
        os._exit(int(e.code or 0))
    except BaseException:  # noqa: BLE001
        import traceback

        traceback.print_exc()
        os._exit(1)


def _spawn(req: dict) -> int:
    """Cold fork: fork + exec the assignment immediately (the original
    spawn path; the miss path once a parked pool exists)."""
    pid = os.fork()
    if pid != 0:
        return pid
    # ---- child ----
    try:
        _close_inherited()
        os.setsid()  # own process group: raylet signals target only us
        # Die with the zygote (which itself dies with the raylet): no
        # orphaned warm-pool workers after a raylet kill -9.
        set_pdeathsig(signal.SIGTERM)
    except BaseException:  # noqa: BLE001
        os._exit(1)
    _child_exec(req)


def _prefork() -> Optional[Tuple[int, int]]:
    """Forks one PARKED child: it blocks on a private pipe until the
    zygote writes its assignment (pop) or closes the write end (reset /
    zygote death). Returns (pid, write_fd), or None when the fork
    failed (pid/memory pressure — exactly when pools fill — must not
    leak the pipe or take down the daemon)."""
    try:
        rfd, wfd = os.pipe()
    except OSError:
        return None
    try:
        pid = os.fork()
    except OSError:
        os.close(rfd)
        os.close(wfd)
        return None
    if pid != 0:
        os.close(rfd)
        return (pid, wfd)
    # ---- parked child ----
    try:
        os.close(wfd)  # our copy of our own write end
        _close_inherited()
        os.setsid()
        set_pdeathsig(signal.SIGTERM)
        buf = b""
        while not buf.endswith(b"\n"):
            chunk = os.read(rfd, 65536)
            if not chunk:
                os._exit(0)  # write end closed: reset or zygote death
            buf += chunk
        os.close(rfd)
        req = json.loads(buf)
        if req.get("exit"):
            os._exit(0)
    except BaseException:  # noqa: BLE001
        os._exit(1)
    _child_exec(req)


def _write_all(fd: int, data: bytes) -> None:
    """os.write until every byte lands: assignment JSON (env + argv) is
    routinely > PIPE_BUF, and a SIGCHLD landing mid-write makes os.write
    return a PARTIAL count — a truncated assignment would make the
    parked child exit on a missing newline while the zygote still
    reports the pop as successful."""
    view = memoryview(data)
    while view:
        n = os.write(fd, view)
        view = view[n:]


def _pop_parked(req: dict) -> Optional[int]:
    """Assigns `req` to a parked child (one pipe write). None when the
    pool is empty or every parked child turned out dead."""
    while _PARKED:
        pid, wfd = _PARKED.pop(0)
        try:
            _write_all(wfd, (json.dumps(req) + "\n").encode())
            os.close(wfd)
            return pid
        except OSError:
            # The child died while parked (OOM-killed, signaled): its
            # pipe raises EPIPE/EBADF. Skip to the next one.
            try:
                os.close(wfd)
            except OSError:
                pass
    return None


def _kill_parked(pid: int, wfd: int) -> None:
    """One parked child's teardown: close its assignment pipe. The child
    reads EOF and exits; one still between its fork and its read finds
    the EOF waiting."""
    try:
        os.close(wfd)
    except OSError:
        pass


def _drain_parked() -> int:
    """Tears down every parked child (fence/reset contract)."""
    n = 0
    while _PARKED:
        _kill_parked(*_PARKED.pop())
        n += 1
    return n


def _fill_pool(target: int) -> int:
    """Pre-forks parked children up to `target`; returns forks done."""
    forked = 0
    while len(_PARKED) < target:
        entry = _prefork()
        if entry is None:
            break
        _PARKED.append(entry)
        forked += 1
    return forked


def _do_spawn(req: dict) -> dict:
    pid = _pop_parked(req)
    if pid is not None:
        return {"pid": pid, "warm": True}
    try:
        return {"pid": _spawn(req), "warm": False}
    except OSError as e:
        # fork() failed (pid/memory pressure): the DAEMON is healthy —
        # answer with an error so the raylet Popen-falls-back without
        # declaring the zygote dead (a reply-less close would trigger a
        # respawn that torches the whole parked pool).
        return {"error": f"fork failed: {e}"}


def _handle(req: dict) -> Optional[dict]:
    """One protocol request -> reply dict (None = no reply / stop)."""
    if req.get("stop"):
        return None
    if req.get("stats"):
        return {"parked": len(_PARKED), "pid": os.getpid()}
    if req.get("reset"):
        return {"drained": _drain_parked()}
    if "exit_code" in req:
        _reap(None, None)  # a child that is a zombie this instant
        return {"code": _EXIT_CODES.get(int(req["exit_code"]))}
    if "pool" in req:
        target = max(0, int(req["pool"]))
        forked = _fill_pool(target)
        # Shrink: drain the excess (newest first; the oldest keep
        # serving pops in FIFO order).
        while len(_PARKED) > target:
            _kill_parked(*_PARKED.pop())
        return {"parked": len(_PARKED), "forked": forked}
    if "batch" in req:
        return {"spawns": [_do_spawn(r) for r in req["batch"]]}
    return _do_spawn(req)


def _prewarm_worker_stack() -> None:
    """Imports the ENTIRE worker import graph before any fork: the
    cluster runtime, rpc, serialization, shm store, observability — the
    ~2 s the launch profile charges to a cold worker's first poll. A
    pre-forked child inherits all of it via COW pages, so its remaining
    boot is socket connects + store attach. Import only; no jax backend
    ever initializes here (fork safety, one process per chip,
    tools/check_import_safety)."""
    from ray_tpu.core import worker_proc  # noqa: F401

    for mod in (
        "ray_tpu.core.cluster_runtime",
        "ray_tpu.core.runtime_base",
        "ray_tpu.core.runtime_context",
        "ray_tpu.core.serialization",
        "ray_tpu.core.shm_store",
        "ray_tpu.core.object_transport",
        "ray_tpu.core.rpc",
        "ray_tpu.core.fastpath",
        "ray_tpu.observability.logs",
        "ray_tpu.observability.flight_recorder",
        "ray_tpu.utils.internal_metrics",
    ):
        try:
            __import__(mod)
        except Exception:  # lint: swallow-ok(prewarm is best-effort; the child imports lazily on a miss)
            pass


def main(sock_path: str) -> None:
    signal.signal(signal.SIGCHLD, _reap)
    _prewarm_worker_stack()

    # Orphan hygiene: the zygote must die with its raylet or a kill -9'd
    # raylet leaks the whole warm pool (children then die via their
    # PR_SET_PDEATHSIG tie to us). pdeathsig is unusable for THIS tie —
    # the raylet Popens us from a transient boot thread — so the accept
    # loop doubles as a ppid watchdog: reparenting to init means the
    # raylet is gone.
    boot_ppid = os.getppid()
    srv = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    srv.settimeout(2.0)
    _CHILD_CLOSE.append(srv)
    if os.path.exists(sock_path):
        os.unlink(sock_path)
    srv.bind(sock_path + ".tmp")
    srv.listen(64)
    os.rename(sock_path + ".tmp", sock_path)  # atomic readiness signal
    while True:
        try:
            conn, _ = srv.accept()
        except socket.timeout:
            if os.getppid() != boot_ppid:
                return  # raylet died: take the warm pool down with us
            continue
        except InterruptedError:
            continue  # SIGCHLD during accept
        except OSError:
            return
        conn.settimeout(None)  # accepted sockets inherit the listener's
        _CHILD_CLOSE.append(conn)
        try:
            f = conn.makefile("rwb")
            line = f.readline()
            if not line:
                continue
            req = json.loads(line)
            reply = _handle(req)
            if reply is None:
                return  # stop request
            f.write((json.dumps(reply) + "\n").encode())
            f.flush()
        except Exception:  # noqa: BLE001  # lint: swallow-ok(one bad spawn request must not kill the zygote server)
            pass
        finally:
            try:
                conn.close()
            except OSError:
                pass
            if conn in _CHILD_CLOSE:
                _CHILD_CLOSE.remove(conn)


class ZygoteSpawnError(RuntimeError):
    """The daemon is alive but THIS fork failed (resource pressure).
    Distinct from daemon loss: callers fall back to Popen for the one
    spawn without triggering a zygote respawn."""


class ZygoteClient:
    """Raylet-side handle: request forks; transparently unavailable when
    the daemon is gone (callers fall back to a direct spawn)."""

    def __init__(self, sock_path: str):
        self.sock_path = sock_path

    def _request(self, req: dict, timeout: float = 10.0) -> dict:
        s = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
        s.settimeout(timeout)
        try:
            s.connect(self.sock_path)
            f = s.makefile("rwb")
            f.write((json.dumps(req) + "\n").encode())
            f.flush()
            return json.loads(f.readline())
        finally:
            s.close()

    @staticmethod
    def spawn_spec(argv: List[str], env: dict, out: str, err: str) -> dict:
        return {"argv": argv, "env": env, "out": out, "err": err}

    def spawn(self, argv: List[str], env: dict, out: str, err: str) -> Tuple[int, bool]:
        """Forks one worker; returns (pid, warm) — warm means a parked
        pre-forked child took the assignment (~1 ms) instead of a fresh
        fork (~10 ms). Raises ZygoteSpawnError when the daemon answered
        but the fork itself failed."""
        reply = self._request(self.spawn_spec(argv, env, out, err))
        if "error" in reply:
            raise ZygoteSpawnError(reply["error"])
        return int(reply["pid"]), bool(reply.get("warm"))

    def spawn_batch(self, specs: List[dict]) -> List[Tuple[int, bool]]:
        """N forks in ONE socket round trip (launch storms coalesce).
        All-or-nothing surface: any per-spawn fork failure raises
        ZygoteSpawnError (callers retry the whole refill later; already-
        forked batch-mates are never adopted, poll the raylet as unknown
        workers, and exit on its stop reply)."""
        reply = self._request({"batch": specs}, timeout=30.0)
        if any("error" in r for r in reply["spawns"]):
            raise ZygoteSpawnError(
                "; ".join(r["error"] for r in reply["spawns"] if "error" in r)
            )
        return [
            (int(r["pid"]), bool(r.get("warm"))) for r in reply["spawns"]
        ]

    def ensure_pool(self, target: int) -> dict:
        """Refills (or shrinks) the parked pre-fork pool to `target`."""
        return self._request({"pool": int(target)}, timeout=30.0)

    def stats(self) -> dict:
        return self._request({"stats": True})

    def exit_code(self, pid: int) -> Optional[int]:
        """How a forked child ended, as Popen.returncode would say it
        (negative: killed by that signal); None if the daemon never reaped
        that pid or has forgotten it."""
        return self._request({"exit_code": int(pid)}, timeout=2.0).get("code")

    def reset(self) -> int:
        """Drains every parked child (fence/teardown: no orphan
        pre-forked workers may outlive the incarnation that forked
        them)."""
        return int(self._request({"reset": True}).get("drained", 0))


def _proc_starttime(pid: int):
    """Kernel start time of `pid` (field 22 of /proc/<pid>/stat) — the
    (pid, starttime) pair is unique across pid reuse. None for a process
    that is gone OR a zombie: it has exited (and closed its devices) even
    if the zygote that should reap it is itself dead or busy."""
    try:
        with open(f"/proc/{pid}/stat", "rb") as f:
            fields = f.read().rsplit(b") ", 1)[1].split()
        return None if fields[0] == b"Z" else fields[19]
    except (OSError, IndexError):
        return None


class PidHandle:
    """Popen-compatible surface over a zygote-forked pid (the subset the
    raylet uses: poll/kill/terminate/send_signal). The zygote reaps, so
    death shows up as a missing/NONMATCHING /proc entry — the recorded
    starttime guards against the OS recycling the pid for an unrelated
    process (which bare os.kill(pid, 0) probing would misreport as our
    live worker, and kill() would then signal)."""

    def __init__(self, pid: int):
        self.pid = pid
        self._rc = None
        self._starttime = _proc_starttime(pid)

    def _alive(self) -> bool:
        st = _proc_starttime(self.pid)
        return st is not None and st == self._starttime

    def poll(self):
        if self._rc is not None:
            return self._rc
        if self._alive():
            return None
        self._rc = -1
        return self._rc

    def send_signal(self, sig: int) -> None:
        if not self._alive():
            self._rc = -1
            return  # pid may be recycled: never signal a stranger
        try:
            os.kill(self.pid, sig)
        except ProcessLookupError:
            self._rc = -1

    def kill(self) -> None:
        self.send_signal(signal.SIGKILL)

    def terminate(self) -> None:
        self.send_signal(signal.SIGTERM)


if __name__ == "__main__":
    main(sys.argv[1])
