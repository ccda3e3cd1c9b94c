"""Does a command leave a process behind? The teardown contract, from outside.

    python3 tools/teardown_check.py [--stall-raylet S] [--out FILE] -- python3 benchmarks/run.py --workload <cell> ...

Runs the command and, at the instant it exits, lists every live process
of the machine that was not there before it started (zombies excepted:
they have exited), and again one second later. Prints one JSON line
(`left`, `left_1s_later`, `rc`, `wall_s`, the command's own last line as
`line`) and appends it to FILE. Exits 1 if `left` is not empty, else with
the command's code.

`--stall-raylet S` runs the command's script in a child of this tool with
`ray_tpu.shutdown` wrapped: the head raylet is SIGSTOPped just before the
call and SIGCONTed S seconds later, so the teardown meets a daemon that is
stalled for longer than any wait of the pre-PR-30 code (core/proctree.py).
"""

from __future__ import annotations

import argparse
import json
import os
import runpy
import signal
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def live_pids() -> dict:
    """pid -> command line of every process that has not exited."""
    out = {}
    for name in os.listdir("/proc"):
        if not name.isdigit():
            continue
        try:
            with open(f"/proc/{name}/stat", "rb") as f:
                state = f.read().rsplit(b") ", 1)[1].split()[0]
            with open(f"/proc/{name}/cmdline", "rb") as f:
                cmdline = f.read().replace(b"\0", b" ").decode(errors="replace").strip()
        except (OSError, IndexError):
            continue
        if state != b"Z":
            out[int(name)] = cmdline
    return out


def run_stalled(stall_s: float, script: str, argv: list) -> None:
    """The child of --stall-raylet: the script, with the raylet stopped
    across `ray_tpu.shutdown()`."""
    sys.path.insert(0, ROOT)
    import ray_tpu
    from ray_tpu.core import runtime_base

    shutdown = ray_tpu.shutdown

    def stalled_shutdown():
        runtime = runtime_base.maybe_runtime()
        cluster = getattr(runtime, "_cluster", None)
        if cluster is not None:
            raylet = cluster._node_procs[cluster.head_node_id]
            os.kill(raylet.pid, signal.SIGSTOP)
            print(f"teardown_check: raylet {raylet.pid} stopped for {stall_s} s", file=sys.stderr, flush=True)
            threading.Timer(stall_s, os.kill, (raylet.pid, signal.SIGCONT)).start()
        shutdown()

    ray_tpu.shutdown = stalled_shutdown
    sys.argv = [script, *argv]
    runpy.run_path(script, run_name="__main__")


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--stall-raylet", type=float, default=None)
    ap.add_argument("--out", default=None)
    ap.add_argument("--as-stalled-child", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    command = args.command[1:] if args.command[:1] == ["--"] else args.command
    if args.as_stalled_child:
        run_stalled(args.stall_raylet, command[0], command[1:])
        return 0
    if args.stall_raylet is not None:
        # command is `python3 <script> <args>`: the script runs inside a child of this tool
        command = [sys.executable, os.path.abspath(__file__), "--as-stalled-child",
                   "--stall-raylet", str(args.stall_raylet), "--", *command[1:]]
    before = set(live_pids())
    t0 = time.monotonic()
    child = subprocess.Popen(command, stdout=subprocess.PIPE, text=True)
    stdout, _ = child.communicate()
    at_exit = live_pids()
    wall_s = time.monotonic() - t0
    me = os.getpid()
    left = {pid: c for pid, c in at_exit.items() if pid not in before and pid != me}
    time.sleep(1.0)
    later = live_pids()
    sys.stdout.write(stdout)
    last = stdout.strip().splitlines()[-1] if stdout.strip() else ""
    try:
        line = json.loads(last)
    except ValueError:
        line = None
    record = {
        "command": command,
        "rc": child.returncode,
        "wall_s": wall_s,
        "left": sorted(left.items()),
        "left_1s_later": sorted((pid, c) for pid, c in left.items() if pid in later),
        "line": line,
    }
    print("teardown_check: " + json.dumps({k: record[k] for k in ("rc", "wall_s", "left", "left_1s_later")}), flush=True)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "a") as f:
            f.write(json.dumps(record) + "\n")
    return 1 if left else child.returncode


if __name__ == "__main__":
    sys.exit(main())
