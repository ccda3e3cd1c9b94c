"""Warm worker-pool manager: forecast-sized prestart + zygote lifecycle.

Re-design of the reference's worker-pool prestart (reference:
worker_pool.h PrestartWorkers + the idle-pool sizing around
kMaximumStartupConcurrency) as a standing control loop instead of the
PR-1 one-shot boot prestart. Of an actor launch's spans
(`actor_launch.*`, ray-tpu timeline) worker_spawn is the long one, so
this module's job is to make sure a launch almost never pays a spawn
synchronously:

- **Tier 1 — live idle workers** (the raylet's `_idle` map): popped in
  microseconds at dispatch. The manager refills this pool ASYNCHRONOUSLY
  after every pop, up to a demand-sized target.
- **Tier 2 — zygote parked pre-forks** (core/zygote.py `{"pool": N}`):
  already-forked, already-imported children waiting on an assignment
  pipe. A tier-1 miss that reaches the zygote is served in ~1-2 ms by a
  parked child instead of a 10-17 ms fork; the parked pool is refilled
  in the background too.

The target follows a demand signal, per the autoscaler's design: a
raylet-local sliding-window estimate of the recent launch rate (times a
horizon) plus the GCS's `pool_hint` from each heartbeat reply — pending
actors placed on this node plus the autoscaler_v2 InstanceManager's
pending-work forecast share (`report_demand_forecast`).

The manager also owns the zygote daemon's LIFECYCLE: boot, death
detection (the daemon dying used to strand the prestart pool silently —
spawns fell back to cold Popen forever), structured logging, respawn,
and parked-pool rebuild. Chaos point `zygote.spawn` (action `kill` =
SIGKILL the daemon at a spawn request) drills exactly that path.
"""

from __future__ import annotations

import collections
import math
import os
import signal
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from ..chaos.controller import maybe_inject as _chaos_inject
from ..observability.flight_recorder import record as _flight_record
from ..observability.logs import get_logger as _get_logger
from ..utils import internal_metrics as imet
from ..utils import lock_order
from ..utils.config import CONFIG
from . import proctree
from .zygote import ZygoteClient, ZygoteSpawnError

_log = _get_logger("worker_pool")


class ZygoteUnavailableError(RuntimeError):
    """The zygote daemon cannot serve this spawn (dead / never booted);
    callers fall back to a cold Popen while the manager respawns it."""


class LaunchRate:
    """Sliding-window launch-rate estimator: a bounded deque of event
    stamps; per_s() counts events inside the window. Exact over the
    window (an EWMA's decay constant would lag a burst's leading edge —
    the edge is precisely when the pool must start growing)."""

    def __init__(self, window_s: float = 2.0, cap: int = 512):
        self.window_s = window_s
        self._stamps: "collections.deque[float]" = collections.deque(maxlen=cap)
        self._lock = threading.Lock()

    def note(self, n: int = 1) -> None:
        now = time.monotonic()
        with self._lock:
            for _ in range(n):
                self._stamps.append(now)

    def per_s(self) -> float:
        cutoff = time.monotonic() - self.window_s
        with self._lock:
            while self._stamps and self._stamps[0] < cutoff:
                self._stamps.popleft()
            return len(self._stamps) / self.window_s


class WorkerPoolManager:
    """Owns zygote lifecycle + pool sizing for one raylet. The raylet
    supplies the spawn machinery via two callbacks (it owns the worker
    table and env assembly); everything else — demand tracking, refill,
    respawn, metrics — lives here."""

    def __init__(self, raylet: Any, prestart: int = 0):
        self._raylet = raylet
        self._prestart = max(0, int(prestart))
        self._rate = LaunchRate(window_s=max(0.5, 4 * CONFIG.worker_pool_interval_s))
        self._lock = lock_order.tracked_lock("worker_pool.state")
        self._hint = 0  # GCS heartbeat pool_hint (forecast share, net of
        # registrations the GCS already consumed against the forecast)
        self._hits = {"idle": 0, "prefork": 0}
        self._misses = {"zygote": 0, "popen": 0}
        self._last_miss = 0.0  # monotonic stamp of the last cold spawn
        self._last_pop = 0.0  # monotonic stamp of the last warm pop
        self._last_trickle = 0.0  # paces no-miss background rebuilds
        self._respawns = 0
        # Respawn backoff: a daemon that dies at boot deterministically
        # (broken env, prewarm import error) must not be fork/exec'd
        # twice a second forever. Doubles per failed boot, capped;
        # reset by a successful boot.
        self._respawn_backoff_s = 1.0
        self._respawn_not_before = 0.0
        # Parked-pool size as of the last maintenance round. stats()
        # reads THIS, never the daemon: the zygote is single-threaded,
        # so a live probe from the heartbeat loop would queue behind an
        # in-flight fork batch — observed stalling heartbeats past the
        # death timeout under load (the node got declared dead by its
        # own pool telemetry).
        self._parked = 0
        self._zygote_proc: Optional[subprocess.Popen] = None
        self._zygote: Optional[ZygoteClient] = None
        self._zygote_failed = threading.Event()
        self._wake = threading.Event()
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._maintenance, daemon=True, name="worker-pool"
        )

    # ------------------------------------------------------------ lifecycle
    def start(self) -> None:
        self._thread.start()

    def stop(self) -> None:
        """Ends and reaps the zygote; returns when it is gone. Its exit is
        what ends the parked pre-forks (their assignment pipes read EOF)."""
        self._stop.set()
        self._wake.set()
        self._end_zygote()
        if self._thread.is_alive():
            # A maintenance round that was mid-boot has seen _stop (or its
            # zygote die) and ends; the daemon it may just have started is
            # ended like the first.
            self._thread.join(proctree.CHILD_EXIT_S)
            self._end_zygote()

    def _end_zygote(self) -> None:
        proc = self._zygote_proc
        if proc is not None:
            proctree.end([proc], signal.SIGKILL)

    # ------------------------------------------------------- demand signal
    def note_demand(self, n: int = 1) -> None:
        """One launch event (actor creation / lease spawn): feeds the
        rate window and wakes the refill loop. Deliberately no local
        hint bookkeeping: the GCS consumes the forecast per registration
        and every 1 Hz heartbeat delivers the consumed value — a second,
        local decrement double-counted the same launches and collapsed
        the hint to zero mid-storm. The ≤1-heartbeat staleness window is
        covered by the refill's `popping` gate instead (a pool serving
        warm pauses rebuilds regardless of what the hint says)."""
        self._rate.note(n)
        self._wake.set()

    def note_hit(self, tier: str) -> None:
        with self._lock:
            self._hits[tier] = self._hits.get(tier, 0) + 1
            self._last_pop = time.monotonic()
        imet.WORKER_POOL_HITS.inc(tier=tier)
        # Ring breadcrumb: a postmortem of a slow actor launch needs to
        # see whether the pool served warm/zygote or fell to cold spawn.
        _flight_record("pool.pop", tier)
        self._wake.set()  # a pop leaves a hole: refill promptly

    def note_miss(self, mode: str) -> None:
        with self._lock:
            self._misses[mode] = self._misses.get(mode, 0) + 1
            self._last_miss = time.monotonic()
        imet.WORKER_POOL_MISSES.inc(mode=mode)
        _flight_record("pool.miss", mode)
        self._wake.set()

    def set_hint(self, n: int) -> None:
        """Heartbeat-reply demand hint: this node's share of the
        autoscaler forecast, already net of the registrations the GCS
        has consumed against it."""
        changed = False
        with self._lock:
            fresh = max(0, int(n))
            if fresh != self._hint:
                self._hint = fresh
                changed = True
        if changed:
            self._wake.set()

    def target(self) -> int:
        """Forecast-sized idle-pool target: configured floor + demand."""
        with self._lock:
            hint = self._hint
        demand = math.ceil(self._rate.per_s() * CONFIG.worker_pool_horizon_s)
        return min(
            int(CONFIG.worker_pool_max), max(self._prestart, demand + hint)
        )

    def _prefork_target(self) -> int:
        """Parked-pool target: same signal, its own floor/cap (parked
        children are cheaper than live workers — COW pages, no sockets —
        so the floor stays above zero even when idle demand is)."""
        if self._zygote is None or not CONFIG.worker_zygote:
            return 0
        demand = math.ceil(self._rate.per_s() * CONFIG.worker_pool_horizon_s)
        with self._lock:
            hint = self._hint
        return min(
            int(CONFIG.worker_pool_prefork_max),
            max(int(CONFIG.worker_pool_prefork), demand + hint),
        )

    # -------------------------------------------------------------- zygote
    def zygote_spawn(self, argv, env, out, err) -> Tuple[int, bool]:
        """One fork through the zygote; (pid, warm). Raises
        ZygoteUnavailableError when the daemon is gone — the caller
        Popens, the maintenance loop respawns."""
        self._chaos_spawn_point(f"spawn:{argv[3] if len(argv) > 3 else ''}")
        z = self._zygote
        if z is None:
            raise ZygoteUnavailableError("zygote not running")
        try:
            return z.spawn(argv, env, out, err)
        except ZygoteSpawnError as e:
            # The daemon is fine; the fork hit resource pressure. Fall
            # back for THIS spawn without tearing the daemon down.
            raise ZygoteUnavailableError(f"zygote fork failed: {e}") from e
        except Exception as e:
            self._note_zygote_failure(e)
            raise ZygoteUnavailableError(f"zygote spawn failed: {e!r}") from e

    def zygote_exit_code(self, pid: int) -> Optional[int]:
        """How a zygote-forked worker ended (the daemon reaps them, so only
        it knows); None when it cannot say. Never raises: this is asked
        while a death is being reported."""
        z = self._zygote
        if z is None:
            return None
        try:
            return z.exit_code(pid)
        except Exception:  # lint: swallow-ok(the daemon may be gone too; the death is reported without the code)
            return None

    def zygote_spawn_batch(self, specs: List[dict]) -> List[Tuple[int, bool]]:
        """N forks, one socket round trip (refill storms coalesce)."""
        self._chaos_spawn_point(f"batch:{len(specs)}")
        z = self._zygote
        if z is None:
            raise ZygoteUnavailableError("zygote not running")
        try:
            return z.spawn_batch(specs)
        except ZygoteSpawnError as e:
            raise ZygoteUnavailableError(f"zygote fork failed: {e}") from e
        except Exception as e:
            self._note_zygote_failure(e)
            raise ZygoteUnavailableError(f"zygote batch failed: {e!r}") from e

    def _chaos_spawn_point(self, detail: str) -> None:
        rule = _chaos_inject("zygote.spawn", detail)
        if rule is None:
            return
        if rule.action == "kill":
            # Kill the zygote DAEMON (not this raylet): the daemon-death
            # failure mode the respawn path must absorb — the in-flight
            # spawn fails over to Popen, the maintenance loop detects the
            # corpse, respawns, and rebuilds the parked pool.
            proc = self._zygote_proc
            if proc is not None and proc.poll() is None:
                proc.kill()
        elif rule.action == "delay":
            time.sleep(rule.delay_s)
        elif rule.action == "raise":
            raise ZygoteUnavailableError("chaos: injected zygote.spawn failure")

    def _note_zygote_failure(self, err: Exception) -> None:
        """A spawn found the daemon dead: strand nothing — flag for the
        maintenance loop (which logs structured, respawns, and rebuilds
        the pool) instead of the old permanent fall-back-to-Popen."""
        _log.warning("zygote daemon unreachable (%r); scheduling respawn", err)
        _flight_record("pool.zygote_lost", repr(err)[:80])
        self._zygote = None
        with self._lock:
            self._parked = 0
        self._zygote_failed.set()
        self._wake.set()

    def zygote_stats(self) -> dict:
        z = self._zygote
        if z is None:
            return {}
        try:
            return z.stats()
        except Exception:  # lint: swallow-ok(stats probe on a dying daemon; respawn path reacts via spawns)
            return {}

    def _zygote_sock(self) -> str:
        r = self._raylet
        return os.path.join(
            os.path.dirname(r.sock_path) or ".", f"zyg_{r.node_id[:8]}.sock"
        )

    def _boot_zygote(self) -> bool:
        """Starts (or restarts) the zygote daemon and waits for its
        socket. Returns True when a client is ready."""
        r = self._raylet
        sock = self._zygote_sock()
        try:
            log = open(os.path.join(r._log_dir, "zygote.log"), "ab", buffering=0)
            self._zygote_proc = subprocess.Popen(
                [sys.executable, "-m", "ray_tpu.core.zygote", sock],
                stdout=log,
                stderr=log,
            )
            log.close()
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and not self._stop.is_set():
                if self._zygote_proc.poll() is not None:
                    return False  # died at boot; Popen path serves everyone
                if os.path.exists(sock):
                    client = ZygoteClient(sock)
                    try:
                        client.stats()  # the daemon, not a stale socket file
                    except OSError:
                        time.sleep(0.05)
                        continue
                    self._zygote = client
                    self._zygote_failed.clear()
                    return True
                time.sleep(0.05)
        except Exception as e:  # noqa: BLE001
            _log.warning("zygote boot failed: %r", e)
        return False

    def on_fence(self) -> None:
        """Fenced-node pool teardown: the old incarnation's pre-forked
        workers must not outlive it (the same reap contract _fence
        applies to leased/live workers). Parked children are blanks, but
        leaving them would hand the NEXT incarnation processes forked
        under the old life's environment snapshot."""
        z = self._zygote
        if z is None:
            return
        try:
            drained = z.reset()
            if drained:
                _log.info("fence drained %d parked pre-forked workers", drained)
        except Exception as e:
            # The daemon itself may have died with the partition; the
            # maintenance loop respawns it either way.
            self._note_zygote_failure(e)

    # -------------------------------------------------------------- stats
    def stats(self) -> dict:
        """Pool health snapshot (heartbeat stats / debug_state / `ray-tpu
        status --verbose`). No I/O: everything here is cached state — the
        heartbeat loop must never wait on the zygote daemon."""
        r = self._raylet
        with r._workers_lock:
            idle = 0
            ready = 0
            for lst in r._idle.values():
                idle += len(lst)
                for wid in lst:
                    w = r._workers.get(wid)
                    if w is not None and w.ready:
                        ready += 1
        with self._lock:
            hits = dict(self._hits)
            misses = dict(self._misses)
            respawns = self._respawns
            parked = self._parked
        target = self.target()
        return {
            "idle": idle,
            "ready": ready,
            "preforked": parked,
            "target": target,
            "refill_lag": max(0, target - idle),
            "hits": hits,
            "misses": misses,
            "zygote_alive": self._zygote is not None,
            "zygote_respawns": respawns,
        }

    # -------------------------------------------------------- maintenance
    def _maintenance(self) -> None:
        """The standing pool loop: zygote liveness/respawn, idle-pool
        refill toward the forecast target, parked-pool top-up, gauges.
        Runs even with RAY_TPU_WORKER_POOL=0 for zygote lifecycle (the
        one-shot prestart semantics need the daemon too); only the
        refill/prefork sizing is gated."""
        first = True
        while not self._stop.is_set():
            if not first:
                self._wake.wait(timeout=CONFIG.worker_pool_interval_s)
                self._wake.clear()
                # Pacing floor: demand notes wake this loop on every
                # pop, so under a steady task load the wake is always
                # set — without a minimum gap the loop would spin
                # back-to-back rounds, contending for the workers lock
                # with the very dispatch path it serves.
                self._stop.wait(0.1)
                if self._stop.is_set():
                    return
            try:
                self._maintain_once(first)
            except Exception as e:  # noqa: BLE001
                # The pool loop must survive anything — a dead loop
                # silently reverts every launch to cold-spawn.
                _log.warning("pool maintenance round failed: %r", e)
            first = False

    def _maintain_once(self, first: bool) -> None:
        r = self._raylet
        # 1. Zygote liveness. A dead daemon used to strand the pool
        # silently (spawns Popen'd forever); now it respawns, counted
        # and flight-recorded, and the parked pool is rebuilt below.
        if CONFIG.worker_zygote:
            proc = self._zygote_proc
            died = (
                self._zygote_failed.is_set()
                or (proc is not None and proc.poll() is not None)
            )
            if died:
                self._zygote = None
            if (
                died
                and not self._stop.is_set()
                and time.monotonic() >= self._respawn_not_before
            ):
                _log.warning(
                    "zygote daemon died (exit %s): respawning and rebuilding "
                    "the prestart pool",
                    proc.poll() if proc is not None else "?",
                )
                _flight_record("pool.zygote_respawn", r.node_id[:12])
                if proc is not None and proc.poll() is None:
                    # Flagged unreachable but the process lingers (wedged
                    # / timed out under load): kill it before respawning
                    # or TWO daemons would race for the socket path and
                    # the old one's parked children would leak.
                    self._end_zygote()
                if self._boot_zygote():
                    with self._lock:
                        self._respawns += 1
                    imet.ZYGOTE_RESPAWNS.inc()
                    self._respawn_backoff_s = 1.0
                else:
                    self._respawn_not_before = (
                        time.monotonic() + self._respawn_backoff_s
                    )
                    self._respawn_backoff_s = min(
                        30.0, self._respawn_backoff_s * 2
                    )
            elif proc is None:
                self._boot_zygote()  # first boot
        if first:
            # One-shot prestart (PR-1 semantics): bring the idle pool to
            # the configured floor before the first task burst — in one
            # go, bypassing the demand pacing gates.
            self._refill(self._prestart, force=True)
            if CONFIG.worker_pool:
                self._ensure_prefork()
            self._update_gauges()
            return
        if not CONFIG.worker_pool:
            self._update_gauges()
            return
        # 2. Refill the live idle pool toward the forecast target.
        self._refill(self.target())
        # 3. Top the zygote's parked pool back up.
        self._ensure_prefork()
        # 4. Retire surplus idle workers once demand decays (forecast
        # TTL expired, rate window drained): a storm-sized pool must not
        # hoard processes forever. Gentle — a couple per round, with
        # slack so a brief lull doesn't churn the pool.
        surplus = -self.target() - 2
        with r._workers_lock:
            surplus += sum(len(v) for v in r._idle.values())
        if surplus > 0:
            r._retire_idle(min(surplus, 2))
        self._update_gauges()

    def _refill(self, target: int, force: bool = False) -> None:
        """Tops the idle pool up toward `target`. `force` (the one-shot
        boot prestart) skips the demand pacing gates — rt.init's
        num_workers floor must be there BEFORE the first burst, not
        trickle in at 1/s."""
        r = self._raylet
        if self._zygote is None and not force:
            # Zygote down (booting / respawning): refilling through
            # Popen at ~300 ms a head would just steal CPU from the
            # demand-path spawns already serving the storm — hold the
            # pool at its configured floor until the daemon is back.
            target = min(target, self._prestart)
        with r._workers_lock:
            idle = sum(len(v) for v in r._idle.values())
        # Bounded per round: one giant batch would occupy the
        # single-threaded zygote for the whole storm (demand-path forks
        # queue behind it); the loop re-runs immediately while demand
        # persists, so sustained storms still fill. The boot prestart
        # (force) has no storm to contend with and fills in one go.
        short = (target - idle) if force else min(target - idle, 8)
        if short <= 0:
            return
        if force:
            spawned = r._prestart_idle(short)
            if spawned:
                _flight_record("pool.refill", (spawned, target))
                r._sched_wake.set()
            return
        now = time.monotonic()
        with self._lock:
            missing = now - self._last_miss < 2.0
            popping = now - self._last_pop < 2.0
            hinted = self._hint > 0
        if not missing:
            # No recent cold spawn: demand is being served warm.
            if popping:
                # Mid-storm with inventory still holding: rebuilding NOW
                # would steal the (single-core CI box's) CPU from the
                # very launches the pool is serving, inflating their
                # tail. If inventory runs out, misses flip the refill to
                # full rate within a round.
                return
            if not hinted:
                # Quiet pool, no declared demand: rebuild as a TRICKLE —
                # one worker per second.
                if now - self._last_trickle < 1.0:
                    return
                self._last_trickle = now
                short = 1
            # hinted + quiet: pre-provisioning for declared demand
            # (forecast) runs at full rate — that fill IS the point.
        t0 = time.perf_counter()
        spawned = r._prestart_idle(short)
        if spawned:
            _flight_record("pool.refill", (spawned, target))
            r._sched_wake.set()  # fresh pool may unblock queued work
            _log.debug(
                "pool refill: +%d idle workers in %.1f ms (target %d)",
                spawned, (time.perf_counter() - t0) * 1e3, target,
            )

    def _ensure_prefork(self) -> None:
        z = self._zygote
        target = self._prefork_target()
        if z is None or target < 0:
            return
        try:
            reply = z.ensure_pool(target)
            with self._lock:
                self._parked = int(reply.get("parked", 0))
        except Exception as e:
            self._note_zygote_failure(e)

    def _update_gauges(self) -> None:
        r = self._raylet
        with r._workers_lock:
            idle = sum(len(v) for v in r._idle.values())
        with self._lock:
            parked = self._parked
        target = self.target()
        imet.WORKER_POOL_SIZE.set(idle, tier="idle")
        imet.WORKER_POOL_SIZE.set(parked, tier="prefork")
        imet.WORKER_POOL_TARGET.set(target)
        imet.WORKER_POOL_REFILL_LAG.set(max(0, target - idle))
