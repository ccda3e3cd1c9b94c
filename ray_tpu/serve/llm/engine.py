"""Continuous-batching inference engine.

One resident loop per replica owns a fixed set of decode SLOTS (the
compiled step's batch width). Requests join a slot the moment one frees
up — token-level scheduling, not request-level: a finishing sequence
leaves the batch between two decode steps and an admitted prefill takes
its slot for the next step (Orca's iteration-level scheduling; vLLM's
engine loop). Prefill admission is interleaved against a token budget so
a burst of long prompts cannot starve decode latency for running
sequences.

Admission control is synchronous reject-with-backpressure: submit()
either reserves KV pages for the whole prompt or raises
KVPoolExhaustedError/BackpressureError (typed) immediately — the caller
sheds load instead of queueing into a pool that cannot hold it.

Token emission is push-based via per-request sinks; generate() adapts a
sink to the blocking iterator the serve streaming path consumes. A
dropped consumer cancels the request: its pages and slot are reclaimed
within one decode step (the cancel queue drains at the top of every loop
iteration).

The engine decides a step at once and may deliver it late. What a token
means for the schedule (counts, done-ness, the slot and the pages given
back) is settled under the lock the moment the step's result is there; the
sink calls are queued in order and made by the loop thread outside the lock.
For a model that announces its launches (model.py: the `launched` callable
on StepTokens / PromptTokens, which PagedLM calls between its dispatch and
its wait) a decode step's sink calls are made from that hook of the NEXT
executable, so the streams they wake run while the chip works and not in
front of the dispatch. Everything else (a prefill's first token, an error, a
done with nothing left to launch) goes out at once, behind whatever is
queued: a stream sees `tok ... tok, done | error` in the order decided. A
model that announces nothing is delivered to at the end of each step, as
ever; which of the two a model is, the engine learns from the hook firing.

Two decode steps in flight. A model whose `decode` can launch without
waiting (it returns a result that is not read yet: model.py, PendingTokens,
to a StepTokens that asks with `deferred`) is launched step N+1 before step
N is read. Greedy decoding leaves a row's next input token on the device,
so step N+1 is built from counts the host has: a row with a step of its own
in flight runs at its next position, is marked -1 in the host's token vector
(the model takes its token from step N's output, on the device), and is left
out if step N reaches its `max_new`. Only the token's VALUE needs the read:
for the sink calls and for done-ness by `eos_token`. Step N is read, decided
(`llm.decide`) and delivered (`llm.emit`) from the hook of the launch behind
it, step N+1's or a prefill's, while that executable runs; with nothing
left to launch it is read at once. Never more than two steps are in flight,
and one only behind a prefill (its first token is read on the host and goes
into the next step's host vector). Done-ness by `eos_token` is therefore
known one step late: such a row computes one dead step, whose token is
dropped when it is decided (`_decide_locked`: the sequence is finished). A
model that returns a plain list (StubModel, or any adapter that waits) has
one step in flight and the order above, unchanged; which of the two a model
is, the engine learns from what `decode` returned.
"""

from __future__ import annotations

import collections
import itertools
import logging
import os
import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Callable, Deque, Dict, List, Optional, Sequence

from ...chaos.controller import kill_now as _chaos_kill
from ...chaos.controller import maybe_inject as _chaos_inject
from ...exceptions import (
    BackpressureError,
    EngineFailedError,
    KVPoolExhaustedError,
    RayTpuError,
)
from ... import tracing as _tracing
from ...utils import internal_metrics as imet
from ...utils import lock_order
from .kv_cache import PagedKVAllocator, SeqPages
from .model import PromptTokens, StepTokens

logger = logging.getLogger(__name__)

Sink = Callable[[str, object], None]  # events: "tok" int | "done" str | "error" exc


def _env_int(name: str, default: int) -> int:
    try:
        return int(os.environ.get(name, "") or default)
    except ValueError:
        return default


@dataclass(frozen=True)
class EngineConfig:
    page_tokens: int = field(default_factory=lambda: _env_int("RAY_TPU_KV_PAGE_TOKENS", 16))
    pool_pages: int = field(default_factory=lambda: _env_int("RAY_TPU_KV_POOL_PAGES", 128))
    # Prompt tokens admitted (prefilled) per loop iteration; running
    # sequences get a decode step between admission rounds regardless.
    prefill_token_budget: int = field(
        default_factory=lambda: _env_int("RAY_TPU_LLM_PREFILL_BUDGET", 256)
    )
    max_queue: int = 64
    max_new_tokens: int = 32
    eos_token: Optional[int] = None


class _Seq:
    __slots__ = (
        "rid", "prompt", "max_new", "pages", "sink", "slot",
        "last_token", "n_out", "cancelled", "finished", "t_submit", "t_first",
        "trace", "waiting_ahead", "ahead",
    )

    def __init__(self, rid: int, prompt: List[int], max_new: int, pages: SeqPages, sink: Sink):
        self.rid = rid
        self.prompt = prompt
        self.max_new = max_new
        self.pages = pages
        self.sink = sink
        self.slot: Optional[int] = None
        self.last_token = 0
        self.n_out = 0
        self.cancelled = False
        self.finished = False
        self.t_submit = time.monotonic()
        self.t_first = 0.0
        # The submitting thread's span context (None with tracing off):
        # spans the engine thread records for this request parent to it.
        self.trace = _tracing.current_context()
        self.waiting_ahead = 0
        # Decode steps launched with this sequence in them and not read yet: 0 or 1.
        self.ahead = 0

    def write_pos(self) -> int:
        """Cache position the NEXT decode step to be launched writes (the
        k/v of its input token): prompt positions [0, len) are prefilled,
        generated token i lands at len(prompt) + i, and a step in flight
        (`ahead`) has taken the position before."""
        return len(self.prompt) + self.n_out - 1 + self.ahead


class _Flight:
    """A decode step launched and not read yet: its launch ordinal, the
    sequences it ran (as launched: one of them may have finished since), its
    result still on its way (PendingTokens), the pages its live lengths cover
    and the instant of its launch."""

    __slots__ = ("step", "batch", "result", "live_pages", "t0")

    def __init__(self, step: int, batch: List[_Seq], result, live_pages: int, t0: float):
        self.step, self.batch, self.result, self.live_pages, self.t0 = step, batch, result, live_pages, t0


class InferenceEngine:
    """Schedules sequences over a paged-KV model adapter (serve/llm/model.py
    protocol: `prefill`, `decode`, and the pool-geometry attributes)."""

    def __init__(self, model, config: Optional[EngineConfig] = None, name: str = "llm"):
        self.model = model
        self.config = config or EngineConfig()
        self.name = name
        cfg = self.config
        labels = {"deployment": name}
        self._m_step = imet.SERVE_DECODE_STEP.labels(**labels)
        self._m_tps = imet.SERVE_TOKENS_PER_S.labels(**labels)
        self._m_shed = imet.SERVE_REQUESTS_SHED.labels(**labels)
        self.alloc = PagedKVAllocator(
            cfg.pool_pages,
            cfg.page_tokens,
            metrics={
                "used": imet.KV_PAGES_USED.labels(**labels),
                "total": imet.KV_PAGES_TOTAL.labels(**labels),
                "hits": imet.PREFIX_CACHE_HITS.labels(**labels),
                "misses": imet.PREFIX_CACHE_MISSES.labels(**labels),
            },
            # A model whose page is one sequence's recurrent state says so: no page of it is a prefix.
            share_prefixes=getattr(model, "shares_prefix_pages", True),
        )
        self._rid = itertools.count(1)
        self._lock = lock_order.tracked_lock("serve.llm.engine")
        self._cond = threading.Condition(self._lock)
        self._waiting: Deque[_Seq] = collections.deque()
        self._slots: List[Optional[_Seq]] = [None] * model.max_slots
        self._by_rid: Dict[int, _Seq] = {}
        self._cancels: Deque[int] = collections.deque()
        self._stop = False
        # Sink calls decided and not yet made, (seq, event, payload) in the
        # order a stream must see them; the loop thread's alone.
        self._pending: List[tuple] = []
        # Whether the model announces its launches: its hook fired and no
        # call since has come back without it. Only then is a step's
        # delivery left for the next launch.
        self._launches = False
        # The decode step launched and not yet read (a model that defers: _Flight), and whether the step
        # last read failed: a step launched behind a failed one is dropped unread.
        self._flight: Optional[_Flight] = None
        self._flight_failed = False
        # Set once by _fail(): the model lost state it cannot rebuild.
        self.failed: Optional[EngineFailedError] = None
        self.shed_total = 0
        self.tokens_emitted = 0
        self.decode_steps = 0  # completed (read and decided)
        self.decode_launches = 0  # launched: a step's ordinal on its spans is its launch's
        self._tok_window = 0
        self._t_window = time.monotonic()
        # Stage clocks (stats()["clocks"]): seconds of time.monotonic(),
        # cumulative, written by the loop thread. The loop is in exactly one
        # stage from its start to its stop (`_enter` leaves one and enters
        # the next at one reading of the clock), so the stages' seconds add
        # up to loop.s; `_stage` names the one it is in right now, so
        # stats() can count the part of it already spent.
        self._clk = {
            "queue_wait": {"n": 0, "s": 0.0},
            "first_token": {"n": 0, "s": 0.0},
            "admit": {"s": 0.0},
            "prefill": {"n": 0, "s": 0.0, "tokens": 0, "computed_tokens": 0},
            "batch": {"s": 0.0},
            "decode": {"n": 0, "s": 0.0, "chained": 0},
            "emit": {"s": 0.0},
            "deliver": {"n": 0, "under_step": 0},
            "decode.kv_pages": {"live": 0, "table": 0},
            "idle": {"s": 0.0},
        }
        self._clk_lock = threading.Lock()
        self._t_loop = (time.monotonic(), None)  # loop start, loop end
        self._stage: Optional[tuple] = ("admit", self._t_loop[0])  # (clock name, t0)
        self._thread = threading.Thread(
            target=self._loop, name=f"llm-engine-{name}", daemon=True
        )
        self._thread.start()

    # ---------------------------------------------------------- admission

    def submit(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        *,
        sink: Sink,
    ) -> int:
        """Reserves pages and enqueues; raises BackpressureError (shed)
        when the queue or the page pool cannot take the request."""
        prompt = PromptTokens((int(t) for t in prompt), self._launched)
        if not prompt:
            raise ValueError("empty prompt")
        max_new = int(max_new_tokens or self.config.max_new_tokens)
        cap = self.model.max_pages_per_seq * self.config.page_tokens
        if len(prompt) + max_new - 1 > cap:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new}) exceeds "
                f"per-sequence KV capacity ({cap} positions)"
            )
        with self._cond:
            if self.failed is not None:
                raise self.failed
            if self._stop:
                raise RuntimeError("engine is shut down")
            if len(self._waiting) >= self.config.max_queue:
                self.shed_total += 1
                self._m_shed.inc()
                raise BackpressureError(
                    reason=f"admission queue full ({self.config.max_queue})"
                )
            try:
                pages = self.alloc.allocate(prompt)
            except KVPoolExhaustedError:
                self.shed_total += 1
                self._m_shed.inc()
                raise
            rid = next(self._rid)
            seq = _Seq(rid, prompt, max_new, pages, sink)
            seq.waiting_ahead = len(self._waiting)
            self._by_rid[rid] = seq
            self._waiting.append(seq)
            self._cond.notify()
            return rid

    def generate(
        self,
        prompt: Sequence[int],
        max_new_tokens: Optional[int] = None,
        on_submit=None,
    ):
        """Blocking token iterator over a submitted request — the shape
        the serve streaming path consumes. Closing the generator (client
        disconnect) cancels the request and frees its pages. `on_submit`
        (if given) receives the request id once admission succeeds, so
        callers can cancel() from another thread while this iterator is
        blocked producing."""
        q: "queue.SimpleQueue" = queue.SimpleQueue()
        rid = self.submit(
            prompt, max_new_tokens, sink=lambda ev, val: q.put((ev, val))
        )
        if on_submit is not None:
            on_submit(rid)

        def _iter():
            try:
                while True:
                    ev, val = q.get()
                    if ev == "tok":
                        yield val
                    elif ev == "done":
                        return
                    else:
                        raise val
            finally:
                self.cancel(rid)

        return _iter()

    def cancel(self, rid: int) -> None:
        """Requests removal; the loop reclaims the slot and pages at the
        top of its next iteration (<= one decode step later). Idempotent,
        and a no-op for already-finished requests."""
        with self._cond:
            seq = self._by_rid.get(rid)
            if seq is None or seq.finished:
                return
            seq.cancelled = True
            self._cancels.append(rid)
            self._cond.notify()

    # --------------------------------------------------------------- loop

    def _finish_locked(self, seq: _Seq, event: str, payload) -> None:
        seq.finished = True
        if seq.slot is not None:
            self._slots[seq.slot] = None
            seq.slot = None
        self._by_rid.pop(seq.rid, None)
        self.alloc.release(seq.pages)
        self._pending.append((seq, event, payload))

    def _drain_cancels_locked(self) -> None:
        while self._cancels:
            rid = self._cancels.popleft()
            seq = self._by_rid.get(rid)
            if seq is None:
                continue
            try:
                self._waiting.remove(seq)
            except ValueError:
                pass  # not waiting: running in a slot (or already gone)
            self._finish_locked(seq, "done", "cancelled")

    def _pick_admissions_locked(self) -> List[_Seq]:
        """Pops waiting sequences into free slots up to the prefill token
        budget. Slots are reserved here (under the lock); the prefill
        compute itself runs outside it. A sequence is charged its uncached
        tokens, which is what its prefill computes (to the chunk: PagedLM
        computes the chunks holding them and reads the cached ones)."""
        budget = self.config.prefill_token_budget
        admitted: List[_Seq] = []
        now = 0.0
        while self._waiting and None in self._slots:
            seq = self._waiting[0]
            new_tokens = len(seq.prompt) - seq.pages.cached_tokens
            if admitted and new_tokens > budget:
                break  # interleave: let running sequences decode first
            self._waiting.popleft()
            slot = self._slots.index(None)
            seq.slot = slot
            seq.prompt.slot = slot  # its decode row from here on: a model with a fixed state a sequence keeps it by that row
            self._slots[slot] = seq
            budget -= new_tokens
            admitted.append(seq)
            now = now or time.monotonic()
            clk = self._clk["queue_wait"]
            clk["n"] += 1
            clk["s"] += now - seq.t_submit
            if seq.trace is not None:
                _tracing.record_span(
                    "llm.queue",
                    int(seq.t_submit * 1e9),
                    int(now * 1e9),
                    {
                        "rid": seq.rid,
                        "prompt_tokens": len(seq.prompt),
                        "cached_tokens": seq.pages.cached_tokens,
                        "waiting_ahead": seq.waiting_ahead,
                    },
                    parent=seq.trace,
                )
        return admitted

    def _finalize_admission_locked(self, seq: _Seq, tok: Optional[int], err) -> None:
        if seq.finished:
            return  # cancelled and reaped while prefilling
        if err is not None:
            self._finish_locked(seq, "error", _typed(err))
            return
        if seq.cancelled:
            self._finish_locked(seq, "done", "cancelled")
            return
        self.alloc.commit(seq.pages, seq.prompt)
        seq.last_token = tok
        seq.t_first = time.monotonic()
        clk = self._clk["first_token"]
        clk["n"] += 1
        clk["s"] += seq.t_first - seq.t_submit
        if seq.trace is not None:
            t_ns = int(seq.t_first * 1e9)
            _tracing.record_span("llm.first_token", t_ns, t_ns, {"rid": seq.rid}, parent=seq.trace)
        self._emit_locked(seq, tok)
        if self._done_after_emit(seq, tok):
            self._finish_locked(seq, "done", "stop")

    def _emit_locked(self, seq: _Seq, tok: int) -> None:
        seq.n_out += 1
        self.tokens_emitted += 1
        self._tok_window += 1
        self._pending.append((seq, "tok", int(tok)))

    def _deliver(self, under_step: bool = False) -> None:
        """Makes the queued sink calls, in the order they were decided. The
        loop thread, outside the lock: a `put` wakes a stream's thread, which
        may call cancel() or submit() at once."""
        pending, self._pending = self._pending, []
        for seq, event, payload in pending:
            try:
                seq.sink(event, payload)
            except Exception:
                # lint: swallow-ok(consumer gone: a token's sequence is
                # cancelled and freed on the next iteration, a finished one
                # is already torn down)
                self.cancel(seq.rid)
        clk = self._clk["deliver"]
        clk["n"] += len(pending)
        if under_step:
            clk["under_step"] += len(pending)

    def _emit(self, under_step: bool = False) -> None:
        """A decode step's deliveries under a span of their own: a child of
        `llm.step` when made at once, of `llm.prefill` / `llm.decode` when
        made from the launch hook."""
        tokens = sum(1 for _seq, event, _payload in self._pending if event == "tok")
        with _tracing.span("llm.emit", {"tokens": tokens, "under_step": int(under_step)}, device=True):
            self._deliver(under_step)

    def _deliver_unless(self, launching: bool) -> None:
        """Delivers what is queued now, unless an executable is about to be
        launched (`launching`) by a model that says when it has been: then
        its hook delivers, under the step in flight."""
        if self._pending and not (launching and self._launches):
            self._deliver()

    def _launched(self) -> None:
        """The model's hook (StepTokens / PromptTokens `launched`): the
        executable this call runs has been dispatched and the thread is about
        to block for its result, or to return it unread. The loop thread,
        inside model.prefill / model.decode, no lock of the engine or of the
        model held. A decode step still in flight is read, decided and
        delivered here, under the executable just launched; else what the
        step before left queued is delivered. The deliveries' seconds go to
        the `emit` clock, not to the stage they interrupt."""
        self._launches = True
        if self._flight is not None:
            self._land(under_step=True)
        elif self._pending:
            stage = self._stage[0]
            self._enter("emit")
            self._emit(under_step=True)
            self._enter(stage)

    def _land(self, under_step: bool) -> None:
        """Reads the decode step in flight (the wait lies in the model's
        `llm.decode.wait`, with the `step` of the step read; its seconds go to
        the `decode` clock), decides it and makes its sink calls (the `emit`
        clock), then goes back to the stage it interrupted. `under_step`: a
        newer executable runs meanwhile. A read that fails fails the step's
        batch fast, as a step that raised does, and marks the step launched
        behind it to be dropped; a pool lost there stops the engine."""
        flight, self._flight = self._flight, None
        stage = self._stage[0]
        self._enter("decode")
        tokens, err = None, None
        try:
            tokens = flight.result.resolve()
        except EngineFailedError as e:
            self._enter(stage)
            self._fail(e)
            return
        except Exception as e:  # noqa: BLE001 - batch fail-fast, loop survives
            err = e
        step_ms = (self._enter("emit") - flight.t0) * 1000.0
        self._flight_failed = err is not None
        self._decide(flight.batch, tokens, err, flight.live_pages, step_ms)
        self._emit(under_step)
        self._enter(stage)

    def _decide(self, batch: List[_Seq], tokens, err: Optional[BaseException], live_pages: int, step_ms: float) -> None:
        """`llm.decide`, under the lock: a decode step that came back is
        decided (`_decide_locked`), one that raised fails every sequence that
        was in it, never wedging: pages free, slots recycle, the engine keeps
        serving whatever arrives next."""
        with _tracing.span("llm.decide", {"tokens": len(batch)}, device=True), self._cond:
            for seq in batch:
                seq.ahead = 0  # read: before a token is counted, so that `write_pos` moves by one
            if err is None:
                self._decide_locked(batch, tokens, live_pages, step_ms)
                return
            logger.warning("decode step failed on %s: %r", self.name, err)
            for seq in batch:
                if not seq.finished:
                    self._finish_locked(seq, "error", _typed(err))

    def _returned(self) -> None:
        """A model call came back. With deliveries still queued its hook did
        not fire: this model announces nothing (until it does), and what was
        left for its launch goes out now."""
        if self._pending:
            self._launches = False
            self._deliver()

    def _done_after_emit(self, seq: _Seq, tok: int) -> bool:
        if seq.n_out >= seq.max_new:
            return True
        eos = self.config.eos_token
        return eos is not None and int(tok) == int(eos)

    def _fail(self, err: EngineFailedError) -> None:
        """Stops serving: every in-flight and later request gets `err`.
        Carrying on would answer each of them from a deleted buffer while
        the replica looked alive."""
        logger.error("engine %s failed and stopped: %s", self.name, err)
        self._flight = None  # what was launched is never read
        with self._cond:
            self.failed = err
            self._stop = True
            for seq in list(self._by_rid.values()):
                self._finish_locked(seq, "error", err)
        self._deliver()

    def _loop(self) -> None:
        try:
            self._run()
        finally:
            self._enter(None)

    def _enter(self, clock: Optional[str]) -> float:
        """The loop leaves the stage it is in, whose seconds go to its
        clock, and enters `clock` (None: the loop ends) at the same reading
        of time.monotonic(), which it returns."""
        now = time.monotonic()
        with self._clk_lock:  # stats() sees the stage open or its seconds, never both
            name, t0 = self._stage
            self._clk[name]["s"] += now - t0
            if clock is None:
                self._stage, self._t_loop = None, (self._t_loop[0], now)
            else:
                self._stage = (clock, now)
        return now

    def _run(self) -> None:
        """From here to the return every instant of this thread lies under
        one of three spans, and in one stage of the clocks: `llm.admit` (the
        locked section that reaps cancels and fills free slots; the lock is
        the one submit() and cancel() take), then `llm.idle` (nothing to
        run: one wait for a submit, a cancel or the stop) or `llm.step`
        (something live, or a step in flight whose rows were all cancelled:
        it is read there, with nothing to launch)."""
        while True:
            with _tracing.span("llm.admit", device=True) as sp:
                self._enter("admit")  # inside the span: what lies between two spans is a hole in the record
                with self._cond:
                    self._drain_cancels_locked()
                    stop = self._stop
                    if stop:
                        for seq in list(self._by_rid.values()):
                            self._finish_locked(seq, "error", RayTpuError("engine shut down"))
                    admitted = [] if stop else self._pick_admissions_locked()
                    live = sum(1 for s in self._slots if s is not None)
                    waiting = len(self._waiting)
                # With something live the step below launches an executable; with nothing, or at the stop, none will be.
                self._deliver_unless(launching=bool(live) and not stop)
                _tracing.add_attrs(sp, waiting=waiting, admitted=len(admitted), live=live)
            if stop:
                return
            if not live and self._flight is None:
                # Nothing waits (it would have been admitted) and nothing
                # runs. The test is made again under the lock the wait
                # gives up: a submit between the two sections is not slept on.
                with _tracing.span("llm.idle", device=True):
                    self._enter("idle")
                    with self._cond:
                        if not (self._stop or self._waiting or self._cancels):
                            self._m_tps.set(0.0)
                            self._cond.wait(timeout=1.0)
                continue
            # One iteration with work in it, from the admissions to the
            # last emit: prefills, one decode step, the emits.
            with _tracing.span(
                "llm.step", {"admitted": len(admitted), "live": live}, device=True
            ):
                if not self._step(admitted):
                    return

    def _step(self, admitted: List[_Seq]) -> bool:
        """Prefills `admitted`, launches one decode step over every live slot
        and settles the step before it, or this one if the model waited for
        it. False: the engine failed and the loop ends.

        Settling has two halves. Deciding (`llm.decide`, under the lock, the
        moment the result is there): each token's count, its sequence's
        done-ness, the slot and pages of a finished one given back, so the
        next `llm.admit` finds them free. Delivering (`llm.emit`, outside the
        lock): the sink calls, queued in that order. For a model that
        announces nothing the second follows the first at once. For one that
        announces its launches the step's deliveries wait for the next
        executable's launch and are made from its hook, under `llm.prefill` or
        `llm.decode` (`_launched`); a prefill's first token, a failed step's
        errors and whatever finds nothing left to launch go out at once. For
        one that also returns its result unread, the step is read there too:
        wait, decide and deliver all lie under the launch behind it (`_land`)."""
        T = self.config.page_tokens
        # Prefill outside the lock (jit-compiled, prompt-sized work):
        # submit/cancel stay responsive while prompts burn in.
        for seq in admitted:
            tok, err = None, None
            clk = self._clk["prefill"]
            clk["n"] += 1
            clk["tokens"] += len(seq.prompt)
            self._enter("prefill")
            attrs = {
                "rid": seq.rid,
                "prompt_tokens": len(seq.prompt),
                "cached_tokens": seq.pages.cached_tokens,
            }
            try:
                with _tracing.span("llm.prefill", attrs, device=True, parent=seq.trace):
                    tok = self.model.prefill(
                        seq.prompt, seq.pages.pages, seq.pages.cached_tokens
                    )
                    # What the model says it computed (PagedLM: whole
                    # chunks, padding included); a model that does not say
                    # computed the uncached tokens.
                    attrs["computed_tokens"] = getattr(
                        tok, "computed_tokens", len(seq.prompt) - seq.pages.cached_tokens
                    )
                    clk["computed_tokens"] += attrs["computed_tokens"]
                    walked = getattr(tok, "counters", {}).get("prefill_chunks")
                    if walked:  # PagedLM: the span's whole big chunks, then its tail in small ones
                        attrs.update(big_chunks=walked["big"], small_chunks=walked["small"])
                    self._add_counters(tok)
                    tok = int(tok)
                self._returned()
            except EngineFailedError as e:
                self._fail(e)
                return False
            except Exception as e:  # noqa: BLE001 - fail one request, not the loop
                err = e
            if self.failed is not None:
                return False  # the step read under this prefill had lost the pool
            # Its first token is decided and goes out now, behind whatever a prefill that raised before its
            # launch left queued: not behind the other prefills of this iteration (streams that arrive
            # together are admitted together, and each would wait for the last one's prompt).
            self._enter("batch")
            with self._cond:
                self._finalize_admission_locked(seq, tok, err)
            self._deliver()

        self._enter("batch")
        with _tracing.span("llm.batch", device=True) as sp:
            with self._cond:
                # Every live sequence but one that the step in flight takes to its `max_new`: its last token is on its way.
                batch = [s for s in self._slots if s is not None and s.n_out + s.ahead < s.max_new]
                # Grow block tables for sequences crossing a page
                # boundary this step; pool exhaustion here fail-fasts the
                # one sequence (its pages recycle for the rest).
                for seq in list(batch):
                    if seq.write_pos() >= seq.pages.num_pages * T:
                        try:
                            self.alloc.extend(seq.pages)
                        except KVPoolExhaustedError as e:
                            batch.remove(seq)
                            self._finish_locked(seq, "error", e)
                _tracing.add_attrs(sp, live=len(batch))
                step = self.decode_launches + 1  # the launch's ordinal: two in flight do not share one
                tokens = StepTokens([0] * len(self._slots), step, self._launched, deferred=True)
                positions = [-1] * len(self._slots)
                tables: List[List[int]] = [[] for _ in self._slots]
                kv_tokens = live_pages = 0
                page_tokens = self.config.page_tokens
                for seq in batch:
                    # A row of the step in flight: its token is that step's output, which only the device has yet.
                    tokens[seq.slot] = -1 if seq.ahead else seq.last_token
                    positions[seq.slot] = seq.write_pos()
                    tables[seq.slot] = seq.pages.pages
                    kv_tokens += positions[seq.slot] + 1
                    live_pages += -(-(positions[seq.slot] + 1) // page_tokens)
            # The last step's tokens, with no prefill since (its hook would have
            # delivered them), ride under the decode launched below.
            self._deliver_unless(launching=bool(batch))
        if not batch:
            if self._flight is not None:
                self._land(under_step=False)  # nothing to launch behind it: read now
            return self.failed is None

        # Model step runs OUTSIDE the lock: submit/cancel stay
        # responsive for the full decode latency.
        t0 = self._enter("decode")
        chained = self._flight is not None
        self._flight_failed = False
        try:
            rule = _chaos_inject("serve.decode", self.name)
            if rule is not None:
                if rule.action == "delay":
                    time.sleep(rule.delay_s)
                elif rule.action == "kill":
                    _chaos_kill("serve.decode", self.name)
                else:
                    raise RayTpuError(
                        f"chaos: injected decode fault ({self.name})"
                    )
            attrs = {
                "live": len(batch),
                "kv_tokens": kv_tokens,
                "step": step,
                "after_prefill": int(bool(admitted)),
            }
            with _tracing.span("llm.decode", attrs, device=True):
                next_tokens = self.model.decode(tokens, positions, tables)
            step_err: Optional[BaseException] = None
            self.decode_launches += 1
            self._clk["decode"]["chained"] += int(chained)
        except EngineFailedError as e:
            self._fail(e)
            return False
        except Exception as e:  # noqa: BLE001 - batch fail-fast, loop survives
            next_tokens, step_err = None, e
        if self._flight is not None and self.failed is None:
            # No hook read it: the launch raised before it, or the model defers and announces nothing.
            self._land(under_step=step_err is None)
        if self.failed is not None:
            return False  # the step read under this launch had lost the pool
        if step_err is None and hasattr(next_tokens, "resolve"):
            # Launched and not waited for: read from the hook of the next launch. Behind a step
            # whose read failed it ran on that step's pool and tokens: dropped unread.
            if not self._flight_failed:
                self._flight = _Flight(step, batch, next_tokens, live_pages, t0)
                for seq in batch:
                    seq.ahead = 1
            self._returned()
            return True

        step_ms = (self._enter("emit") - t0) * 1000.0
        if step_err is None:
            self._returned()
        self._decide(batch, next_tokens, step_err, live_pages, step_ms)
        # A failed step's errors at once, behind what its missing launch left
        # queued. A step's tokens at once to a model that announces nothing;
        # else they wait for the next launch (`_launched`), or for the
        # `llm.admit` that finds nothing left to launch.
        if step_err is not None or not self._launches:
            self._emit()
        return True

    def _decide_locked(self, batch: List[_Seq], next_tokens, live_pages: int, step_ms: float) -> None:
        """A completed decode step's bookkeeping: its counters, and for each
        sequence its token, whether it is done, and its slot and pages back
        if so. The sink calls are queued, not made."""
        self.decode_steps += 1
        self._clk["decode"]["n"] += 1
        pages = self._clk["decode.kv_pages"]
        pages["live"] += live_pages
        pages["table"] += len(self._slots) * self.model.max_pages_per_seq
        self._add_counters(next_tokens)
        self._m_step.observe(step_ms)
        for seq in batch:
            if seq.finished or seq.cancelled:
                continue
            tok = int(next_tokens[seq.slot])
            seq.last_token = tok
            self._emit_locked(seq, tok)
            if self._done_after_emit(seq, tok):
                self._finish_locked(seq, "done", "stop")
        now = time.monotonic()
        dt = now - self._t_window
        if dt >= 0.5:
            self._m_tps.set(self._tok_window / dt)
            self._tok_window = 0
            self._t_window = now

    def _add_counters(self, result) -> None:
        """What the model says its router, windows or states did with a step
        (PagedLM's DecodeTokens and PrefillToken: `counters`), added up by
        name; a model that says nothing adds no clock."""
        for name, counts in getattr(result, "counters", {}).items():
            clk = self._clk.setdefault(name, dict.fromkeys(counts, 0))
            for key, n in counts.items():
                clk[key] += n

    # -------------------------------------------------------------- admin

    def stats(self) -> dict:
        with self._cond:
            running = sum(1 for s in self._slots if s is not None)
            return {
                "running": running,
                "waiting": len(self._waiting),
                "slots": len(self._slots),
                "tokens_emitted": self.tokens_emitted,
                "decode_steps": self.decode_steps,
                "shed_total": self.shed_total,
                "failed": repr(self.failed) if self.failed is not None else None,
                "kv": self.alloc.stats(),
                "clocks": self._clocks_locked(),
            }

    def _clocks_locked(self) -> dict:
        """Where the loop's time went since the engine started (seconds of
        time.monotonic(), cumulative). queue_wait: submit -> slot, per
        admitted request; first_token: submit -> first emit (the engine's
        own TTFT); the loop's stages, each from its start to the next one's:
        admit (the locked section at the top of an iteration: cancels
        reaped, free slots filled), prefill / decode (inside model.prefill /
        model.decode), batch (the locked sections that finalize an admission
        behind its prefill and send its first token, and the one that grows
        block tables and builds the step's inputs), emit (a completed
        step decided under the lock, and the sink calls wherever they are
        made: those made from a launch's hook are taken out of the prefill /
        decode they interrupt; a step read from such a hook has its wait
        booked to decode and its decide and sink calls to emit); decode.n:
        the decode steps completed (read and decided), decode.chained: the
        decode steps dispatched while the decode step before them was still
        unread (two in flight: a model that defers; 0 for one that waits;
        a step behind a prefill is not, the prefill's hook read the step
        before it); deliver: the sink calls made (n) and those of
        them made from a launch's hook, under a step in flight (under_step);
        prefill.tokens: prompt tokens of those calls,
        cached ones included; prefill.computed_tokens: positions their
        executables computed, as the model reports them: the uncached span
        rounded up to whole chunks, cached positions not; prefill_chunks
        (PagedLM): the chunks those spans were walked in, `big` ones of
        `big_rows` positions (transformer.prefill_chunk_rows: a model whose
        weights ask for more rows a pass than the floor) and `small` ones, of
        `rows` computed in all; decode.kv_pages: over the completed decode steps, the
        pages their live lengths cover (what a step must read) against
        slots x pages a sequence (what a step that gathers the block
        tables reads); decode_window (a model with attention windows only):
        kv_read, the K/V positions the steps' live rows see, each layer
        clipped to its window, against kv_live, layers x their lengths;
        decode_experts (a routed model only): touched, the distinct experts
        the steps' rows chose, summed over routed layers, of `held` in as many
        `steps`; decode_state (a model whose cache is a recurrent state only):
        the bytes of state the steps read and wrote (live rows x layers x 2 x
        a state) and the state slots live, summed over `steps`; prefill_state
        (the same): the chunks its prefills computed and how many of them were
        carried a state in by their predecessor; prefill_experts (a routed
        model only): the rows its prefills' chunks handed their routed layers'
        experts (chunk rows x routed layers, over `chunks`) and those of them
        that went through the grouped product; a KDA stack (states AND K/V
        pages) counts both of those and decode_kv beside them: the K/V bytes
        and positions its steps' live rows read in their softmax layers;
        decode_experts.picks / held_picks: the steps' rows x choices a routed
        layer, and how many of them fell on experts held here (all, unless
        the model holds one chip's share of them); loop.s: wall time of the loop, loop.idle_s the part of
        it waiting with nothing to do. loop.s = idle_s + admit.s +
        prefill.s + batch.s + decode.s + emit.s, and loop.s - idle_s -
        prefill.s - decode.s is the engine's own host time. A stage in
        progress counts up to now, so two calls bracket a window exactly."""
        with self._clk_lock:
            now = time.monotonic()
            clk = {k: dict(v) for k, v in self._clk.items()}
            stage = self._stage
            t_start, t_end = self._t_loop
        if stage is not None:
            clk[stage[0]]["s"] += max(0.0, now - stage[1])
        clk["loop"] = {"s": (t_end or now) - t_start, "idle_s": clk.pop("idle")["s"]}
        return clk

    def close(self) -> None:
        with self._cond:
            self._stop = True
            self._cond.notify_all()
        self._thread.join(timeout=10.0)


def _typed(err: BaseException) -> BaseException:
    """Errors crossing the streaming boundary keep taxonomy identity;
    anything else wraps so callers always get a RayTpuError subclass."""
    if isinstance(err, RayTpuError):
        return err
    wrapped = RayTpuError(f"{type(err).__name__}: {err}")
    wrapped.__cause__ = err
    return wrapped
