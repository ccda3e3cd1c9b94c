"""Distributed tracing: spans around submit/execute with context propagation.

Re-design of the reference's OpenTelemetry integration (reference:
python/ray/util/tracing/tracing_helper.py:34 _OpenTelemetryProxy, :92
span-injecting decorators around task submission, :165 context carried
inside task specs so worker-side spans parent to the submitting span).
The TPU build keeps the same shape without requiring the opentelemetry
package: spans are plain dicts `{trace_id, span_id, parent_id, name,
start_us, end_us, attrs}`, the ambient context rides a contextvar, task
entries carry `trace_ctx`, and exporters are pluggable — the default
writes JSONL under the session dir so spans from every process (driver,
raylets' workers) merge by trace_id. `collect()` reassembles the tree.

Cross-process causality stitches two ways: parent links (this module's
context propagation) and **flow ids** for the Perfetto exporter's arrows
(observability/perfetto.py). `inject_context()` mints a flow id at
submit time; the submit-side span carries it as `flow_out`, the
executing-side span as `flow_in`, and intermediate hops (the raylet's
schedule span) as `flow_step` — the exporter pairs them into s/t/f
chrome-trace flow events.

Opt-in: `RAY_TPU_TRACING=1` (read once at import; inherited by
daemons/workers) or `tracing.enable(exporter)` in-process. Span open/close
additionally feed the always-on flight recorder
(observability/flight_recorder.py).

Fit for a hot path: a span is timed with one `time.monotonic_ns()` pair
(`t0_ns`, `t1_ns`: CLOCK_MONOTONIC is one clock for every process of a
host), `start_us`/`end_us` derive from a wall anchor taken once per
process, closed spans are buffered in memory and written by `flush()`
(task end in a worker, replica shutdown, `disable()`, atexit, a full
buffer), and with tracing off `span()` is one test of a module flag that
returns a shared no-op. `span(..., device=True)` additionally enters a
`jax.profiler.TraceAnnotation`, tracing on or off, so the same name shows
on the device trace's own clock when one is being taken.
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import contextvars
import itertools
import json
import os
import sys
import threading
import time
import uuid
from typing import Any, Dict, List, Optional

from .observability.flight_recorder import record as _frec

_ctx: "contextvars.ContextVar[Optional[dict]]" = contextvars.ContextVar(
    "ray_tpu_trace_ctx", default=None
)

_lock = threading.Lock()
_exporter: Optional["SpanExporter"] = None
# THE flag every call site tests (through span()/is_enabled()): the env
# toggle as this process was started, then enable()/disable().
_on = os.environ.get("RAY_TPU_TRACING") == "1"
# Wall time of monotonic zero, taken once: start_us/end_us = anchor + t_ns.
_WALL_ANCHOR_US = int(time.time() * 1e6) - time.monotonic_ns() // 1000


class SpanExporter:
    def export(self, span: dict) -> None:  # pragma: no cover - interface
        raise NotImplementedError

    def flush(self) -> None:
        pass

    def shutdown(self) -> None:
        pass


class InMemoryExporter(SpanExporter):
    def __init__(self):
        self.spans: List[dict] = []

    def export(self, span: dict) -> None:
        self.spans.append(span)


class JsonlExporter(SpanExporter):
    """One JSONL file per process under <dir>/; `collect()` merges them.

    Spans are buffered (a deque append per span: no lock, no json, no
    write) and written by flush(): at shutdown(), at atexit for a process
    that exits without calling disable(), and when the buffer passes
    FLUSH_AT — then by a writer thread, so the thread that closed the
    span never stalls for the serialization of thousands (measured: a
    70 ms hole in a load generator). Processes that are killed rather
    than exiting flush where their work ends (worker_proc after each
    task, a serve replica in prepare_shutdown, the raylet from its main
    loop). The file opens at the first flush, so a process that never
    closes a span leaves none."""

    FLUSH_AT = 1024

    def __init__(self, directory: str):
        self.path = os.path.join(directory, f"spans_{os.getpid()}.jsonl")
        self._buf: "collections.deque[dict]" = collections.deque()
        self._f = None
        self._flock = threading.Lock()
        self._full = threading.Event()
        self._writer: Optional[threading.Thread] = None
        atexit.register(self.shutdown)

    def export(self, span: dict) -> None:
        self._buf.append(span)
        if len(self._buf) >= self.FLUSH_AT and not self._full.is_set():
            self._full.set()
            if self._writer is None:
                self._writer = threading.Thread(
                    target=self._write_when_full, daemon=True, name="trace-writer"
                )
                self._writer.start()

    def _write_when_full(self) -> None:
        while self._full.wait():
            self._full.clear()
            if self._f is not None and self._f.closed:
                return
            self.flush()

    def flush(self) -> None:
        buf = self._buf
        if not buf:
            return
        with self._flock:
            lines = []
            while True:
                try:  # popleft, not a swap: a racing append is never lost
                    lines.append(json.dumps(buf.popleft(), default=repr))
                except IndexError:
                    break
            if not lines:
                return
            if self._f is None:
                os.makedirs(os.path.dirname(self.path), exist_ok=True)
                self._f = open(self.path, "a")
            self._f.write("\n".join(lines) + "\n")
            self._f.flush()

    def shutdown(self) -> None:
        with contextlib.suppress(Exception):
            self.flush()
            with self._flock:
                if self._f is not None and not self._f.closed:
                    os.fsync(self._f.fileno())
                    self._f.close()
            self._full.set()  # lets the writer thread see the closed file and end
        atexit.unregister(self.shutdown)


def enable(exporter: Optional[SpanExporter] = None) -> None:
    """Turns tracing on in THIS process. Without an exporter, spans go to
    JSONL under $RAY_TPU_TRACE_DIR (or the tmp default)."""
    global _exporter, _on
    with _lock:
        if exporter is None:
            exporter = JsonlExporter(trace_dir())
        _exporter = exporter
        _on = True


def disable() -> None:
    global _exporter, _on
    with _lock:
        if _exporter is not None:
            _exporter.shutdown()
        _exporter = None
        _on = False


def flush() -> None:
    """Writes what this process has buffered. Off: one test of the flag."""
    if _on and _exporter is not None:
        _exporter.flush()


def sync_env() -> None:
    """Re-reads RAY_TPU_TRACING, which is otherwise read once at import:
    api.init() calls this, so a process that set the toggle for the
    cluster it is about to start is traced itself."""
    global _on
    if not _on and os.environ.get("RAY_TPU_TRACING") == "1":
        _on = True


def trace_dir() -> str:
    import tempfile

    return os.environ.get("RAY_TPU_TRACE_DIR") or os.path.join(
        tempfile.gettempdir(), "ray_tpu_traces"
    )


def _active() -> SpanExporter:
    """The exporter of a process where tracing is on. Daemons/workers
    inherit the env toggle and get their JSONL sink at the first span."""
    global _exporter
    exp = _exporter
    if exp is None:
        with _lock:
            if _exporter is None:
                _exporter = JsonlExporter(trace_dir())
            exp = _exporter
    return exp


def is_enabled() -> bool:
    return _on


def new_flow_id() -> str:
    """A fresh id for one cross-process edge (submit->execute,
    request->replica); rendered as a Perfetto flow arrow."""
    return uuid.uuid4().hex[:16]


# ----------------------------------------------------------------- spans
class _NoSpan:
    """What span() returns when tracing is off: one shared object whose
    `with ... as sp` binds None."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc_info):
        return False


_NO_SPAN = _NoSpan()


def _annotation(name: str, attrs: Optional[Dict[str, Any]]):
    """The device-trace half of a `device=True` span; None in a process
    that has not imported jax (the driver must stay off it)."""
    if "jax" not in sys.modules:
        return None
    return sys.modules["jax"].profiler.TraceAnnotation(name, **(attrs or {}))


class _Span:
    __slots__ = ("sp", "_token", "_ann", "_n_attrs")

    def __init__(self, name, attrs, parent, ann):
        # Identity is fixed here so `parent` can be another thread's
        # context (the engine thread records under the request's span).
        self.sp = {
            "trace_id": parent["trace_id"] if parent else uuid.uuid4().hex,
            "span_id": os.urandom(8).hex(),
            "parent_id": parent["span_id"] if parent else None,
            "name": name,
            "pid": os.getpid(),
            "tid": threading.get_ident() & 0xFFFFFFFF,
            "attrs": attrs if attrs is not None else {},
        }
        self._ann = ann
        self._n_attrs = len(self.sp["attrs"])  # what the annotation was built with

    def __enter__(self) -> dict:
        sp = self.sp
        self._token = _ctx.set({"trace_id": sp["trace_id"], "span_id": sp["span_id"]})
        # Flight-record detail carries the thread id: the dump-side
        # reconstruction of still-open spans must not collide two concurrent
        # same-named spans (e.g. two exec loops both in channel_wait).
        _frec("span_open", (sp["name"], sp["tid"]))
        if self._ann is not None:
            self._ann.__enter__()
        sp["t0_ns"] = time.monotonic_ns()
        return sp

    def __exit__(self, exc_type, exc_val, tb):
        sp = self.sp
        t1_ns = time.monotonic_ns()
        if self._ann is not None:
            # attrs learned inside the span (add_attrs, or the caller's dict
            # written to): a dict keeps its order, so they are the tail
            late = dict(itertools.islice(sp["attrs"].items(), self._n_attrs, None))
            if late:
                self._ann.set_metadata(**late)
            self._ann.__exit__(exc_type, exc_val, tb)
        if exc_val is not None:
            sp["attrs"]["error"] = repr(exc_val)
        _ctx.reset(self._token)
        _frec("span_close", (sp["name"], sp["tid"]))
        _export(sp, sp["t0_ns"], t1_ns)
        return False


def _export(sp: dict, t0_ns: int, t1_ns: int) -> None:
    """Stamps both clocks on a closed span and hands it to the exporter."""
    sp["t0_ns"], sp["t1_ns"] = t0_ns, t1_ns
    sp["start_us"] = _WALL_ANCHOR_US + t0_ns // 1000
    sp["end_us"] = _WALL_ANCHOR_US + t1_ns // 1000
    _active().export(sp)


def span(
    name: str,
    attrs: Optional[Dict[str, Any]] = None,
    device: bool = False,
    parent: Optional[dict] = None,
):
    """Opens a span under the ambient context (or under `parent`, a
    {trace_id, span_id} captured elsewhere); sets itself as ambient for
    the duration (children parent to it — including spans created in
    OTHER processes via the propagated trace_ctx). `with span(...) as sp`
    binds the span dict, or None when tracing is off: the off path is
    this one test and a shared no-op object.

    `device=True` additionally enters a jax.profiler.TraceAnnotation of
    the same name and attrs, tracing on or off (off, the span IS the
    annotation): it costs a check of the profiler's level when no device
    trace is being taken and lands in the xplane file when one is. Only
    for the few spans a device trace should see; what `as sp` binds there
    is for `add_attrs` alone."""
    if not _on:
        if device:
            return _annotation(name, attrs) or _NO_SPAN
        return _NO_SPAN
    return _Span(name, attrs, parent or _ctx.get(), _annotation(name, attrs) if device else None)


def add_attrs(sp, **attrs) -> None:
    """Attributes a span learns only inside itself (what a locked section
    found), given what `with span(...) as sp` bound: the span's dict with
    tracing on (a `device=True` span hands them to its annotation as it
    closes), the bare annotation with tracing off, None where there is
    neither."""
    if sp is None:
        return
    if isinstance(sp, dict):
        sp["attrs"].update(attrs)
    else:
        sp.set_metadata(**attrs)


def record_span(
    name: str,
    t0_ns: int,
    t1_ns: int,
    attrs: Optional[Dict[str, Any]] = None,
    parent: Optional[dict] = None,
) -> None:
    """A span whose two instants were taken by the caller (one that began
    on another thread, or an instant: t0_ns == t1_ns). Call only where
    is_enabled() was tested."""
    _export(_Span(name, attrs, parent, None).sp, t0_ns, t1_ns)


def current_context() -> Optional[dict]:
    """The ambient {trace_id, span_id} to inject into an outgoing task
    entry (reference: tracing_helper.py:165 _inject_tracing_into_function)."""
    if not _on:
        return None
    return _ctx.get()


def inject_context() -> Optional[dict]:
    """The context a submitter stamps into an outgoing task entry: the
    ambient {trace_id, span_id} plus a fresh flow id for the Perfetto
    submit->execute arrow. With no ambient span the entry still gets a
    trace_id (the execution roots a new trace) and a flow id, so the
    arrow exists even for fire-and-forget submissions."""
    if not _on:
        return None
    ctx = _ctx.get()
    return {
        "trace_id": ctx["trace_id"] if ctx else uuid.uuid4().hex,
        "span_id": ctx["span_id"] if ctx else None,
        "flow": new_flow_id(),
    }


def continue_context(trace_ctx: Optional[dict], name: str, attrs=None):
    """Worker side: opens an execution span under a propagated trace_ctx
    (its children then parent to it through the ambient context). A flow
    id riding the context lands on the execution span as `flow_in` — the
    head of the Perfetto arrow whose tail is the submit-side `flow_out`."""
    if not _on:
        return _NO_SPAN
    if not trace_ctx:
        return span(name, attrs)
    if trace_ctx.get("flow"):
        attrs = dict(attrs or {})
        attrs["flow_in"] = trace_ctx["flow"]
    # The parent carries ONLY the span identity — a flow id leaking into
    # child spans would pair arrows twice.
    parent = {"trace_id": trace_ctx.get("trace_id"), "span_id": trace_ctx.get("span_id")}
    return _Span(name, attrs, parent, None)


# ------------------------------------------------------------- collection
def collect(directory: Optional[str] = None) -> List[dict]:
    """Merges every process's JSONL spans (stable-sorted by start time).

    Tolerant of truncated/corrupt lines: a worker killed mid-write leaves
    a partial last line (or raw bytes under memory pressure), and one
    poisoned file must not discard every other process's spans — skip the
    line, keep the rest. This process's own buffer is written first."""
    flush()
    directory = directory or trace_dir()
    spans: List[dict] = []
    try:
        names = sorted(os.listdir(directory))
    except OSError:
        return spans
    for fname in names:
        if not fname.endswith(".jsonl"):
            continue
        try:
            with open(os.path.join(directory, fname), errors="replace") as f:
                for line in f:
                    try:
                        sp = json.loads(line)
                    except ValueError:
                        continue  # truncated/corrupt line
                    # A partial write can still parse (e.g. a bare number
                    # from a split record): only span-shaped dicts merge.
                    if isinstance(sp, dict) and "span_id" in sp:
                        spans.append(sp)
        except OSError:
            continue
    spans.sort(key=lambda s: s.get("start_us", 0))
    return spans


def span_tree(spans: List[dict]) -> Dict[Optional[str], List[dict]]:
    """Groups spans by parent_id for tree walks in tests/tools."""
    by_parent: Dict[Optional[str], List[dict]] = {}
    for s in spans:
        by_parent.setdefault(s.get("parent_id"), []).append(s)
    return by_parent
