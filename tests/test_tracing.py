"""Distributed tracing spans (reference: util/tracing/tracing_helper.py —
spans around submit/execute with context propagated in task specs;
VERDICT r4 item 10: a nested task tree produces parent-linked spans)."""

import os

import pytest

import ray_tpu as rt
from ray_tpu import tracing


def test_span_nesting_in_process():
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        with tracing.span("outer"):
            with tracing.span("inner"):
                pass
    finally:
        tracing.disable()
    assert [s["name"] for s in exp.spans] == ["inner", "outer"]  # close order
    inner, outer = exp.spans
    assert inner["parent_id"] == outer["span_id"]
    assert inner["trace_id"] == outer["trace_id"]
    assert outer["parent_id"] is None
    assert outer["end_us"] >= outer["start_us"]


def test_nested_task_tree_parent_linked_spans(tmp_path, monkeypatch):
    """driver span -> task A (worker process) -> nested task B (worker
    process): every execution span parents to its submitter's span and
    all share one trace id, collected across processes via the JSONL
    sink (reference: tracing_helper.py:92,165)."""
    trace_dir = str(tmp_path / "traces")
    monkeypatch.setenv("RAY_TPU_TRACING", "1")
    monkeypatch.setenv("RAY_TPU_TRACE_DIR", trace_dir)
    rt.shutdown()
    rt.init(num_cpus=4, num_workers=2)
    tracing.enable()
    try:
        @rt.remote
        def child(x):
            return x + 1

        @rt.remote
        def parent(x):
            return rt.get(child.remote(x)) + 10

        with tracing.span("driver_root"):
            assert rt.get(parent.remote(1), timeout=120) == 12
    finally:
        rt.shutdown()
        tracing.disable()

    spans = tracing.collect(trace_dir)
    by_name = {}
    for s in spans:
        by_name.setdefault(s["name"].split(" ")[0], []).append(s)
    root = [s for s in spans if s["name"] == "driver_root"][0]
    runs = [s for s in spans if s["name"].startswith("run ")]
    assert len(runs) >= 2, [s["name"] for s in spans]
    # All spans share the root's trace.
    assert all(s["trace_id"] == root["trace_id"] for s in runs)
    # Parent links: one run span parents to the root (task A), and one
    # parents to A's span (nested task B) — executed in different worker
    # processes than the driver.
    parents = {s["parent_id"] for s in runs}
    ids = {s["span_id"] for s in runs}
    assert root["span_id"] in parents
    assert parents & ids, "no span parented to another task's span"
    assert any(s["pid"] != root["pid"] for s in runs)
    # Flow stitching: every execution span's flow_in pairs with a
    # submit-side span's flow_out (the Perfetto submit->execute arrow).
    submits = [s for s in spans if s["name"].startswith("submit ")]
    out_ids = {s["attrs"].get("flow_out") for s in submits}
    for s in runs:
        assert s["attrs"].get("flow_in") in out_ids, s


# ------------------------------------------------ hot-path contract (PR 24)
def test_span_off_is_one_shared_noop_and_creates_no_file(tmp_path, monkeypatch):
    monkeypatch.setenv("RAY_TPU_TRACE_DIR", str(tmp_path / "t"))
    tracing.disable()
    a, b = tracing.span("a"), tracing.span("b", {"k": 1})
    assert a is b  # no generator, no dict: the same object every time
    with a as sp:
        assert sp is None
    assert tracing.continue_context({"trace_id": "x", "span_id": "y"}, "c") is a
    assert tracing.current_context() is None and tracing.inject_context() is None
    tracing.flush()
    assert not (tmp_path / "t").exists()


def test_span_is_timed_on_the_monotonic_clock():
    import time

    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        before = time.monotonic_ns()
        with tracing.span("timed", {"k": 1}) as sp:
            assert sp["attrs"] == {"k": 1}
        after = time.monotonic_ns()
    finally:
        tracing.disable()
    (s,) = exp.spans
    assert before <= s["t0_ns"] <= s["t1_ns"] <= after
    # start_us/end_us are the same two instants on the wall anchor, not a
    # second clock read per span.
    assert s["end_us"] - s["start_us"] == s["t1_ns"] // 1000 - s["t0_ns"] // 1000
    assert abs(s["start_us"] - time.time() * 1e6) < 5e6


def test_buffered_exporter_writes_on_disable_and_collect_reads_back(tmp_path, monkeypatch):
    d = tmp_path / "spans"
    monkeypatch.setenv("RAY_TPU_TRACE_DIR", str(d))
    tracing.enable()
    try:
        for i in range(50):
            with tracing.span(f"s{i}", {"i": i}):
                pass
        assert not d.exists() or not os.listdir(d)  # buffered: nothing on disk yet
    finally:
        tracing.disable()
    spans = tracing.collect(str(d))
    assert sorted(s["attrs"]["i"] for s in spans) == list(range(50))
    assert all(s["t0_ns"] <= s["t1_ns"] and s["pid"] == os.getpid() for s in spans)


def test_explicit_parent_and_recorded_span():
    """What the engine thread uses: a span under a context captured on
    another thread, and a span whose instants the caller took."""
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        with tracing.span("request"):
            ctx = tracing.current_context()
        with tracing.span("elsewhere", parent=ctx):
            with tracing.span("nested"):
                pass
        tracing.record_span("instant", 5_000, 5_000, {"rid": 1}, parent=ctx)
    finally:
        tracing.disable()
    by = {s["name"]: s for s in exp.spans}
    assert by["elsewhere"]["parent_id"] == by["request"]["span_id"]
    assert by["nested"]["parent_id"] == by["elsewhere"]["span_id"]
    assert by["instant"]["parent_id"] == by["request"]["span_id"]
    assert len({s["trace_id"] for s in exp.spans}) == 1
    assert by["instant"]["t0_ns"] == by["instant"]["t1_ns"] == 5_000


@pytest.mark.parametrize("on", ["0", "1"])
def test_device_span_does_not_import_jax(tmp_path, on):
    """A device=True span in a process without jax (the driver) must not
    import it, tracing on or off."""
    import subprocess
    import sys

    code = (
        "import sys\n"
        "from ray_tpu import tracing\n"
        "with tracing.span('llm.step', {'live': 1}, device=True):\n"
        "    pass\n"
        "tracing.disable()\n"
        "assert 'jax' not in sys.modules, 'jax imported'\n"
    )
    env = dict(os.environ, RAY_TPU_TRACING=on, RAY_TPU_TRACE_DIR=str(tmp_path))
    subprocess.run([sys.executable, "-c", code], env=env, check=True, timeout=60)
    assert len(tracing.collect(str(tmp_path))) == int(on)


def test_device_span_enters_a_trace_annotation(monkeypatch):
    """With jax imported, a device=True span is a TraceAnnotation when
    tracing is off, and wraps one when it is on."""
    import jax

    entered = []

    class Ann:
        def __init__(self, name, **kw):
            self.name, self.kw = name, kw

        def __enter__(self):
            entered.append((self.name, self.kw))
            return self

        def __exit__(self, *a):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    tracing.disable()
    with tracing.span("llm.decode", {"live": 2}, device=True):
        pass
    exp = tracing.InMemoryExporter()
    tracing.enable(exp)
    try:
        with tracing.span("llm.decode", {"live": 3}, device=True):
            pass
        with tracing.span("not.device", {"live": 4}):
            pass
    finally:
        tracing.disable()
    assert entered == [("llm.decode", {"live": 2}), ("llm.decode", {"live": 3})]
    assert [s["name"] for s in exp.spans] == ["llm.decode", "not.device"]


@pytest.mark.parametrize("on", [False, True])
def test_add_attrs_reaches_the_span_and_its_annotation(monkeypatch, on):
    """Attributes learned inside a device span land on the annotation
    (set_metadata: directly with tracing off, as the span closes with it on)
    and, with tracing on, in the exported span; where `as sp` bound nothing
    it is a no-op."""
    import jax

    late = []

    class Ann:
        def __init__(self, name, **kw):
            self.kw = kw

        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def set_metadata(self, **kw):
            late.append(kw)

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Ann)
    exp = tracing.InMemoryExporter()
    tracing.enable(exp) if on else tracing.disable()
    try:
        with tracing.span("llm.admit", {"early": 1}, device=True) as sp:
            tracing.add_attrs(sp, admitted=2, live=3)
        with tracing.span("llm.idle", device=True) as sp:
            pass  # nothing learned: set_metadata is not called
        with tracing.span("not.device") as sp:
            tracing.add_attrs(sp, n=1)
    finally:
        tracing.disable()
    assert late == [{"admitted": 2, "live": 3}]
    if on:
        assert [s["attrs"] for s in exp.spans] == [{"early": 1, "admitted": 2, "live": 3}, {}, {"n": 1}]
