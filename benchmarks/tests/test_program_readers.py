"""The readers of what the PROGRAM records about itself (PR 24): the engine's
stage clocks (stats()["clocks"], through the window's marks) and its `llm.*`
spans in the traced segment. Hand-built evidence for the counters; for the
spans, the two traces recorded on a v5e chip: tiny_v5e_llm.xplane.pb.gz
(benchmarks/tools/record_program_trace.py: an engine over a 2-layer PagedLM
serving three requests, 3 prefills and 5 decode steps) holds `llm.*` events,
tiny_v5e.xplane.pb.gz (recorded before the program had them) holds none."""

import os

import pytest

from benchmarks.lib import spec
from benchmarks.readers import counter_mean, counter_ratio, counter_residual_share, trace_program_spans as tps

RECORDED = os.path.join(spec.BENCH_DIR, "recorded")
WITH_SPANS = os.path.join(RECORDED, "tiny_v5e_llm.xplane.pb.gz")
WITHOUT = os.path.join(RECORDED, "tiny_v5e.xplane.pb.gz")
PREP = {"stat": "median_sum_ms", "within": "llm.decode", "spans": ["llm.decode.prep", "llm.decode.dispatch"]}


def clocks(qn, qs, loop, idle, prefill, decode):
    return {"clocks": {
        "queue_wait": {"n": qn, "s": qs}, "loop": {"s": loop, "idle_s": idle},
        "prefill": {"n": 0, "s": prefill, "tokens": 0}, "decode": {"n": 0, "s": decode},
    }}


def marks(a, b):
    return {"marks": [{"engine": a}, {"engine": b}]}


MEAN = {"sum": "clocks.queue_wait.s", "count": "clocks.queue_wait.n", "scale": 1000}
RATIO = {"numerator": "kv.prefix_hits", "denominator": ["kv.prefix_hits", "kv.prefix_misses"]}
# a key that holds a dot itself is named by a list of keys (stats()["clocks"]["decode.kv_pages"], PR 28)
PAGES = {"numerator": ["clocks", "decode.kv_pages", "live"], "denominator": [["clocks", "decode.kv_pages", "table"]]}
KV_A = {"kv": {"prefix_hits": 10, "prefix_misses": 30}, "clocks": {"decode.kv_pages": {"live": 100, "table": 1000}}}
KV_B = {"kv": {"prefix_hits": 40, "prefix_misses": 40}, "clocks": {"decode.kv_pages": {"live": 250, "table": 2000}}}
SHARE = {"total": "clocks.loop.s", "excluded": ["clocks.loop.idle_s"], "accounted": ["clocks.prefill.s", "clocks.decode.s"]}
A = clocks(10, 0.5, 100.0, 40.0, 5.0, 50.0)
B = clocks(46, 2.3, 130.0, 42.0, 9.0, 73.0)  # window: 36 requests waited 1.8 s; 28 s awake, 4 prefill, 23 decode


@pytest.mark.parametrize("reader,args,evidence,expected", [
    (counter_mean, MEAN, marks(A, B), 50.0),
    (counter_mean, MEAN, marks(A, A), None),  # nothing counted in the window
    (counter_mean, MEAN, marks({"running": 1}, {"running": 2}), None),  # a program without the clocks
    (counter_mean, MEAN, marks(A, {"clocks": {"queue_wait": {"n": 3}}}), None),  # half a counter
    (counter_mean, MEAN, {}, None),  # a runner without marks
    (counter_mean, dict(MEAN, scale=1), marks(A, B), 0.05),
    (counter_ratio, RATIO, marks(KV_A, KV_B), 75.0),  # 30 hits of 40 pages
    (counter_ratio, RATIO, marks(KV_A, KV_A), None),  # nothing counted in the window
    (counter_ratio, RATIO, marks(A, B), None),  # a program without the counter: nothing to read, not a KeyError (PR 45)
    (counter_ratio, RATIO, marks(KV_A, {"kv": {"prefix_hits": 40}}), None),  # half a counter
    (counter_ratio, RATIO, {}, None),  # a runner without marks
    (counter_ratio, PAGES, marks(KV_A, KV_B), 15.0),
    (counter_ratio, dict(PAGES, numerator="clocks.decode.kv_pages.live"), marks(KV_A, KV_B), None),  # split at its dots the key is not found
    (counter_mean, {"sum": ["clocks", "decode.kv_pages", "live"], "count": ["clocks", "decode.kv_pages", "table"]}, marks(KV_A, KV_B), 0.15),
    (counter_residual_share, SHARE, marks(A, B), 100.0 * (28.0 - 4.0 - 23.0) / 28.0),
    (counter_residual_share, SHARE, marks(A, A), None),  # the loop was never awake
    (counter_residual_share, SHARE, marks({"kv": {}}, {"kv": {}}), None),
    (counter_residual_share, dict(SHARE, excluded=[]), marks(A, B), 100.0 * (30.0 - 27.0) / 30.0),
])
def test_counter_readers(reader, args, evidence, expected):
    got = reader.read(evidence, args)
    assert got == pytest.approx(expected) if expected is not None else got is None


def test_the_new_metric_files_name_these_readers_and_paths():
    for name, reader in (("queue_wait_mean_ms", "counter_mean"), ("engine_ttft_mean_ms", "counter_mean"),
                         ("engine_host_share_pct", "counter_residual_share"),
                         ("decode_host_prep_ms.tpot", "trace_program_spans"),
                         ("decode_host_prep_ms.tok", "trace_program_spans"),
                         ("prefix_hit_page_share_pct", "counter_ratio")):
        mf = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", name + ".json"))
        assert mf["reader"] == reader
    # the paths exist in what the engine really returns
    from ray_tpu.serve.llm import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm.model import StubModel

    eng = InferenceEngine(StubModel(), EngineConfig(page_tokens=4, pool_pages=16), name="bench-readers")
    try:
        first = eng.stats()
        assert list(eng.generate([1, 2, 3], 4)) == [7, 8, 9, 10]
        ev = marks(first, eng.stats())
    finally:
        eng.close()
    assert counter_ratio.read(ev, PAGES) is not None  # the dotted key is in what the engine really returns
    for name in ("queue_wait_mean_ms", "engine_ttft_mean_ms", "engine_host_share_pct"):
        mf = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", name + ".json"))
        value = __import__(f"benchmarks.readers.{mf['reader']}", fromlist=["read"]).read(ev, mf["args"])
        assert value is not None and value >= 0.0, name


def evidence_of(path):
    return {"worker": {"trace_path": path}}


@pytest.mark.parametrize("args", [PREP, {"stat": "idle_unexplained_pct"}])
def test_a_trace_without_program_spans_reads_as_nothing(args):
    """Nothing to read is None whatever is asked; the second stat went with its two metrics in PR 45."""
    assert tps.program_spans(WITHOUT) == []
    assert tps.read(evidence_of(WITHOUT), args) is None
    assert tps.read({"worker": {"trace_path": None}}, args) is None


def test_program_spans_of_the_recorded_trace():
    spans = tps.program_spans(WITH_SPANS)
    names = [s["name"] for s in spans]
    assert names.count("llm.prefill") == 3 and names.count("llm.decode") == names.count("llm.step") == 5
    for part in ("prep", "dispatch", "wait"):
        assert names.count(f"llm.decode.{part}") == 5 and names.count(f"llm.prefill.{part}") == 3
    assert names.count("llm.emit") == 5
    steps = [s for s in spans if s["name"] == "llm.step"]
    assert [s["args"]["admitted"] for s in steps][0] >= 1 and all(1 <= s["args"]["live"] <= 3 for s in steps)
    prefill = [s for s in spans if s["name"] == "llm.prefill"]
    assert sorted(s["args"]["prompt_tokens"] for s in prefill) == [40, 43, 100]
    assert {s["args"]["bucket_tokens"] for s in spans if s["name"] == "llm.prefill.dispatch"} == {64, 128}
    # children lie inside their parents on the trace's clock
    for d in (s for s in spans if s["name"] == "llm.decode"):
        inner = [s for s in spans if s["name"].startswith("llm.decode.") and d["start"] <= s["start"] < d["end"]]
        assert [s["name"] for s in inner] == ["llm.decode.prep", "llm.decode.dispatch", "llm.decode.wait"]
        assert all(s["end"] <= d["end"] + 1e-6 for s in inner)


def test_span_metrics_on_the_recorded_trace():
    ev = evidence_of(WITH_SPANS)
    prep = tps.read(ev, PREP)
    # numpy inputs + the jitted call returning, for a 4-slot, 8-page table: well under a millisecond, not zero
    assert 0.02 < prep < 2.0
    with pytest.raises(ValueError):
        tps.read(ev, {"stat": "idle_unexplained_pct"})  # retired with its metrics (PR 45)
    from benchmarks.readers._common import trace_of

    tr, spans = trace_of(ev), tps.spans_of(ev)
    totals = tps.idle_by_innermost_span(tr, spans)
    assert sum(totals.values()) == pytest.approx(tps.tl.measure(tps.device_idle(tr)))
    # a tiny model leaves the chip idle while the host waits for its tokens
    assert max(totals, key=totals.get).startswith("llm.")
    # the one pass in order of time gives what asking every span about every gap gives
    slow = {}
    for a, b in tps.device_idle(tr):
        over = [s for s in spans if s["start"] <= (a + b) / 2 < s["end"]]
        owner = min(over, key=lambda s: s["end"] - s["start"])["name"] if over else tps.NO_SPAN
        slow[owner] = slow.get(owner, 0.0) + (b - a)
    assert totals == pytest.approx(slow)
