"""The readers of what PR 40 put into the program: the engine loop's spans
from start to stop (readers/trace_idle_causes.py) and the executables' names
in the device's `XLA Modules` line (readers/trace_modules.py). First on
hand-made intervals, where every number can be checked by eye, then on
recorded/tiny_v5e_engine.xplane.pb.gz (tools/record_engine_trace.py on a v5e
chip: one request, a pause with nothing to serve, three requests at once) and
on the two older recordings, which hold neither and must read as nothing."""

import os
import types

import pytest

from benchmarks.archs import dense_decoder
from benchmarks.lib import spec, trace as tl
from benchmarks.readers import trace_decode_roofline, trace_idle_causes as tic, trace_modules as tm, trace_program_spans as tps
from benchmarks.readers._common import trace_of

RECORDED = os.path.join(spec.BENCH_DIR, "recorded")
ENGINE = os.path.join(RECORDED, "tiny_v5e_engine.xplane.pb.gz")
OLDER = [os.path.join(RECORDED, "tiny_v5e_llm.xplane.pb.gz"), os.path.join(RECORDED, "tiny_v5e.xplane.pb.gz")]
DECODE = r"^jit_llm_decode\("
NEW_METRICS = {
    "serve_idle_no_work_pct": ("trace_idle_causes", "engine"), "serve_idle_in_flight_pct": ("trace_idle_causes", "paged forward"),
    "serve_idle_host_pct": ("trace_idle_causes", "engine"), "decode_device_step_p50_ms": ("trace_modules", "paged forward"),
    "decode_device_roofline": ("trace_modules", "paged forward"),
}
# the recorded model in the architecture file's keys
TINY = {
    "arch": "dense_decoder", "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 2, "num_key_value_heads": 1,
    "num_hidden_layers": 2, "vocab_size": 1024, "max_position_embeddings": 512, "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
    "torch_dtype": "float32",
}
MS = 1e-3


def span(name, a, b, **args):
    return {"name": name, "start": a * MS, "end": b * MS, "args": args}


def step(t, wait_end, n, live=1, kv=8, after_prefill=0, batch=True):
    """llm.batch [t, t+1], llm.decode [t+1, wait_end] = prep 1 ms, dispatch 1 ms, wait; emit 2 ms; the rest is llm.step's own."""
    d = t + 1 if batch else t
    return ([span("llm.batch", t, d, live=live)] if batch else []) + [
        span("llm.decode", d, wait_end, live=live, kv_tokens=kv, step=n, after_prefill=after_prefill),
        span("llm.decode.prep", d, d + 1), span("llm.decode.dispatch", d + 1, d + 2, step=n),
        span("llm.decode.wait", d + 2, wait_end, step=n), span("llm.emit", wait_end, wait_end + 2, tokens=live),
    ]


# 100 ms of an engine thread: a step, nothing to do for 21 ms, a step with a prefill, 2 ms under no span, a step.
SPANS = sorted(
    [span("llm.step", 0, 13, admitted=0, live=1)] + step(0, 10, 1, batch=False)
    + [span("llm.admit", 13, 14, waiting=0, admitted=0, live=0), span("llm.idle", 14, 34),
       span("llm.admit", 34, 35, waiting=0, admitted=1, live=1),
       span("llm.step", 35, 61, admitted=1, live=1), span("llm.prefill", 35, 45, rid=2),
       span("llm.prefill.prep", 35, 36), span("llm.prefill.dispatch", 36, 37), span("llm.prefill.wait", 37, 45)]
    + step(45, 58, 2, live=2, kv=30, after_prefill=1)
    + [span("llm.admit", 63, 64, waiting=0, admitted=0, live=2), span("llm.step", 64, 100, admitted=0, live=2)]
    + step(64, 98, 3, live=2, kv=32),
    key=lambda s: s["start"],
)
BENCH = [span("bench.decode", 0, 10, live=1, kv_tokens=8), span("bench.prefill", 35, 45),
         span("bench.decode", 46, 58, live=2, kv_tokens=30), span("bench.decode", 65, 98, live=2, kv_tokens=32), span("bench.decode", 99, 100, live=0, kv_tokens=0)]
# what the device ran: (module name, its ops); a module lasts from its first op to its last, gaps and all
RUNS = [("jit_llm_decode(1)", [(3, 5), (6, 8)]), ("jit_llm_prefill_p8(2)", [(38, 44)]),
        ("jit_llm_decode(1)", [(49, 52), (53, 57)]), ("jit_llm_decode(1)", [(68, 80), (81, 97)])]


def evidence(spans=SPANS, runs=RUNS, early_ms=0.0):
    """Hand-made evidence; `early_ms` puts the device's clock that far ahead of the host's, as a v5e trace does."""
    tr = tl.Trace.__new__(tl.Trace)
    ops = [("%fusion = f32[8]{0} fusion()", (a - early_ms) * MS, (b - early_ms) * MS) for _n, iv in runs for a, b in iv]
    tr.ops, tr.async_ops, tr.spans, tr.skew_s = {"/device:TPU:0": ops}, {}, list(BENCH), 0.0
    modules = [{"name": n, "start": (iv[0][0] - early_ms) * MS, "end": (iv[-1][1] - early_ms) * MS, "run_id": i} for i, (n, iv) in enumerate(runs)]
    cell = types.SimpleNamespace(arch=dense_decoder, config=TINY, allow_cpu=False)
    return {"worker": {"trace_path": None, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}, "cell": cell,
            "_trace": tr, "_program_spans": spans, "_executions": modules}


@pytest.mark.parametrize("early_ms", [0.0, 0.7])
def test_the_causes_cut_each_idle_interval_at_the_span_borders_and_add_up(early_ms):
    ev = evidence(early_ms=early_ms)
    tr = trace_of(ev)
    secs = tic.idle_seconds_by_cause(tr, SPANS)
    # in flight [1,10] [36,45] [47,58] [66,98] = 61 ms, of which the device ran 5 + 6 + 8 + 29 - the gaps (1 + 1 + 1) = 45
    assert secs["in_flight"] == pytest.approx((61 - 45) * MS)
    assert secs["no_work"] == pytest.approx(21 * MS)  # the admit that found nothing and the wait after it
    # prep 1 + emit and the step's own 3 | admit 1 + prep 1 | batch 1 + prep 1 + emit and own 3 | admit 1 + batch 1 + prep 1 + emit 2
    assert secs["host"] == pytest.approx((4 + 2 + 5 + 5) * MS)
    assert secs["hole"] == pytest.approx(2 * MS)  # [61, 63] lies under no span
    idle = tl.measure(tps.device_idle(tr))
    assert sum(secs.values()) == pytest.approx(idle) and idle == pytest.approx(tr.idle_share() * tr.window_s())
    shares = {c: tic.read(ev, {"cause": c}) for c in tic.CAUSES}
    assert sum(shares.values()) == pytest.approx(100.0 * tr.idle_share())
    assert shares["host"] == pytest.approx(16.0) and shares["in_flight"] == pytest.approx(16.0)
    # the gap from the first step's result to the prefill's launch, [8, 38]: the midpoint's owner would get all 30 ms
    by_midpoint = tps.idle_by_innermost_span(tr, SPANS)
    if not early_ms:
        assert by_midpoint["llm.idle"] == pytest.approx(30 * MS) and by_midpoint["llm.idle"] > secs["no_work"]


def test_a_device_clock_that_runs_early_does_not_move_the_causes():
    """No device instant is compared with a host instant: on a clock 2.5 ms early the first module seems to start
    before its dispatch, a cut instant by instant would call that op time busy under `llm.decode.prep` and the
    same length idle in flight, and the causes stay where they were."""
    on_time, early = evidence(), evidence(early_ms=2.5)
    flight = tic.in_flight_intervals(SPANS)
    outside = [tl.measure(tl.subtract(tl.union([(a, b) for _n, a, b in trace_of(ev).ops["/device:TPU:0"]]), flight)) for ev in (on_time, early)]
    assert outside[0] == 0.0 and outside[1] == pytest.approx(2.0 * MS)  # [0.5, 1], [35.5, 36], [46.5, 47], [65.5, 66]: half a millisecond of each run
    assert tic.idle_seconds_by_cause(trace_of(early), SPANS) == pytest.approx(tic.idle_seconds_by_cause(trace_of(on_time), SPANS))


@pytest.mark.parametrize("without", ["llm.admit", "llm.idle"])
def test_causes_need_the_loop_under_spans(without):
    spans = [s for s in SPANS if s["name"] != without]
    got = [tic.read(evidence(spans=spans), {"cause": c}) for c in tic.CAUSES]
    if without == "llm.admit":  # PR 24's spans alone: no work cannot be told from a busy host
        assert got == [None] * 4
    else:  # a busy cell's segment may hold no wait at all; the time shows as a hole, not as a guess
        assert got[0] == pytest.approx(1.0) and got[3] == pytest.approx(22.0)
    with pytest.raises(ValueError):
        tic.read(evidence(), {"cause": "launch"})


def test_executions_by_name_and_the_device_roofline_on_hand_made_steps():
    ev = evidence()
    cell = ev["cell"]
    assert tm.read(ev, {"stat": "p50_ms", "module": DECODE}) == pytest.approx(8.0)  # 5, 8, 29 ms; the prefill is another name
    assert tm.read(ev, {"stat": "p50_ms", "module": r"^jit_llm_prefill_p\d+\("}) == pytest.approx(6.0)
    steps = tm.decode_steps(ev, {"span": "llm.decode", "module": DECODE})
    assert [(s["args"]["step"], m["run_id"]) for s, m in steps] == [(1, 0), (2, 2), (3, 3)]
    bw = 819e9
    need = sum(dense_decoder.decode_step_min_bytes(TINY, live, kv) for live, kv in ((1, 8), (2, 30), (2, 32)))
    device = tm.read(ev, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": cell})
    assert device == pytest.approx(100.0 * need / bw / (42 * MS))
    # never above the op-clipped share of the same steps: a duration holds the gaps between the ops (3 ms here) too
    clipped = trace_decode_roofline.read(ev, {"span": "bench.decode", "cell": cell})
    assert clipped == pytest.approx(100.0 * need / bw / (39 * MS)) and device < clipped


def test_an_early_device_clock_still_joins_each_execution_to_its_step():
    ev = evidence(early_ms=0.9)  # the first module starts at 2.1 ms, its llm.decode at 0; the third at 67.1 in [65, 98]
    steps = tm.decode_steps(ev, {"span": "llm.decode", "module": DECODE})
    assert [(s["args"]["step"], m["run_id"]) for s, m in steps] == [(1, 0), (2, 2), (3, 3)]
    # an execution well before any span of its kind (a warm-up's) and a span with none are left out
    lone = evidence(runs=[("jit_llm_decode(1)", [(20, 25)])] + RUNS[1:])
    assert [s["args"]["step"] for s, _m in tm.decode_steps(lone, {"span": "llm.decode", "module": DECODE})] == [2, 3]


def test_unnamed_executables_and_spans_without_ordinals_read_as_nothing():
    unnamed = evidence(runs=[("jit_step(%d)" % i, iv) for i, (_n, iv) in enumerate(RUNS)])
    no_ordinal = evidence(spans=[dict(s, args={k: v for k, v in s["args"].items() if k != "step"}) for s in SPANS])
    for args in ({"stat": "p50_ms", "module": DECODE}, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": unnamed["cell"]}):
        assert tm.read(unnamed, args) is None
    assert tm.read(no_ordinal, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": unnamed["cell"]}) is None
    with pytest.raises(ValueError):
        tm.read(evidence(), {"stat": "mean", "module": DECODE})


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metric_files(name):
    reader, layer = NEW_METRICS[name]
    mf = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", name + ".json"))
    (entry,) = [m for m in spec.benchmark_json()["per_layer"] if m["name"] == name]
    serving = [w["name"] for w in spec.benchmark_json()["workloads"] if "serve" in w["name"]]
    assert mf["reader"] == reader and mf["layer"] == entry["layer"] == layer and mf["moves"] == entry["moves"] == "serve_tok_s"
    assert mf["cells"] == entry["workloads"] == serving and len(serving) == 3
    assert spec.read_metric(types.SimpleNamespace(bench_dir=spec.BENCH_DIR, arch=dense_decoder, config=TINY, allow_cpu=False), name, evidence()) is not None


# ------------------------------------------------ the recordings (a v5e chip)


def recorded(path):
    cell = types.SimpleNamespace(arch=dense_decoder, config=TINY, allow_cpu=False)
    return {"worker": {"trace_path": path, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}, "cell": cell}


@pytest.fixture(scope="module")
def engine_trace():
    return recorded(ENGINE)


def test_the_recorded_loop_lies_under_spans_without_a_hole(engine_trace):
    spans = tps.spans_of(engine_trace)
    names = [s["name"] for s in spans]
    assert names.count("llm.step") == names.count("llm.batch") == names.count("llm.decode") == names.count("llm.emit") == 7
    assert names.count("llm.admit") == 9 and names.count("llm.idle") == 1 and names.count("llm.prefill") == 4
    top = [s for s in spans if s["name"] in ("llm.idle", "llm.admit", "llm.step")]
    for a, b in zip(top, top[1:]):
        assert 0.0 <= b["start"] - a["end"] < 1e-4, (a["name"], b["name"])  # the largest hole: 76 us
        assert (a["name"] == "llm.admit") != (b["name"] == "llm.admit")
    admits = [s["args"] for s in top if s["name"] == "llm.admit"]
    assert sum(a["admitted"] for a in admits) == 4 and {"waiting": 0, "admitted": 0, "live": 0} in admits
    decodes = [s for s in spans if s["name"] == "llm.decode"]
    ordinals = [s["args"]["step"] for s in decodes]
    assert ordinals == list(range(ordinals[0], ordinals[0] + 7)) and {s["args"]["after_prefill"] for s in decodes} == {0, 1}
    for part in ("llm.decode.dispatch", "llm.decode.wait"):
        assert [s["args"]["step"] for s in spans if s["name"] == part] == ordinals


def test_the_recorded_executables_carry_their_names(engine_trace):
    names = [m["name"].split("(")[0] for m in tm.executions_of(engine_trace)]
    assert names.count("jit_llm_decode") == 7 and names.count("jit_llm_prefill_p4") == 3 and names.count("jit_llm_prefill_p8") == 1
    assert "jit_step" not in names and len(names) == 11
    # ~28 us a step of a 2-layer model; the 64- and 128-token buckets are told apart by name
    assert 0.02 < tm.read(engine_trace, {"stat": "p50_ms", "module": DECODE}) < 0.04
    assert 0.02 < tm.read(engine_trace, {"stat": "p50_ms", "module": r"^jit_llm_prefill_p8\("}) < 0.04
    steps = tm.decode_steps(engine_trace, {"span": "llm.decode", "module": DECODE})
    assert len(steps) == 7 and [m["run_id"] for _s, m in steps] == sorted(m["run_id"] for _s, m in steps)


def test_idle_causes_on_the_recorded_trace(engine_trace):
    tr = trace_of(engine_trace)
    shares = {c: tic.read(engine_trace, {"cause": c}) for c in tic.CAUSES}
    assert sum(shares.values()) == pytest.approx(100.0 * tr.idle_share())
    # a tiny model: the chip is idle 99 % of 45 ms, half of it the 20 ms pause, most of the rest waiting for a step's tokens
    assert 45 < shares["no_work"] < 52 and 40 < shares["in_flight"] < 48 and 4 < shares["host"] < 9 and 0 <= shares["hole"] < 0.5
    assert tps.read(engine_trace, {"stat": "idle_unexplained_pct"}) < 0.5  # the older metric falls to ~0 on a loop under spans


def test_the_device_roofline_is_at_or_below_the_op_clipped_one_on_the_recorded_trace(engine_trace):
    cell = engine_trace["cell"]
    device = tm.read(engine_trace, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": cell})
    clipped = trace_decode_roofline.read(engine_trace, {"span": "bench.decode", "cell": cell})
    assert 0.0 < device <= clipped < 100.0


@pytest.mark.parametrize("path", OLDER)
def test_a_program_without_the_loop_spans_or_the_names_reads_as_nothing(path):
    ev = recorded(path)
    assert [tic.read(ev, {"cause": c}) for c in tic.CAUSES] == [None] * 4
    assert tm.read(ev, {"stat": "p50_ms", "module": DECODE}) is None
    assert tm.read(ev, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": ev["cell"]}) is None
