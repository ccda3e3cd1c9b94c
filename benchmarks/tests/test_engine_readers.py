"""The readers of what PR 40 put into the program: the engine loop's spans
from start to stop (readers/trace_idle_causes.py) and the executables' names
in the device's `XLA Modules` line (readers/trace_modules.py). First on
hand-made intervals, where every number can be checked by eye, then on
recorded/tiny_v5e_engine.xplane.pb.gz (tools/record_engine_trace.py on a v5e
chip: one request, a pause with nothing to serve, three requests at once) and
on the two older recordings, which hold neither and must read as nothing.
Then (PR 45) the same decode steps in four forms of one engine loop: the host
waits for a step inside the span that launched it (`waits_inside`, the program
as it stands), the same instants with the wait under a span of its own
(`waits_outside`), and two steps in flight (`two_in_flight`, and again with the waits under spans of
their own): no serving reader may tell a pair of the same instants apart, and what is the steps' own (the device's
step, its roofline, the kernels' rooflines, the host's prep) reads the same in
all four."""

import os
import re
import types

import pytest

from benchmarks.archs import dense_decoder
from benchmarks.lib import spec, trace as tl
from benchmarks.readers import span_period, span_stat, trace_idle_causes as tic, trace_modules as tm, trace_paged_window_roofline, trace_program_spans as tps, trace_state_update_roofline
from benchmarks.readers._common import trace_of

RECORDED = os.path.join(spec.BENCH_DIR, "recorded")
ENGINE = os.path.join(RECORDED, "tiny_v5e_engine.xplane.pb.gz")
OLDER = [os.path.join(RECORDED, "tiny_v5e_llm.xplane.pb.gz"), os.path.join(RECORDED, "tiny_v5e.xplane.pb.gz")]
DECODE = r"^jit_llm_decode\("
# PR 40's five metrics: each is a family of files, the plain name and, where the end-to-end metric it moves differs, a twin (`.tpot`)
FAMILIES = {
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


def evidence(spans=SPANS, runs=RUNS, early_ms=0.0, bench=BENCH, arch=dense_decoder):
    """Hand-made evidence; `early_ms` puts the device's clock that far ahead of the host's, as a v5e trace does.
    An execution's ops are named alike but a decode step's last, its `kernel`. The measured window's host-clock spans
    are the traced ones here (a run keeps them apart: `worker.spans`, the same calls on `time.monotonic`)."""
    tr = tl.Trace.__new__(tl.Trace)
    ops = [("%kernel.7 = f32[8]{0} custom-call()" if (a, b) == iv[-1] and "decode" in n else "%fusion = f32[8]{0} fusion()", (a - early_ms) * MS, (b - early_ms) * MS)
           for n, iv in runs for a, b in iv]
    tr.ops, tr.async_ops, tr.spans, tr.skew_s = {"/device:TPU:0": ops}, {}, sorted(bench, key=lambda s: s["start"]), 0.0
    modules = [{"name": n, "start": (iv[0][0] - early_ms) * MS, "end": (iv[-1][1] - early_ms) * MS, "run_id": i} for i, (n, iv) in enumerate(runs)]
    cell = types.SimpleNamespace(arch=arch, config=TINY, allow_cpu=False)
    return {"worker": {"trace_path": None, "device": {"platform": "tpu", "kind": "TPU v5 lite"}}, "cell": cell,
            "_trace": tr, "_program_spans": sorted(spans, key=lambda s: s["start"]), "_executions": modules,
            "window": (min(s["start"] for s in bench), max(s["end"] for s in bench)),
            "spans": [[s["name"], s["start"], s["end"], s["args"]] for s in tr.spans]}


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
    # never above the share by the steps' op time: a duration holds the gaps between the ops (3 ms here) too
    busy = sum(b - a for n, iv in RUNS if n.startswith("jit_llm_decode") for a, b in iv)
    assert busy == 39 and device < 100.0 * need / bw / (busy * MS)


@pytest.mark.parametrize("early_ms", [0.9, 2.5, -1.0])
def test_a_device_clock_that_runs_early_or_late_still_joins_each_execution_to_its_step(early_ms):
    """0.9 ms early the first module starts at 2.1 ms, its llm.decode at 0; 2.5 ms early it starts before its own
    dispatch does: the k-th execution is still the k-th step, because no pair is decided on the clocks."""
    ev = evidence(early_ms=early_ms)
    steps = tm.decode_steps(ev, {"span": "llm.decode", "module": DECODE})
    assert [(s["args"]["step"], m["run_id"]) for s, m in steps] == [(1, 0), (2, 2), (3, 3)]


def test_the_traces_edges_an_execution_without_its_span_and_a_span_without_its_execution_are_left_out():
    args = {"span": "llm.decode", "module": DECODE}
    # the trace began with a step in flight: its execution is there (inside the first span of any kind), its spans are not
    before = evidence(spans=[s for s in SPANS if s["args"].get("step") != 1 or s["name"] == "llm.step"])
    assert [(s["args"]["step"], m["run_id"]) for s, m in tm.decode_steps(before, args)] == [(2, 2), (3, 3)]
    # ... and ended with one launched: its spans are there (the wait cut off), its execution is not
    after = evidence(spans=[s for s in SPANS if not (s["name"] == "llm.decode.wait" and s["args"]["step"] == 3)], runs=RUNS[:-1])
    assert [(s["args"]["step"], m["run_id"]) for s, m in tm.decode_steps(after, args)] == [(1, 0), (2, 2)]
    # both at once, and an execution of a warm-up's long before any span: the counts agree (3 and 3) and say nothing
    both = evidence(spans=before["_program_spans"], runs=[("jit_llm_decode(1)", [(-900, -895)])] + RUNS[:-1])
    assert [(s["args"]["step"], m["run_id"]) for s, m in tm.decode_steps(both, args)] == [(2, 3)]
    assert tm.join([], both["_executions"]) == [] and tm.join(before["_program_spans"], []) == []
    # spans without an ordinal (a prefill's; a program older than PR 40) are joined by the clock, as ever
    prefill = [s for s in SPANS if s["name"] == "llm.prefill"]
    assert [(s["args"]["rid"], m["run_id"]) for s, m in tm.join(prefill, both["_executions"])] == [(2, 2)]


def test_unnamed_executables_and_spans_without_ordinals_read_as_nothing():
    unnamed = evidence(runs=[("jit_step(%d)" % i, iv) for i, (_n, iv) in enumerate(RUNS)])
    no_ordinal = evidence(spans=[dict(s, args={k: v for k, v in s["args"].items() if k != "step"}) for s in SPANS])
    for args in ({"stat": "p50_ms", "module": DECODE}, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": unnamed["cell"]}):
        assert tm.read(unnamed, args) is None
    assert tm.read(no_ordinal, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": unnamed["cell"]}) is None
    with pytest.raises(ValueError):
        tm.read(evidence(), {"stat": "mean", "module": DECODE})


@pytest.mark.parametrize("base", sorted(FAMILIES))
def test_the_new_metric_files(base):
    """A family is held to what it must cover, not to a count of cells: every member reads through the family's reader
    under the family's layer with the family's args, its cells (the BENCHMARK.json entry's `workloads`, the one place
    they stand) are serving cells, and every serving cell reports the family once for each end-to-end metric a member
    moves there (a later cell appends its name to the entry's `workloads`; PR 58: ONE pattern names the decode
    executable of whatever cache layout, `jit_llm_decode`, `_hybrid`, `_state`, of which a replica compiles one)."""
    reader, layer = FAMILIES[base]
    bench = spec.benchmark_json()
    serving = [w["name"] for w in bench["workloads"] if "serve" in w["name"]]
    members = [m for m in bench["per_layer"] if m["name"] == base or m["name"].startswith(base + ".")]
    assert base in [m["name"] for m in members]
    seen = []
    for entry in members:
        mf = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", entry["name"] + ".json"))
        assert mf["reader"] == reader and mf["layer"] == entry["layer"] == layer and mf["moves"] == entry["moves"], entry["name"]
        assert set(entry["workloads"]) <= set(serving), entry["name"]
        assert mf["args"] == dict(spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", base + ".json"))["args"], **{k: mf["args"][k] for k in ("module",) if k in mf["args"]})
        if "module" in mf["args"]:
            names = {"jit_llm_decode(7)", "jit_llm_decode_hybrid(7)", "jit_llm_decode_state(7)"} if entry["name"] == base else {"jit_llm_decode(7)"}
            others = {"jit_llm_decode_other(7)", "jit_llm_prefill_p8(7)", "xjit_llm_decode(7)", "jit_llm_decode_hybrid_state(7)", *names}
            assert {n for n in others if re.search(mf["args"]["module"], n)} == names, entry["name"]
        seen += [(c, mf["moves"]) for c in entry["workloads"]]
    assert len(seen) == len(set(seen)) and {c for c, _m in seen} == set(serving)  # no cell twice for one end-to-end metric, none left out
    assert spec.read_metric(types.SimpleNamespace(bench_dir=spec.BENCH_DIR, arch=dense_decoder, config=TINY, allow_cpu=False), base, evidence()) is not None


# PR 58: each read what the entry it names reads (same reader, args and `moves`); its cells are on that entry now
MERGED = spec.load_json(os.path.join(spec.BENCH_DIR, "tools", "renamed_pr58.json"))


def test_no_retired_name_is_left():
    """PR 45: the metrics that timed a device step through the host span `bench.decode`, and the share of the idle
    under no span, are gone from BENCHMARK.json, from metrics/ and from the readers; what stands in their place is there.
    PR 58: so are the copies of a quantity under a family's suffix, and the entry each was merged into is there."""
    names = {m["name"] for m in spec.benchmark_json()["per_layer"]}
    files = {f[: -len(".json")] for f in os.listdir(os.path.join(spec.BENCH_DIR, "metrics"))}
    for gone in ("decode_roofline.tpot", "decode_roofline.tok", "decode_roofline.afmoe", "decode_step_p50_ms.tpot", "decode_step_p50_ms.tok",
                 "decode_step_p50_ms.afmoe", "serve_idle_unexplained_pct", "serve_idle_unexplained_pct.afmoe", *MERGED):
        assert gone not in names and gone not in files
    assert len(MERGED) == 59 and set(MERGED.values()) <= names & files
    assert {"decode_device_roofline.tpot", "decode_period_p50_ms", "decode_period_p50_ms.tpot"} <= names & files
    readers = os.listdir(os.path.join(spec.BENCH_DIR, "readers"))
    assert "trace_decode_roofline.py" not in readers and "trace_decode_roofline_counted.py" not in readers and "span_period.py" in readers
    # every serving cell reports a device roofline for each end-to-end metric its retired `decode_roofline.*` moved, and the period
    moved = {}
    for m in spec.benchmark_json()["per_layer"]:
        for stem in ("decode_device_roofline", "decode_period_p50_ms"):
            if m["name"].split(".")[0] == stem:
                for c in m["workloads"]:
                    moved.setdefault((stem, c), set()).add(m["moves"])
    for w in spec.benchmark_json()["workloads"]:
        if "serve" in w["name"]:
            want = {"serve_tok_s", "tpot_p95_ms"} if w["name"] == "dsllm7b-serve-chat-steady" else {"serve_tok_s"}
            assert moved[("decode_device_roofline", w["name"])] == want
            assert moved[("decode_period_p50_ms", w["name"])] == ({"tpot_p95_ms"} if w["name"] == "dsllm7b-serve-chat-steady" else {"serve_tok_s"})


# ------------------------------------------------ one loop, four forms (PR 45)

FORMS = ("waits_inside", "waits_outside", "two_in_flight", "two_in_flight_outside")
STEPS = 5
T0 = 25.0  # the first decode iteration starts here, behind two prefills and a pause
DEVICE_STEP_MS = 8.0  # two ops of 4 and 3.5 ms with half a millisecond between them
# a stand-in architecture file at a served model's sizes, in round numbers: a step reads 5.5 GB of weights and 1 MB a
# K/V token (84 % of the HBM peak in 8 ms); 6e7 B a K/V token the paged kernel reads, 8e8 B a live row's state
KERNELS = types.SimpleNamespace(
    decode_step_min_bytes=lambda config, live, kv_tokens: 5.5e9 + 1e6 * kv_tokens,
    decode_attention_bytes=lambda config, live, kv_tokens: 6e7 * kv_tokens,
    decode_state_bytes=lambda config, live: 8e8 * live,
)


def prefill_call(t, rid, run):
    """llm.prefill [t, t+10]: prep 1 ms, dispatch 1 ms, wait 8 ms; the device works [t+3, t+9]."""
    return ([span("llm.prefill", t, t + 10, rid=rid), span("llm.prefill.prep", t, t + 1), span("llm.prefill.dispatch", t + 1, t + 2),
             span("llm.prefill.wait", t + 2, t + 10)], [span("bench.prefill", t, t + 10)], [(f"jit_llm_prefill_p8({run})", [(t + 3, t + 9)])])


def decode_ops(t):
    return ("jit_llm_decode(1)", [(t, t + 4), (t + 4.5, t + DEVICE_STEP_MS)])


def loop(form):
    """(llm.* spans, bench.* spans, device runs) of one engine thread: a request of one token, nothing to do for
    3 ms, a prefill (the iteration ends at 25 ms), five decode steps at two live rows, a prefill. `waits_inside`: a step is launched and read inside
    its `llm.decode` (12 ms a cycle: admit 0.5, batch 1, prep 0.5, dispatch 1, the device 8 ms from the dispatch's
    middle, the result 1 ms later, decide 0.5). `waits_outside`: the same instants, but `llm.decode` and `bench.decode`
    end with the dispatch and the wait lies under `llm.step`. `two_in_flight`: step n+1 is launched before step n is
    read; the device runs the five steps back to back, each `llm.decode` holds its own dispatch and the wait of the
    step BEFORE (its end: that execution's end + 1 ms), the host's decide, admit and batch lie under the next execution.
    `two_in_flight_outside`: those instants with every `llm.decode` / `bench.decode` a bare prep and dispatch, each wait
    under `llm.step`: no execution but the first starts inside a span of its kind."""
    llm, bench, runs = [span("llm.admit", -1, 0, waiting=1, admitted=1, live=0), span("llm.step", 0, 11, admitted=1, live=0)], [], []
    for part, more in zip((llm, bench, runs), prefill_call(0, 1, 1)):
        part += more
    llm += [span("llm.admit", 11, 12, waiting=0, admitted=0, live=0), span("llm.idle", 12, 14), span("llm.admit", 14, 15, waiting=1, admitted=1, live=0)]
    for part, more in zip((llm, bench, runs), prefill_call(15, 2, 2)):
        part += more
    live = 2
    args = [dict(live=live, kv_tokens=30 + live * n) for n in range(STEPS + 1)]
    if form in ("waits_inside", "waits_outside"):
        for n in range(1, STEPS + 1):
            t = T0 + 12 * (n - 1)
            call_end = t + 11.5 if form == "waits_inside" else t + 3
            llm += [span("llm.step", 15, T0 + 12, admitted=1, live=live)] if n == 1 else [span("llm.admit", t, t + 0.5, waiting=0, admitted=0, live=live), span("llm.step", t + 0.5, t + 12, admitted=0, live=live)]
            llm += [span("llm.batch", t + 0.5, t + 1.5, live=live), span("llm.decode", t + 1.5, call_end, step=n, after_prefill=int(n == 1), **args[n]),
                    span("llm.decode.prep", t + 1.5, t + 2), span("llm.decode.dispatch", t + 2, t + 3, step=n),
                    span("llm.decode.wait", t + 3, t + 11.5, step=n), span("llm.decide", t + 11.5, t + 12, tokens=live)]
            bench.append(span("bench.decode", t + 1.5, call_end, **args[n]))
            runs.append(decode_ops(t + 2.5))
        t = T0 + 12 * STEPS
    else:
        t, landed = T0, None  # `landed`: when the step before's result is on the host
        llm.append(span("llm.step", 15, T0 + 3, admitted=1, live=live))
        for n in range(1, STEPS + 1):
            if n > 1:
                llm += [span("llm.admit", t, t + 0.5, waiting=0, admitted=0, live=live), span("llm.step", t + 0.5, landed + 0.5, admitted=0, live=live)]
            call_end = t + 3 if landed is None or form == "two_in_flight_outside" else landed
            llm += [span("llm.batch", t + 0.5, t + 1.5, live=live), span("llm.decode", t + 1.5, call_end, step=n, after_prefill=int(n == 1), **args[n]),
                    span("llm.decode.prep", t + 1.5, t + 2), span("llm.decode.dispatch", t + 2, t + 3, step=n)]
            if landed is not None:
                llm += [span("llm.decode.wait", t + 3, landed, step=n - 1), span("llm.decide", landed, landed + 0.5, tokens=live)]
            bench.append(span("bench.decode", t + 1.5, call_end, **args[n]))
            runs.append(decode_ops(T0 + 2.5 + DEVICE_STEP_MS * (n - 1)))
            t = call_end if landed is None else landed + 0.5
            landed = T0 + 2.5 + DEVICE_STEP_MS * n + 1
        # the loop's next turn finds a request waiting: it reads the last step first, at the top of the iteration
        llm += [span("llm.admit", t, t + 0.5, waiting=1, admitted=1, live=live), span("llm.step", t + 0.5, landed + 12.5, admitted=1, live=live),
                span("llm.decode.wait", t + 0.5, landed, step=STEPS), span("llm.decide", landed, landed + 0.5, tokens=live)]
        t = landed + 0.5 - 1  # the prefill below starts a millisecond on
    if form in ("waits_inside", "waits_outside"):
        llm += [span("llm.admit", t, t + 1, waiting=1, admitted=1, live=live), span("llm.step", t + 1, t + 13, admitted=1, live=live)]
    for part, more in zip((llm, bench, runs), prefill_call(t + 1, 3, 3)):
        part += more
    return llm, bench, runs


def form_evidence(form, early_ms=0.0):
    llm, bench, runs = loop(form)
    return evidence(spans=llm, runs=runs, early_ms=early_ms, bench=bench, arch=KERNELS)


def serving_readings(ev):
    """Every serving reader that pairs a host span with device work, or times a step, on one evidence."""
    cell = ev["cell"]
    out = {f"idle_{c}": tic.read(ev, {"cause": c}) for c in tic.CAUSES}
    out["idle"] = 100.0 * trace_of(ev).idle_share()
    out["device_step_p50_ms"] = tm.read(ev, {"stat": "p50_ms", "module": DECODE})
    out["device_roofline"] = tm.read(ev, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": cell})
    out["paged_window_roofline"] = trace_paged_window_roofline.read(ev, {"span": "bench.decode", "pattern": "kernel", "cell": cell})
    out["state_update_roofline"] = trace_state_update_roofline.read(ev, {"span": "bench.decode", "pattern": "kernel", "cell": cell})
    out["host_prep_ms"] = tps.read(ev, {"stat": "median_sum_ms", "within": "llm.decode", "spans": ["llm.decode.prep", "llm.decode.dispatch"]})
    out["batch_mean"] = span_stat.read(ev, {"span": "bench.decode", "stat": "mean_arg", "arg": "live"})
    out["period_p50_ms"] = span_period.read(ev, {"span": "bench.decode", "without": "bench.prefill"})
    return out


STEPS_OWN = ("device_step_p50_ms", "device_roofline", "paged_window_roofline", "state_update_roofline", "host_prep_ms", "batch_mean")


@pytest.mark.parametrize("early_ms", [0.0, 0.7])
def test_the_forms_by_hand(early_ms):
    """`waits_inside`: a window of 96 ms, the device busy 6 + 6 + 5 x 7.5 + 6 = 55.5; in flight 9 + 9 + 5 x 9.5 + 9 less that;
    no work 3; host 4 before the steps, 2.5 a step, 2 after. `two_in_flight`: 79 ms, the same busy time; in flight
    [1, 10], [16, 25], [27, 68.5] (the five steps' intervals overlap into one), [70, 79] less it; host 4 + 2 before the
    first launch + 1.5 after the last result."""
    got = {form: serving_readings(form_evidence(form, early_ms)) for form in FORMS}
    for form, window, flight, host in (("waits_inside", 96.0, 74.5, 18.5), ("waits_outside", 96.0, 74.5, 18.5), ("two_in_flight", 79.0, 68.5, 7.5), ("two_in_flight_outside", 79.0, 68.5, 7.5)):
        r = got[form]
        assert trace_of(form_evidence(form, early_ms)).window_s() == pytest.approx(window * MS)
        assert r["idle"] == pytest.approx(100.0 * (window - 55.5) / window)
        assert r["idle_in_flight"] == pytest.approx(100.0 * (flight - 55.5) / window) and r["idle_no_work"] == pytest.approx(100.0 * 3.0 / window)
        assert r["idle_host"] == pytest.approx(100.0 * host / window) and r["idle_hole"] == pytest.approx(0.0, abs=1e-9)
        assert r["idle_in_flight"] + r["idle_no_work"] + r["idle_host"] + r["idle_hole"] == pytest.approx(r["idle"])
        assert r["device_step_p50_ms"] == pytest.approx(DEVICE_STEP_MS) and r["host_prep_ms"] == pytest.approx(1.5) and r["batch_mean"] == 2.0
    bw, kv = 819e9, sum(30 + 2 * n for n in range(1, STEPS + 1))
    need = sum(5.5e9 + 1e6 * (30 + 2 * n) for n in range(1, STEPS + 1))
    for form in FORMS:
        assert got[form]["device_roofline"] == pytest.approx(100.0 * need / bw / (STEPS * DEVICE_STEP_MS * MS))  # 84.5 %
        assert got[form]["paged_window_roofline"] == pytest.approx(100.0 * 6e7 * kv / bw / (STEPS * 3.5 * MS))  # 75.4 %
        assert got[form]["state_update_roofline"] == pytest.approx(100.0 * 8e8 * 2 * STEPS / bw / (STEPS * 3.5 * MS))  # 55.8 %
    # the period: a launch every 12 ms where the host waits for each result, and the device's step where it does not
    assert got["waits_inside"]["period_p50_ms"] == got["waits_outside"]["period_p50_ms"] == pytest.approx(12.0)
    for form in FORMS[2:]:
        assert got[form]["period_p50_ms"] == pytest.approx(got[form]["device_step_p50_ms"]) == pytest.approx(DEVICE_STEP_MS)


@pytest.mark.parametrize("early_ms", [0.0, 0.7, 1.2])
def test_no_reader_tells_where_the_host_waits(early_ms):
    """The acceptance of ISSUE 45: nothing reads `None`, no share of a peak passes 100 %, `waits_outside` reads as
    `waits_inside` in EVERY reader (the same instants under other spans), and with two steps in flight what is the
    steps' own reads the same to 0.1 point; the period there is the device's step."""
    got = {form: serving_readings(form_evidence(form, early_ms)) for form in FORMS}
    for form in FORMS:
        assert all(v is not None for v in got[form].values()), (form, got[form])
        assert all(got[form][k] <= 100.0 for k in got[form] if k.endswith("roofline"))
    for inside, outside in (("waits_inside", "waits_outside"), ("two_in_flight", "two_in_flight_outside")):
        for k, v in got[inside].items():
            assert got[outside][k] == pytest.approx(v, abs=1e-9), (outside, k)
    for form in FORMS[2:]:
        for k in STEPS_OWN:
            assert got[form][k] == pytest.approx(got["waits_inside"][k], abs=0.1), (form, k)
        assert got[form]["period_p50_ms"] == pytest.approx(got[form]["device_step_p50_ms"], abs=0.1)
    # every step is joined in every form: five pairs, in order, each execution with its own ordinal's span
    for form in FORMS:
        steps = tm.decode_steps(form_evidence(form, early_ms), {"span": "llm.decode", "module": DECODE})
        assert [(s["args"]["step"], s["args"]["kv_tokens"]) for s, _m in steps] == [(n, 30 + 2 * n) for n in range(1, STEPS + 1)], form
        assert [m["run_id"] for _s, m in steps] == [2, 3, 4, 5, 6]


def test_the_next_wait_in_time_is_the_step_befores_with_two_in_flight():
    """What `in_flight_intervals` did until PR 45 (a dispatch closed by the next `.wait` in time) on the third form:
    step n's interval ends with step n-1's result, 14 of the steps' 37.5 busy milliseconds (the last execution whole)
    lie outside every interval, and the reader's invariant, all busy time inside the intervals, breaks."""
    llm, _bench, runs = loop("two_in_flight")
    llm = sorted(llm, key=lambda s: s["start"])
    waits = [s for s in llm if s["name"] == "llm.decode.wait"]
    by_time = tl.union([(d["start"], next((w["end"] for w in waits if w["start"] >= d["start"]), d["end"])) for d in llm if d["name"] == "llm.decode.dispatch"])
    by_step = [iv for iv in tic.in_flight_intervals(llm) if iv[0] == pytest.approx(27 * MS)]
    assert by_time[0] == pytest.approx((27 * MS, 36.5 * MS)) and by_time[-1][1] == pytest.approx(60.5 * MS) and by_step == [pytest.approx((27 * MS, 68.5 * MS))]
    busy = tl.union([(a * MS, b * MS) for n, iv in runs if n.startswith("jit_llm_decode") for a, b in iv])
    assert tl.measure(tl.subtract(busy, by_time)) == pytest.approx(14 * MS) and tl.measure(tl.subtract(busy, by_step)) == 0.0


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
    # what the retired `serve_idle_unexplained_pct` read, by a gap's midpoint: ~0 on a loop under spans, like the hole
    by_span = tps.idle_by_innermost_span(tr, tps.spans_of(engine_trace))
    assert by_span.get(tps.NO_SPAN, 0.0) < 0.005 * sum(by_span.values())


def test_the_device_roofline_on_the_recorded_trace_joins_all_seven_steps_by_their_ordinals(engine_trace):
    cell, args = engine_trace["cell"], {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": engine_trace["cell"]}
    steps = tm.decode_steps(engine_trace, args)
    ordinals = [s["args"]["step"] for s, _m in steps]
    assert ordinals == list(range(ordinals[0], ordinals[0] + 7))
    # this recording's device clock runs ~1.3 ms ahead of the host's: four of seven executions START before their own dispatch does
    flights = tm.step_flights(tps.spans_of(engine_trace), "llm.decode")
    early = [flights[s["args"]["step"]][0] - m["start"] for s, m in steps]
    assert sum(e > 0 for e in early) >= 4 and max(early) < 1e-3
    need = sum(dense_decoder.decode_step_min_bytes(TINY, int(s["args"]["live"]), int(s["args"]["kv_tokens"])) for s, _m in steps)
    device = tm.read(engine_trace, args)
    assert device == pytest.approx(100.0 * need / 819e9 / sum(m["end"] - m["start"] for _s, m in steps)) and 0.0 < device < 100.0
    assert cell.arch is dense_decoder


@pytest.mark.parametrize("path", OLDER)
def test_a_program_without_the_loop_spans_or_the_names_reads_as_nothing(path):
    ev = recorded(path)
    assert [tic.read(ev, {"cause": c}) for c in tic.CAUSES] == [None] * 4
    assert tm.read(ev, {"stat": "p50_ms", "module": DECODE}) is None
    assert tm.read(ev, {"stat": "roofline", "module": DECODE, "span": "llm.decode", "cell": ev["cell"]}) is None
