"""Records benchmarks/recorded/tiny_v5e_engine.xplane.pb.gz (run once, on the
chip), and reads a cell's traced segment the way that recording is read.

    chiprun -- python3 benchmarks/tools/record_engine_trace.py          # -> chiprun_out/recorded/
    python3 benchmarks/tools/record_engine_trace.py report <xplane.pb | a run's -trace directory>

The recording is tiny_v5e_llm's (tools/record_program_trace.py: an engine over
a 2-layer PagedLM, `bench.*` annotations around its adapter as BenchModel's)
taken with the engine of PR 40, whose loop lies under `llm.idle` / `llm.admit`
/ `llm.step` from start to stop and whose executables carry their names into
the line `XLA Modules`: one request alone, a pause with nothing to serve (an
`llm.idle` between two `bench.*` spans, so inside lib/trace.py's window), then
three requests at once. For the tests of readers/trace_idle_causes.py and
readers/trace_modules.py; the older recording stays as it is (its events are
what a program without those spans and names leaves).

`report` prints one JSON object, the builder's reading of a traced segment
(PERF.md quotes it): the idle seconds by cause, the executions by name, and
per decode step the launch and result halves of its time in flight (module
start against dispatch start, wait end against module end; the device's clock
is shifted by the least amount that puts every module after its dispatch, so
the launch half reads from 0 and the split between the halves carries that
caveat), the steps after a prefill apart from the others.
"""

from __future__ import annotations

import collections
import glob
import gzip
import json
import os
import shutil
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

DECODE = r"^jit_llm_decode\("


def report(path: str) -> dict:
    from benchmarks.lib import trace as tl
    from benchmarks.lib.stats import percentile
    from benchmarks.readers import trace_idle_causes as tic, trace_modules as tm, trace_program_spans as tps

    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
    ev = {"worker": {"trace_path": path}}
    tr, spans = tm.trace_of(ev), tps.spans_of(ev) or []
    if tr is None:
        return {"llm_events": dict(collections.Counter(s["name"] for s in spans)), "device": "no device plane or no bench.* span in this trace"}
    out = {"window_s": tr.window_s(), "busy_s": tr.busy_s(), "idle_pct": 100.0 * tr.idle_share(), "trace_skew_s": tr.skew_s}
    out["llm_events"] = dict(collections.Counter(s["name"] for s in spans))
    by_name = collections.defaultdict(list)
    for m in tm.executions_of(ev):
        by_name[m["name"].split("(")[0]].append((m["end"] - m["start"]) * 1e3)
    out["executions"] = {n: {"n": len(v), "p50_ms": percentile(v, 50), "sum_s": sum(v) / 1e3} for n, v in sorted(by_name.items())}
    secs = tic.idle_seconds_by_cause(tr, spans)
    if secs is not None:
        out["idle_s_by_cause"] = secs
        out["idle_pct_of_window_by_cause"] = {k: 100.0 * v / tr.window_s() for k, v in secs.items()}
    out["idle_s_by_innermost_span"] = tps.idle_by_innermost_span(tr, spans)

    # a decode step in flight, halves: by the program's spans and the device's execution of the same step
    by_step = lambda name: {s["args"]["step"]: s for s in spans if s["name"] == name and "step" in s["args"]}  # noqa: E731
    dispatch, wait = by_step("llm.decode.dispatch"), by_step("llm.decode.wait")
    steps = [(s, m) for s, m in tm.decode_steps(ev, {"span": "llm.decode", "module": DECODE}) if s["args"]["step"] in dispatch and s["args"]["step"] in wait]
    if steps:
        shift = max(dispatch[s["args"]["step"]]["start"] - m["start"] for s, m in steps)
        rows = collections.defaultdict(lambda: collections.defaultdict(list))
        for s, m in steps:
            d, w = dispatch[s["args"]["step"]], wait[s["args"]["step"]]
            inner = [p for p in spans if p["name"] == "llm.decode.prep" and s["start"] <= p["start"] < s["end"]]
            for kind in ("all", "after_prefill" if s["args"].get("after_prefill") else "after_decode"):
                r = rows[kind]
                r["in_flight_ms"].append((w["end"] - d["start"]) * 1e3)
                r["device_ms"].append((m["end"] - m["start"]) * 1e3)
                r["launch_ms"].append((m["start"] + shift - d["start"]) * 1e3)
                r["result_ms"].append((w["end"] - m["end"] - shift) * 1e3)
                r["host_prep_ms"].append((sum(p["end"] - p["start"] for p in inner) + d["end"] - d["start"]) * 1e3)
                r["dispatch_ms"].append((d["end"] - d["start"]) * 1e3)
        out["decode_steps"] = {
            kind: dict({k: {"p50": percentile(v, 50), "p90": percentile(v, 90)} for k, v in r.items()}, n=len(r["device_ms"]))
            for kind, r in rows.items()
        }
        out["device_clock_shift_s"] = shift
    tops = [s for s in spans if s["name"] in ("llm.idle", "llm.admit", "llm.step")]
    holes = [b["start"] - a["end"] for a, b in zip(tops, tops[1:])]
    out["top_level"] = {"n": len(tops), "largest_hole_ms": max(holes, default=0.0) * 1e3, "overlaps": sum(1 for h in holes if h < -1e-9),
                        "covered_s": tl.measure(tl.union([(s["start"], s["end"]) for s in tops]))}
    return out


def record() -> int:
    import jax

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm import EngineConfig, InferenceEngine
    from ray_tpu.serve.llm.model import PagedLM

    out = os.path.join(ROOT, "chiprun_out", "recorded")
    os.makedirs(out, exist_ok=True)
    dev = jax.devices()[0]
    print("device", dev.platform, dev.device_kind, len(jax.devices()), flush=True)
    cfg = tfm.TransformerConfig(
        vocab_size=1024, d_model=256, n_layers=2, n_heads=2, n_kv_heads=1, d_ff=512, max_seq_len=512,
    )
    lm = PagedLM(cfg, None, seed=0, num_pages=64, page_tokens=16, max_slots=4, max_pages_per_seq=8)

    class Annotated:
        """bench.* annotations around the program's adapter, as BenchModel's:
        `last_tokens` goes on unopened, so the engine's step ordinal reaches PagedLM."""

        vocab, max_slots, max_pages_per_seq = lm.vocab, lm.max_slots, lm.max_pages_per_seq

        def prefill(self, prompt, pages, cached_tokens):
            with jax.profiler.TraceAnnotation("bench.prefill", prompt_tokens=len(prompt), cached_tokens=int(cached_tokens)):
                return lm.prefill(prompt, pages, cached_tokens)

        def decode(self, last_tokens, positions, block_tables):
            live = [int(p) for p in positions if int(p) >= 0]
            with jax.profiler.TraceAnnotation("bench.decode", live=len(live), kv_tokens=sum(p + 1 for p in live)):
                return lm.decode(last_tokens, positions, block_tables)

    eng = InferenceEngine(Annotated(), EngineConfig(page_tokens=16, pool_pages=64), name="recorded")
    list(eng.generate(list(range(1, 41)), 3))  # compiles the 64-token prefill bucket and decode
    list(eng.generate(list(range(1, 101)), 2))  # and the 128-token bucket

    logdir = os.path.join(out, "tb_engine")
    shutil.rmtree(logdir, ignore_errors=True)
    jax.profiler.start_trace(logdir)
    outs = [list(eng.generate(list(range(3, 43)), 3))]
    time.sleep(0.02)  # nothing to serve: an llm.idle inside the window
    threads = [
        threading.Thread(target=lambda p=p, n=n: outs.append(list(eng.generate(p, n))))
        for p, n in ((list(range(1, 41)), 6), (list(range(5, 105)), 4), (list(range(1, 41)) + [7, 8, 9], 5))
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    jax.profiler.stop_trace()
    eng.close()
    (path,) = glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb"))
    dst = os.path.join(out, "tiny_v5e_engine.xplane.pb.gz")
    with open(path, "rb") as f, gzip.open(dst, "wb", 9) as g:
        g.write(f.read())
    print("tokens", sorted(len(o) for o in outs), "xplane bytes", os.path.getsize(path), "gz", os.path.getsize(dst), flush=True)
    print(json.dumps(report(dst)), flush=True)
    shutil.rmtree(logdir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    if sys.argv[1:2] == ["report"]:
        print(json.dumps(report(sys.argv[2])))
        sys.exit(0)
    sys.exit(record())
