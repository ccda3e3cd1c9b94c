"""What a launch costs the engine thread by what ran just before it, from a
serving cell's traced segment (run on the machine that holds the xplane):

    python3 tools/engine_launch_report.py <a run's -trace directory | xplane.pb[.gz]>

One JSON object. For the decode steps and for the prefills alike, split by
what the engine thread did last: `after_decode` (the launch follows a decode
step's tokens: up to a slot's worth of streams were woken, or are being) and
`after_prefill` (it follows a prefill, whose one first token woke one stream).
A prefill is `after_decode` when it is the first of its `llm.step` and
`after_prefill` when another prefill of the same step ran before it. Each
group: `prep_ms` (the `.prep` span), `dispatch_ms` (the jitted call
returning), `launch_ms` (the executable's start on the device less the
dispatch's start; the device's clock is shifted by the least amount that puts
every decode execution after its dispatch, as
benchmarks/tools/record_engine_trace.py does, so the fastest launch reads 0),
`emit_under_ms` (the `llm.emit` spans that lie between the dispatch and the
end of the wait: deliveries made under the step in flight, PR 43; 0 on a
program that delivers in front of the dispatch), `result_ms` (the end of the
wait less the execution's end: the result's way back, and how late the host
came for it), p50 and p90, and `n`.
A decode step's dispatch and wait are the ones that carry its `step`, wherever
they lie: since PR 46 the engine reads a step from the hook of the launch
behind it, so the `llm.decode.wait` INSIDE a step's `llm.decode` is the step
before's, and a step's limits for the join are its flight
(`trace_modules.step_flights`), not its span.
`emit` counts the `llm.emit` spans and their seconds, all and those marked
`under_step`, and lists the first dozen made at once with the engine spans
around them; `spans` gives every `llm.*` span's count, p50, p90 and sum.
"""

from __future__ import annotations

import bisect
import collections
import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.lib.stats import percentile  # noqa: E402


def _groups(rows):
    return {
        kind: dict({k: {"p50": percentile(v, 50), "p90": percentile(v, 90)} for k, v in r.items()}, n=len(r["dispatch_ms"]))
        for kind, r in rows.items()
    }


def report(path: str) -> dict:
    from benchmarks.readers import trace_modules as tm, trace_program_spans as tps

    if os.path.isdir(path):
        (path,) = glob.glob(os.path.join(path, "plugins", "profile", "*", "*.xplane.pb"))
    ev = {"worker": {"trace_path": path}}
    # The device's clock against the host's, as the readers settle it: lib/trace.py's skew, then a decode
    # execution belongs to the step of its ordinal and a prefill's to the span it starts in (readers/trace_modules.join).
    tr = tm.trace_of(ev)
    return summarize(tps.spans_of(ev) or [], tm.executions_of(ev), tr.skew_s if tr is not None else 0.0)


def summarize(spans, mods, skew: float = 0.0) -> dict:
    """The report from the program's `llm.*` spans (sorted by start) and the device's executions."""
    from benchmarks.readers import trace_modules as tm

    named = collections.defaultdict(list)
    for s in spans:
        named[s["name"]].append(s)
    starts = {n: [s["start"] for s in v] for n, v in named.items()}

    def inside(name, a, b):
        """The `name` spans that start in [a, b)."""
        v, k = named[name], starts.get(name, [])
        return v[bisect.bisect_left(k, a): bisect.bisect_left(k, b)]

    def launches(span_name, module_prefix, keep=lambda s: True):
        """(engine span, its dispatch, its wait, its execution) for every `span_name` that has one of each."""
        out = []
        parts = {part: {s["args"]["step"]: s for s in named[f"{span_name}.{part}"] if "step" in s["args"]} for part in ("dispatch", "wait")}
        kept, executions = [s for s in named[span_name] if keep(s)], [m for m in mods if m["name"].startswith(module_prefix)]
        for s, m in tm.join(kept, executions, skew, tm.step_flights(spans, span_name)):
            if "step" in s["args"]:  # by the ordinal: the wait may lie under a later launch
                d, w = ([parts[part][s["args"]["step"]]] if s["args"]["step"] in parts[part] else [] for part in ("dispatch", "wait"))
            else:
                d, w = inside(span_name + ".dispatch", s["start"], s["end"]), inside(span_name + ".wait", s["start"], s["end"])
            if len(d) == 1 and len(w) == 1:
                out.append((s, d[0], w[0], m))
        return out

    decodes = launches("llm.decode", "jit_llm_decode")
    if not decodes:
        return {"llm_events": {n: len(v) for n, v in named.items()}, "decode": "no decode step with its dispatch, wait and execution in this trace"}
    shift = max(d["start"] - m["start"] for _s, d, _w, m in decodes)

    def row(r, span, d, w, m, prep_name):
        prep = inside(prep_name, span["start"], span["end"])
        r["prep_ms"].append(sum(p["end"] - p["start"] for p in prep) * 1e3)
        r["dispatch_ms"].append((d["end"] - d["start"]) * 1e3)
        r["launch_ms"].append((m["start"] + shift - d["start"]) * 1e3)
        r["emit_under_ms"].append(sum(e["end"] - e["start"] for e in inside("llm.emit", d["end"], w["end"])) * 1e3)
        r["result_ms"].append((w["end"] - m["end"] - shift) * 1e3)

    out = {"device_clock_shift_s": shift}
    rows = collections.defaultdict(lambda: collections.defaultdict(list))
    for s, d, w, m in decodes:
        row(rows["after_prefill" if s["args"].get("after_prefill") else "after_decode"], s, d, w, m, "llm.decode.prep")
    out["decode"] = _groups(rows)

    rows = collections.defaultdict(lambda: collections.defaultdict(list))
    for s, d, w, m in launches("llm.prefill", "jit_llm_prefill", keep=lambda s: "rid" in s["args"]):
        step = [o for o in named["llm.step"] if o["start"] <= s["start"] < o["end"]]
        first = not step or not [p for p in inside("llm.prefill", step[0]["start"], s["start"]) if "rid" in p["args"]]
        row(rows["after_decode" if first else "after_prefill"], s, d, w, m, "llm.prefill.prep")
    out["prefill"] = _groups(rows)

    under = [e for e in named["llm.emit"] if e["args"].get("under_step")]
    out["emit"] = {
        "n": len(named["llm.emit"]), "s": sum(e["end"] - e["start"] for e in named["llm.emit"]),
        "under_step_n": len(under), "under_step_s": sum(e["end"] - e["start"] for e in under),
    }
    engine = [s for s in spans if s["name"] in ("llm.admit", "llm.batch", "llm.decide", "llm.prefill", "llm.decode", "llm.step")]
    out["emit"]["at_once"] = [
        {"tokens": e["args"].get("tokens"), "start": e["start"],
         "inside": [s["name"] for s in engine if s["start"] <= e["start"] and e["end"] <= s["end"]]}
        for e in named["llm.emit"] if not e["args"].get("under_step")
    ][:12]
    out["spans"] = {
        n: {"n": len(v), "p50_ms": percentile([(s["end"] - s["start"]) * 1e3 for s in v], 50),
            "p90_ms": percentile([(s["end"] - s["start"]) * 1e3 for s in v], 90), "sum_s": sum(s["end"] - s["start"] for s in v)}
        for n, v in sorted(named.items())
    }
    return out


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1])))
