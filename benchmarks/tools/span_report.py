"""What a RAY_TPU_TRACING=1 run of a cell says, from the program's span files
(ray_tpu/tracing.py JSONL, one per process) and the run's request timeline.

    RAY_TPU_TRACING=1 RAY_TPU_TRACE_DIR=<dir> python3 benchmarks/run.py --workload <cell> ...
    python3 benchmarks/tools/span_report.py hops  <dir> benchmarks/out/<cell>-<seed>-timeline.jsonl
    python3 benchmarks/tools/span_report.py setup <dir>
    python3 benchmarks/tools/span_report.py idle  benchmarks/out/<cell>-<seed>-trace

hops   per token of the measured window, the time between the hops of the
       stream path, all on CLOCK_MONOTONIC (one clock for every process of a
       host): engine emit -> core.stream_item start -> its end (report sent)
       -> core.stream_ack end (item in the owner's memory store) ->
       core.stream_next return -> serve.stream.next return -> the client's
       clock read (first and last token of a request: the timeline keeps
       only those). Token i of a request is emitted by the i-th llm.emit after
       its llm.first_token (every step emits for every live sequence).
setup  the one-off spans of process and replica start, in order.
idle   the device's idle seconds in a traced segment by the innermost llm.*
       span over each gap (benchmarks/readers/trace_program_spans.py).

Prints one JSON object. Builder's tool: PERF.md section 6 quotes it.
"""

from __future__ import annotations

import bisect
import collections
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

SETUP_NAMES = (
    "rt.init", "serve.run", "actor_launch", "actor_launch.gcs_register", "actor_launch.worker_spawn",
    "actor_launch.init", "train.worker_group.start", "train.setup_mesh",
)


from benchmarks.lib.stats import percentile as _pct  # noqa: E402


def _summary(values_ms):
    return {
        "n": len(values_ms), "p50": _pct(values_ms, 50), "p95": _pct(values_ms, 95), "max": max(values_ms, default=None),
        "mean": statistics.fmean(values_ms) if values_ms else None,
    }


def _weighted_pct(pairs, q):
    """Percentile of values each counted `weight` times: pairs = [(value, weight)]."""
    pairs = sorted(pairs)
    total = sum(w for _v, w in pairs)
    acc = 0.0
    for v, w in pairs:
        acc += w
        if acc >= q / 100 * total:
            return v
    return None


def hops(trace_dir: str, timeline_path: str) -> dict:
    from ray_tpu import tracing

    spans = tracing.collect(trace_dir)
    with open(timeline_path) as f:
        head = json.loads(f.readline())
        timeline = [json.loads(line) for line in f]
    w0, w1 = (int(t * 1e9) for t in head["window"])
    by_id = {s["span_id"]: s for s in spans}
    name = lambda s: s["name"].split(" ")[0]  # noqa: E731

    items, acks, nexts = {}, {}, {}
    for s in spans:
        n = name(s)
        if n == "core.stream_item":
            items[(s["attrs"]["task"], s["attrs"]["index"])] = s
        elif n == "core.stream_ack":
            acks[(s["attrs"]["task"], s["attrs"]["index"])] = s
        elif n == "core.stream_next" and s["attrs"].get("found") != "header":
            nexts[(s["attrs"]["task"], s["attrs"]["index"])] = s
    first = {s["trace_id"]: s for s in spans if name(s) == "llm.first_token"}
    emits = sorted((s["t1_ns"], s["attrs"].get("tokens", 0)) for s in spans if name(s) == "llm.emit")
    emit_ends = [t for t, _n in emits]
    # the client's clock reads, keyed by the request's serve.request span:
    # a record was `sent` just before handle.remote() opened that span
    requests = sorted((s["t0_ns"], s) for s in spans if name(s) == "serve.request" and s["attrs"].get("stream"))
    sent = sorted((int(r["sent"] * 1e9), r) for r in timeline if r.get("sent") is not None)
    client = {}
    for t_sent, rec in sent:
        i = bisect.bisect_left(requests, (t_sent,))
        if i < len(requests) and requests[i][0] - t_sent < 50e6:
            client[requests[i][1]["span_id"]] = rec

    rows = collections.defaultdict(list)
    tally = collections.Counter()
    next_ms_by_kind = collections.defaultdict(list)
    for key, nxt in nexts.items():
        if not (w0 <= nxt["t1_ns"] <= w1) or key not in items or key not in acks:
            continue
        item, ack = items[key], acks[key]
        ft = first.get(item["trace_id"])
        if ft is None:
            continue
        if key[1] == 0:
            t_emit = ft["t0_ns"]
        else:
            j = bisect.bisect_right(emit_ends, ft["t0_ns"]) + key[1] - 1
            if j >= len(emit_ends):
                continue
            t_emit = emit_ends[j]
        outer = by_id.get(nxt["parent_id"])
        if outer is None or name(outer) != "serve.stream.next":
            continue
        ms = lambda a, b: (b - a) / 1e6  # noqa: E731
        rows["1 emit -> stream_item start"].append(ms(t_emit, item["t0_ns"]))
        rows["2 stream_item (store + report sent)"].append(ms(item["t0_ns"], item["t1_ns"]))
        rows["3 report sent -> stream_ack end (in owner memstore)"].append(ms(item["t1_ns"], ack["t1_ns"]))
        rows["4 in memstore -> core.stream_next returns"].append(ms(ack["t1_ns"], nxt["t1_ns"]))
        rows["5 core.stream_next -> serve.stream.next returns"].append(ms(nxt["t1_ns"], outer["t1_ns"]))
        rows["total emit -> serve.stream.next returns"].append(ms(t_emit, outer["t1_ns"]))
        rows["core.stream_next duration"].append(ms(nxt["t0_ns"], nxt["t1_ns"]))
        rec = client.get(outer["parent_id"])
        if rec is not None:
            t_client = {0: rec["first_token"], rec["tokens"] - 1: rec["last_token"]}.get(key[1])
            if t_client is not None:
                rows["6 serve.stream.next -> client clock read (first/last tokens)"].append(
                    ms(outer["t1_ns"], int(t_client * 1e9)))
        a = nxt["attrs"]
        kind = f"remote_checks={a['remote_checks']} waits={a['waits']}"
        tally["next " + kind] += 1
        next_ms_by_kind[kind].append(ms(nxt["t0_ns"], nxt["t1_ns"]))
        tally["found=" + str(a.get("found"))] += 1
        tally["route=" + item["attrs"]["route"]] += 1
        tally["reported=" + item["attrs"]["reported"]] += 1
        tally["ack.notified=" + str(ack["attrs"]["notified"])] += 1
        tally["ack.inline=" + str(ack["attrs"]["inline"])] += 1
        tally["ack before next was called" if ack["t1_ns"] <= nxt["t0_ns"] else "ack while next waited"] += 1

    gaps = [((b[0] - a[0]) / 1e6, b[1]) for a, b in zip(emits, emits[1:]) if w0 <= b[0] <= w1]
    per_name = collections.Counter(name(s) for s in spans)
    files = [os.path.join(trace_dir, f) for f in os.listdir(trace_dir) if f.endswith(".jsonl")]
    t_all = [s["t0_ns"] for s in spans] + [s["t1_ns"] for s in spans]
    return {
        "tokens_joined": len(rows["total emit -> serve.stream.next returns"]),
        "hops_ms": {k: _summary(v) for k, v in sorted(rows.items())},
        "tallies": dict(tally),
        "core.stream_next_ms_by_kind": {k: _summary(v) for k, v in sorted(next_ms_by_kind.items())},
        "engine_emit_gap_ms_weighted_by_tokens": {
            "steps": len(gaps), "p50": _weighted_pct(gaps, 50), "p95": _weighted_pct(gaps, 95),
            "p99": _weighted_pct(gaps, 99),
        },
        "spans_total": len(spans), "spans_by_name": dict(per_name.most_common()),
        "span_seconds_covered": (max(t_all) - min(t_all)) / 1e9 if t_all else 0,
        "bytes_written": sum(os.path.getsize(p) for p in files), "files": len(files),
    }


def setup(trace_dir: str) -> dict:
    from ray_tpu import tracing

    spans = tracing.collect(trace_dir)
    if not spans:
        return {"spans": 0}
    t0 = min(s["t0_ns"] for s in spans)
    rows = []
    for s in spans:
        n = s["name"].split(" ")[0]
        if n in SETUP_NAMES or (n == "run" and any(k in s["name"] for k in ("setup_mesh", "setup_distributed", "start_training"))):
            rows.append({
                "name": s["name"][:70], "pid": s["pid"], "at_s": round((s["t0_ns"] - t0) / 1e9, 3),
                "seconds": round((s["t1_ns"] - s["t0_ns"]) / 1e9, 3),
            })
    return {"first_span_to_last_s": (max(s["t1_ns"] for s in spans) - t0) / 1e9, "spans": rows}


def idle(trace_logdir: str) -> dict:
    from benchmarks.lib.trace import Trace
    from benchmarks.lib.worker_train import find_xplane
    from benchmarks.readers import trace_program_spans as tps

    path = find_xplane(trace_logdir) if os.path.isdir(trace_logdir) else trace_logdir
    tr, spans = Trace(path), tps.program_spans(path)
    totals = tps.idle_by_innermost_span(tr, spans)
    durations = collections.defaultdict(list)
    for s in spans:
        durations[s["name"]].append((s["end"] - s["start"]) * 1e3)
    return {
        "window_s": tr.window_s(), "busy_s": tr.busy_s(), "idle_s": sum(totals.values()),
        "idle_s_by_innermost_llm_span": dict(sorted(totals.items(), key=lambda kv: -kv[1])),
        "llm_span_ms": {k: {"n": len(v), "p50": _pct(v, 50), "mean": statistics.fmean(v)} for k, v in sorted(durations.items())},
    }


if __name__ == "__main__":
    out = {"hops": hops, "setup": setup, "idle": idle}[sys.argv[1]](*sys.argv[2:])
    print(json.dumps(out, indent=1))
