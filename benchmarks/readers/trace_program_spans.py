"""Reads the PROGRAM's own spans out of the traced segment: the host plane's
events whose name starts with `llm.` (ray_tpu/tracing.py `span(...,
device=True)`: engine step, prefill, decode, emit and PagedLM's prep /
dispatch / wait). lib/trace.py keeps only `bench.*` host events, so this
reader opens worker["trace_path"] itself; the device side (op intervals,
skew, window) and the interval arithmetic are lib/trace.py's, by import.

args.stat:
  "median_sum_ms"  for each `within` span, the summed duration of the `spans`
                   events that start inside it; the median, ms

`idle_by_innermost_span` is the run's `breakdown.idle_gaps` (run.py) and the
builder's report (tools/record_engine_trace.py); the share of the idle under no
span at all is `trace_idle_causes`' hole, the three causes' difference from
the idle share, and no metric of its own since PR 45.

None where the trace holds no llm.* event (a program without those spans)."""

from __future__ import annotations

import gzip
from typing import Dict, List, Optional

from ..lib import trace as tl
from ..lib.stats import percentile

PREFIX = "llm."
NO_SPAN = "(no llm.* span)"  # idle_by_innermost_span's name for what no program span covers


def program_spans(path: str) -> List[Dict]:
    """Host events named llm.*: name, start, end (s, the trace's time base), args."""
    import jax

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    out = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append({
                        "name": e.name, "start": e.start_ns * 1e-9, "end": (e.start_ns + e.duration_ns) * 1e-9,
                        "args": {k: v for k, v in e.stats},
                    })
    out.sort(key=lambda s: s["start"])
    return out


def spans_of(evidence) -> Optional[List[Dict]]:
    if "_program_spans" not in evidence:
        path = evidence["worker"].get("trace_path")
        evidence["_program_spans"] = (program_spans(path) or None) if path else None
    return evidence["_program_spans"]


def device_idle(tr) -> List[tl.Interval]:
    """Idle intervals of the first chip inside the traced window."""
    w = tr.window()
    busy = tl.clip(tl.union([(a, b) for _n, a, b in tr.ops[tr.chips[0]]]), *w)
    return tl.subtract([w], busy)


def idle_by_innermost_span(tr, spans: List[Dict]) -> Dict[str, float]:
    """Idle seconds summed by the SHORTEST llm.* span over each gap's midpoint
    (the innermost one: llm.decode.wait inside llm.decode inside llm.step).
    One pass over gaps and spans in order of time: `over` holds the spans that
    have begun and not ended at the midpoint in hand."""
    totals: Dict[str, float] = {}
    spans = sorted(spans, key=lambda s: s["start"])
    over, j = [], 0
    for a, b in device_idle(tr):
        mid = (a + b) / 2
        while j < len(spans) and spans[j]["start"] <= mid:
            over.append(spans[j])
            j += 1
        over = [s for s in over if mid < s["end"]]
        owner = min(over, key=lambda s: s["end"] - s["start"])["name"] if over else NO_SPAN
        totals[owner] = totals.get(owner, 0.0) + (b - a)
    return totals


def read(evidence, args):
    spans = spans_of(evidence)
    if not spans:
        return None
    stat = args["stat"]
    if stat == "median_sum_ms":
        parts = [s for s in spans if s["name"] in args["spans"]]
        sums = [
            sum(p["end"] - p["start"] for p in parts if o["start"] <= p["start"] < o["end"]) * 1e3
            for o in spans if o["name"] == args["within"]
        ]
        return percentile(sums, 50) if sums else None
    raise ValueError(f"unknown stat {stat!r}")
