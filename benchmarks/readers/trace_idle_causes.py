"""What the first chip's idle time in the traced window is, by the PROGRAM's
spans (PR 40: the engine thread lies under `llm.idle` / `llm.admit` /
`llm.step` from its start to its stop): three causes, each as a share of the
traced window, so that they add up to `serve_device_idle_pct`.

  no_work    under `llm.idle`, and under an `llm.admit` that admitted nothing
             with nothing live: no request was there. A higher rate fills it.
  in_flight  between the start of an `llm.*.dispatch` and the end of its
             `.wait` (a decode step's: the wait that carries its `step`), less
             the device's busy time: launch latency, gaps inside the
             executable, the result's transfer and the host's wake-up.
             What dispatching step N+1 before reading step N hides.
  host       under `llm.admit` with work, `llm.batch`, `llm.emit`,
             `llm.*.prep` and the rest of `llm.step`: the host working while
             nothing is queued on the device.
  hole       what no span covers (not a metric; it must read under a point).

The device's clock and the host's agree to within a millisecond only
(lib/trace.py: modules were seen 0.4-0.8 ms "before" their dispatch), a tenth
of a decode step, so no device instant is compared with a host instant here.
The idle intervals are cut at the spans' borders by what must hold on one
clock: the device runs nothing that is not some step in flight. Outside the
in-flight intervals the chip is idle throughout, so no_work, host and hole
are those intervals' lengths; all of the window's busy time lies inside the
in-flight intervals, so their idle part is their length less that busy time
(a step's share: wait end - dispatch start - its module's busy time). An
idle gap that runs from one step's result over emit, admit, batch and prep
into the next step's launch is thereby cut at each border, not given whole
to its midpoint's owner (lib/trace.py `idle_gaps_by_span`, readers/
trace_program_spans.py `idle_by_innermost_span`).

args.cause: "no_work" | "in_flight" | "host" | "hole". None where the trace
holds no `llm.admit` event: a program whose loop is not under spans (the
spans of PR 24 alone cannot tell no work from a busy host)."""

from __future__ import annotations

from typing import Dict, List, Optional

from ..lib import trace as tl
from ._common import trace_of
from .trace_program_spans import device_idle, spans_of

CAUSES = ("no_work", "in_flight", "host", "hole")
HOST_SPANS = ("llm.admit", "llm.batch", "llm.emit", "llm.prefill.prep", "llm.decode.prep", "llm.step")


def in_flight_intervals(spans: List[Dict]) -> List[tl.Interval]:
    """(dispatch start, end of ITS wait) for every `llm.<x>.dispatch`: a
    dispatch that carries a `step` (decode, PR 40) is closed by the `.wait`
    of that step, wherever the host makes it (with two steps in flight the
    next wait in time is the step before's); one without (a prefill: one at a
    time) by the next `.wait` without a step. A dispatch whose wait the
    trace lost ends itself; a step's wait whose dispatch the trace lost (the
    trace began with that step in flight) has been in flight from the start."""
    out = []
    for kind in ("llm.prefill", "llm.decode"):
        dispatches = [s for s in spans if s["name"] == kind + ".dispatch"]
        waits = [s for s in spans if s["name"] == kind + ".wait"]
        of_step = {s["args"]["step"]: s for s in waits if "step" in s["args"]}
        in_time = [s for s in waits if "step" not in s["args"]]
        j = 0
        for d in dispatches:
            if "step" in d["args"]:
                w = of_step.get(d["args"]["step"])
            else:
                while j < len(in_time) and in_time[j]["start"] < d["start"]:
                    j += 1
                w = in_time[j] if j < len(in_time) else None
            out.append((d["start"], d["end"] if w is None else w["end"]))
        launched = {d["args"].get("step") for d in dispatches}
        out += [(float("-inf"), w["end"]) for n, w in of_step.items() if n not in launched]
    return tl.union(out)


def cause_intervals(spans: List[Dict]) -> Dict[str, List[tl.Interval]]:
    """The three causes' intervals on the host's clock, disjoint: in flight
    first, then no work, then whatever else of the engine's spans."""
    flight = in_flight_intervals(spans)
    no_work = tl.union([
        (s["start"], s["end"]) for s in spans
        if s["name"] == "llm.idle"
        or (s["name"] == "llm.admit" and not s["args"].get("admitted", 0) and not s["args"].get("live", 0))
    ])
    no_work = tl.subtract(no_work, flight)
    host = tl.union([(s["start"], s["end"]) for s in spans if s["name"] in HOST_SPANS])
    host = tl.subtract(tl.subtract(host, flight), no_work)
    return {"in_flight": flight, "no_work": no_work, "host": host}


def idle_seconds_by_cause(tr, spans: List[Dict]) -> Optional[Dict[str, float]]:
    """Idle seconds of the first chip in the traced window by cause; the four
    add up to the window less its busy time. None without `llm.admit`."""
    if not any(s["name"] == "llm.admit" for s in spans):
        return None
    w = tr.window()
    window_s = w[1] - w[0]
    busy_s = window_s - tl.measure(device_idle(tr))
    secs = {cause: tl.measure(tl.clip(iv, *w)) for cause, iv in cause_intervals(spans).items()}
    secs["hole"] = window_s - sum(secs.values())
    secs["in_flight"] -= busy_s
    return secs


def read(evidence, args):
    if args["cause"] not in CAUSES:
        raise ValueError(f"unknown cause {args['cause']!r}")
    tr, spans = trace_of(evidence), spans_of(evidence)
    if tr is None or not spans:
        return None
    secs = idle_seconds_by_cause(tr, spans)
    return None if secs is None else 100.0 * secs[args["cause"]] / tr.window_s()
