"""The device's own record of an executable's runs: the first chip's line
`XLA Modules`, one event an execution, named `jit_<function>(<fingerprint>)`.
Since PR 40 PagedLM names its jitted closures (`llm_decode`,
`llm_prefill_p<pages>`), so a name pattern from the metric file tells the
decode step from every prefill bucket; before, all were `jit_step`, and this
reader finds nothing to read.

An execution's duration runs from its first op to its last and covers the
gaps between them, whatever the host does around it: it does not change when
the host stops waiting inside the span that launched it (two steps in flight).

Which step an execution is: the device runs one stream in launch order and
the program numbers its decode steps (`step` on `llm.decode`,
`llm.decode.dispatch`, `llm.decode.wait`), so the k-th decode execution of the
trace is the step with the k-th ordinal. Only where the two sequences start
(the trace's edges hold an execution without its span, or a span without its
execution) is read off the clocks, once for the whole trace: `anchor`. No
comparison of a device instant with a host instant decides a single pair, and
nothing here asks when the host waits for a step's result. (`join` keeps the
clock's way for spans without an ordinal, a prefill's: the builder's
tools/engine_launch_report.py joins both kinds through it.)

args.module: a regular expression on the event's name. args.stat:
  "p50_ms"    median device duration of the matching executions that lie in
              the traced window, ms
  "roofline"  over the executions in the window that have a step: the bytes
              the steps must read (the cell's architecture file:
              `decode_step_min_bytes` of the span's `live` / `kv_tokens`, or
              `decode_step_bytes` with the experts the program counted,
              `_counted.py`) / peak bytes/s / the executions' summed
              durations, %. At or below the op-time share of the same steps:
              the durations hold the ops' gaps too.

None where no execution matches (a program with unnamed executables) or no
`args.span` event carries a `step`."""

from __future__ import annotations

import gzip
import re
from typing import Dict, List, Optional, Tuple

from ..lib.stats import percentile
from ._common import device_peaks, trace_of
from ._counted import experts_touched_a_step
from .trace_program_spans import spans_of

LINE = "XLA Modules"
CLOCKS_AGREE_S = 1e-3  # lib/trace.py: device and host events share a time base to within a millisecond


def executions(path: str) -> List[Dict]:
    """The first chip's module events in order of execution: name, start, end
    (s, the trace's time base), run_id."""
    import jax

    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    chips = sorted((p for p in data.planes if p.name.startswith("/device:TPU:")), key=lambda p: p.name)
    out = []
    for line in chips[0].lines if chips else ():
        if line.name == LINE:
            for e in line.events:
                stats = dict(e.stats)
                # device_duration_ps where the event has it: the event's own duration is that, rounded to ns
                dur = stats["device_duration_ps"] * 1e-12 if "device_duration_ps" in stats else e.duration_ns * 1e-9
                out.append({"name": e.name, "start": e.start_ns * 1e-9, "end": e.start_ns * 1e-9 + dur, "run_id": stats.get("run_id")})
    out.sort(key=lambda m: (m["start"], m["run_id"] or 0))
    return out


def executions_of(evidence) -> List[Dict]:
    if "_executions" not in evidence:
        path = evidence["worker"].get("trace_path")
        evidence["_executions"] = executions(path) if path else []
    return evidence["_executions"]


def inside(tr, m: Dict) -> bool:
    """Whether an execution lies inside the traced window (lib/trace.py's:
    first `bench.*` span to the last), on the clock Trace put the ops on."""
    w0, w1 = tr.window()
    return m["start"] + tr.skew_s >= w0 and m["end"] + tr.skew_s <= w1


def in_window(evidence, pattern: str) -> List[Dict]:
    """Matching executions that lie inside the traced window."""
    tr = trace_of(evidence)
    if tr is None:
        return []
    rx = re.compile(pattern)
    return [m for m in executions_of(evidence) if rx.search(m["name"]) and inside(tr, m)]


def anchor(steps: List[int], limits: Dict[int, Tuple[float, float]], modules: List[Dict], skew_s: float = 0.0) -> Optional[int]:
    """The ordinal of the trace's first execution. A step's execution cannot
    start before its dispatch starts nor end after its wait ends; the anchor
    is the shift of the one sequence along the other that breaks those two
    the least, by the mean over the pairs it makes. A wrong shift breaks one
    of them by a step's length or by the host's time between two launches in
    EVERY pair, the right one by what the clocks disagree (lib/trace.py: under
    a millisecond), so the least is far from the next. None without a pair."""
    best = None
    for first in range(steps[0] - len(modules) + 1, steps[-1] + 1):
        broken = pairs = 0
        for k, m in enumerate(modules):
            if first + k in limits:
                lo, hi = limits[first + k]
                broken += max(0.0, lo - (m["start"] + skew_s)) + max(0.0, m["end"] + skew_s - hi)
                pairs += 1
        if pairs and (best is None or (broken / pairs, -pairs) < best[0]):
            best = ((broken / pairs, -pairs), first)
    return None if best is None else best[1]


def join_by_ordinal(spans: List[Dict], modules: List[Dict], skew_s: float = 0.0, flights: Optional[Dict[int, Tuple[float, float]]] = None) -> List[Tuple[Dict, Dict]]:
    """(span, execution) pairs in order of execution: the k-th execution is
    the step `anchor` + k. `spans` carry `step`; `flights` gives a step's
    (dispatch start, wait end) where the trace holds both; without it a
    step's limits are its span's own, which holds only for a program that
    waits inside the span. An execution whose step has no span, and a span
    whose step has no execution (the trace's edges), are left out."""
    by_step = {int(s["args"]["step"]): s for s in spans}
    steps = sorted(by_step)
    if flights is None:
        limits = {n: (by_step[n]["start"], by_step[n]["end"]) for n in steps}
    else:
        limits = {n: flights.get(n, (by_step[n]["start"], float("inf"))) for n in steps}
    first = anchor(steps, limits, modules, skew_s)
    return [] if first is None else [(by_step[first + k], m) for k, m in enumerate(modules) if first + k in by_step]


def join_by_start(spans: List[Dict], modules: List[Dict], skew_s: float = 0.0) -> List[Tuple[Dict, Dict]]:
    """For spans without an ordinal (a prefill: one at a time, read before
    anything else is launched): an execution belongs to the span it starts
    in, give or take what the two clocks agree to. A span with no execution
    or several is left out."""
    pairs, j = [], 0
    for s in spans:
        while j < len(modules) and modules[j]["start"] + skew_s < s["start"] - CLOCKS_AGREE_S:
            j += 1
        k = j
        while k < len(modules) and modules[k]["start"] + skew_s < s["end"]:
            k += 1
        if k - j == 1:
            pairs.append((s, modules[j]))
        j = k
    return pairs


def join(spans: List[Dict], modules: List[Dict], skew_s: float = 0.0, flights: Optional[Dict[int, Tuple[float, float]]] = None) -> List[Tuple[Dict, Dict]]:
    """Spans of one kind with the executions of their executable: by the
    ordinal where every span carries `step` (decode), by the clock where
    none does (prefill)."""
    if not spans or not modules:
        return []
    if all("step" in s["args"] for s in spans):
        return join_by_ordinal(spans, modules, skew_s, flights)
    return join_by_start(spans, modules, skew_s)


def step_flights(spans: List[Dict], name: str) -> Dict[int, Tuple[float, float]]:
    """step -> (start of its `<name>.dispatch`, end of its `<name>.wait`), for the steps the trace holds both of."""
    at = {part: {int(s["args"]["step"]): s for s in spans if s["name"] == f"{name}.{part}" and "step" in s["args"]} for part in ("dispatch", "wait")}
    return {n: (d["start"], at["wait"][n]["end"]) for n, d in at["dispatch"].items() if n in at["wait"]}


def decode_steps(evidence, args) -> List[Tuple[Dict, Dict]]:
    """The traced decode steps: the program's span with the device's
    execution, for the executions that lie inside the traced window."""
    tr, every = trace_of(evidence), spans_of(evidence) or []
    spans = [s for s in every if s["name"] == args["span"] and "step" in s["args"]]
    if tr is None or not spans:
        return []
    rx = re.compile(args["module"])
    modules = [m for m in executions_of(evidence) if rx.search(m["name"])]
    return [(s, m) for s, m in join(spans, modules, tr.skew_s, step_flights(every, args["span"])) if inside(tr, m)]


def step_bytes(evidence, cell, span_args) -> Optional[float]:
    """What that step must read: with the experts the program counted where
    the architecture file takes a count, else its minimum."""
    live, kv_tokens = int(span_args["live"]), int(span_args["kv_tokens"])
    if hasattr(cell.arch, "decode_step_bytes"):
        touched = experts_touched_a_step(evidence)
        return None if touched is None else cell.arch.decode_step_bytes(cell.config, live, kv_tokens, touched)
    return cell.arch.decode_step_min_bytes(cell.config, live, kv_tokens)


def read(evidence, args) -> Optional[float]:
    stat = args["stat"]
    if stat == "p50_ms":
        runs = in_window(evidence, args["module"])
        return percentile([(m["end"] - m["start"]) * 1e3 for m in runs], 50) if runs else None
    if stat == "roofline":
        steps = [(s, m) for s, m in decode_steps(evidence, args) if s["args"].get("live", 0) > 0]
        if not steps:
            return None
        cell = args["cell"]
        bw = device_peaks(evidence)["hbm_bytes_per_s"]
        least = traced = 0.0
        for s, m in steps:
            need = step_bytes(evidence, cell, s["args"])
            if need is None:
                return None
            least += need / bw
            traced += m["end"] - m["start"]
        return 100.0 * least / traced
    raise ValueError(f"unknown stat {stat!r}")
