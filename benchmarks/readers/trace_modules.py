"""The device's own record of an executable's runs: the first chip's line
`XLA Modules`, one event an execution, named `jit_<function>(<fingerprint>)`.
Since PR 40 PagedLM names its jitted closures (`llm_decode`,
`llm_prefill_p<pages>`), so a name pattern from the metric file tells the
decode step from every prefill bucket; before, all were `jit_step`, and this
reader finds nothing to read.

An execution's duration runs from its first op to its last and covers the
gaps between them, whatever the host does around it: it does not change when
the host stops waiting inside a span (two steps in flight), which is what
`decode_roofline.*` and `decode_step_p50_ms.*` lean on (lib/trace.py
`span_device_seconds` clips op time to the HOST span `bench.decode`).

args.module: a regular expression on the event's name. args.stat:
  "p50_ms"    median device duration of the matching executions that lie in
              the traced window, ms
  "roofline"  over those executions that the program's `args.span` events
              (llm.decode: `live`, `kv_tokens`, `step`) can be joined to: the
              bytes the steps must read (the cell's architecture file, as
              trace_decode_roofline.py / _counted.py choose) / peak bytes/s /
              the executions' summed durations, %. At or below the op-clipped
              share of the same steps: the durations hold the ops' gaps too.

None where no execution matches (a program with unnamed executables)."""

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


def in_window(evidence, pattern: str) -> List[Dict]:
    """Matching executions that lie inside the traced window (lib/trace.py's:
    first `bench.*` span to the last), on the clock Trace put the ops on."""
    tr = trace_of(evidence)
    if tr is None:
        return []
    rx, (w0, w1) = re.compile(pattern), tr.window()
    return [
        m for m in executions_of(evidence)
        if rx.search(m["name"]) and m["start"] + tr.skew_s >= w0 and m["end"] + tr.skew_s <= w1
    ]


def join(spans: List[Dict], modules: List[Dict], skew_s: float = 0.0) -> List[Tuple[Dict, Dict]]:
    """(span, execution) pairs in order: an execution belongs to the span it
    starts in, give or take what the two clocks agree to. A span with no
    execution or several (the trace's edges; a span of another kind) is left out."""
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


def decode_steps(evidence, args) -> List[Tuple[Dict, Dict]]:
    """The traced decode steps: the program's span with the device's execution."""
    tr = trace_of(evidence)
    spans = [s for s in spans_of(evidence) or [] if s["name"] == args["span"] and "step" in s["args"]]
    return join(spans, in_window(evidence, args["module"]), tr.skew_s) if tr is not None and spans else []


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
