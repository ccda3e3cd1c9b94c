"""Helpers the readers share. A reader is `read(evidence, args) -> number | None`;
`evidence` is what the cell's runner returned (window, spans, timeline, marks,
worker facts), `args` the metric file's `args` plus `cell`. None means there
was nothing to read, and the metric is left out of the line."""

from __future__ import annotations

from typing import Any, Dict, List, Optional


def window_spans(evidence: Dict[str, Any], name: str) -> List[list]:
    """Spans of that name that ENDED inside the measured window: [name, t0, t1, args]."""
    w0, w1 = evidence["window"]
    return [s for s in evidence.get("spans", []) if s[0] == name and w0 <= s[2] <= w1]


def trace_of(evidence: Dict[str, Any]):
    """The run's device trace, read once; None in a run without one (or
    with no device plane, as in a CPU rehearsal)."""
    if "_trace" not in evidence:
        path = evidence["worker"].get("trace_path")
        tr = None
        if path:
            from ..lib.trace import Trace

            tr = Trace(path)
            if not tr.chips or not tr.spans:
                tr = None
        evidence["_trace"] = tr
    return evidence["_trace"]


def device_peaks(evidence: Dict[str, Any]) -> Optional[Dict[str, float]]:
    """The chip's peaks; None only in a CPU rehearsal, where a share of a
    peak would be a device number from a CPU run."""
    from ..lib import peaks

    device = evidence["worker"]["device"]
    if device["platform"] != "tpu" and evidence["cell"].allow_cpu:
        return None
    return peaks.for_kind(device["kind"])
