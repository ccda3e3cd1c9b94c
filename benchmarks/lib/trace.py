"""Reduction of a jax profiler trace (.xplane.pb) to numbers.

What a TPU v5e trace holds (looked at by hand, PERF.md section 6): a plane
per chip, `/device:TPU:<n>`, whose line `XLA Ops` has one event per executed
HLO op (the event's name is the op's whole HLO text) and whose line
`Async XLA Ops` has the asynchronous copies and collectives; and a plane
`/host:CPU` whose line of the python thread holds the benchmark's own
`jax.profiler.TraceAnnotation` spans (`bench.*`, their keyword arguments as
event stats). Device and host events share one time base only to within a
millisecond: device modules were seen to start 0.4-0.8 ms "before" the host
span that launched them. Where the trace holds exactly one device module per
span, the skew is measured (the least shift that puts every module after its
span's start) and taken out; otherwise it is left in, and gaps are attributed
by their midpoint for what that resolution carries.

The traced window is from the start of the first `bench.*` span to the end
of the last: starting and stopping the profiler is not part of it.

Only `jax.profiler.ProfileData` is needed; no backend is opened.
"""

from __future__ import annotations

import gzip
import re
from typing import Callable, Dict, List, Optional, Tuple

Interval = Tuple[float, float]  # seconds

SPAN_PREFIX = "bench."
UNATTRIBUTED = "engine_loop__unattributed"


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def measure(intervals: List[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals if b > lo and a < hi]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """a minus b; both already unions (sorted, disjoint)."""
    out: List[Interval] = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


_CONTAINER = re.compile(r"%(while|conditional|call)[.\d]* = ")
_SHAPE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")


def display_name(hlo: str) -> str:
    """`%convert.48 = f32[4096,16,32,128]{...} convert(...)` -> `convert.48_f32_4096_16_32_128_`."""
    head, _, rest = hlo.partition(" = ")
    name = head.strip().lstrip("%")
    m = _SHAPE.search(rest)
    if not m:
        return name
    dims = m.group(2).replace(",", "_")
    return f"{name}_{m.group(1)}_{dims}_"


class Trace:
    """One trace file, read once: device op events per chip and host spans."""

    def __init__(self, path: str):
        import jax

        opener = gzip.open if path.endswith(".gz") else open
        with opener(path, "rb") as f:
            data = jax.profiler.ProfileData.from_serialized_xspace(f.read())
        self.ops: Dict[str, List[Tuple[str, float, float]]] = {}  # plane -> (hlo, start, end)
        self.async_ops: Dict[str, List[Tuple[str, float, float]]] = {}
        self.spans: List[Dict] = []
        modules: List[float] = []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                for line in plane.lines:
                    if line.name == "XLA Modules" and not modules:
                        modules = sorted(e.start_ns * 1e-9 for e in line.events)
                    if line.name in ("XLA Ops", "Async XLA Ops"):
                        evs = [
                            (e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events
                        ]
                        (self.ops if line.name == "XLA Ops" else self.async_ops)[plane.name] = evs
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith(SPAN_PREFIX):
                            self.spans.append({
                                "name": e.name,
                                "start": e.start_ns * 1e-9,
                                "end": (e.start_ns + e.duration_ns) * 1e-9,
                                "args": {k: v for k, v in e.stats},
                            })
        self.spans.sort(key=lambda s: s["start"])
        self.skew_s = 0.0
        if modules and len(modules) == len(self.spans):
            self.skew_s = max(0.0, max(s["start"] - m for s, m in zip(self.spans, modules)))
            for table in (self.ops, self.async_ops):
                for chip, evs in table.items():
                    table[chip] = [(n, a + self.skew_s, b + self.skew_s) for n, a, b in evs]

    # ------------------------------------------------------------ window

    @property
    def chips(self) -> List[str]:
        return sorted(self.ops)

    def window(self) -> Optional[Interval]:
        if not self.spans:
            return None
        return self.spans[0]["start"], max(s["end"] for s in self.spans)

    def window_s(self) -> float:
        w = self.window()
        return 0.0 if w is None else w[1] - w[0]

    def _busy(self, chip: str, keep: Optional[Callable[[str], bool]] = None) -> List[Interval]:
        w = self.window()
        if w is None:
            return []
        evs = self.ops.get(chip, [])
        return clip(union([(a, b) for n, a, b in evs if keep is None or keep(n)]), *w)

    def busy_s(self) -> float:
        """Seconds in which an op ran, averaged over the chips of the trace."""
        if not self.chips:
            return 0.0
        return sum(measure(self._busy(c)) for c in self.chips) / len(self.chips)

    def idle_share(self) -> Optional[float]:
        """1 - busy / window, the mean over the chips."""
        if not self.chips or not self.window_s():
            return None
        return 1.0 - self.busy_s() / self.window_s()

    # --------------------------------------------------------------- ops

    def op_seconds(self, pattern: str) -> float:
        """Total device time of ops whose HLO text matches, mean over chips."""
        if not self.chips:
            return 0.0
        rx, w = re.compile(pattern), self.window()
        total = 0.0
        for c in self.chips:
            total += sum(min(b, w[1]) - max(a, w[0]) for n, a, b in self.ops[c] if b > w[0] and a < w[1] and rx.search(n))
        return total / len(self.chips)

    def op_calls(self, pattern: str) -> List[Tuple[str, float]]:
        """(hlo, seconds) of every matching op inside the window on the first chip."""
        if not self.chips:
            return []
        rx, w = re.compile(pattern), self.window()
        return [(n, b - a) for n, a, b in self.ops[self.chips[0]] if a >= w[0] and b <= w[1] and rx.search(n)]

    def top_ops(self, k: int = 10) -> List[List]:
        """Ops by total device time (mean over chips). Control-flow ops are
        left out: a `while` spans the ops of its body, which are listed themselves."""
        if not self.chips:
            return []
        w, totals = self.window(), {}
        for c in self.chips:
            for n, a, b in self.ops[c]:
                if b > w[0] and a < w[1] and not _CONTAINER.match(n):
                    key = display_name(n)
                    totals[key] = totals.get(key, 0.0) + (min(b, w[1]) - max(a, w[0])) / len(self.chips)
        return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]

    def exposed_collective_share(self, pattern: str) -> Optional[float]:
        """Share of the window in which a collective (sync op, or an async
        one between its start and done) runs and no other op does; the worst chip."""
        if not self.chips or not self.window_s():
            return None
        rx, w, worst = re.compile(pattern), self.window(), 0.0
        for c in self.chips:
            coll = [(a, b) for n, a, b in self.ops[c] + self.async_ops.get(c, []) if rx.search(n)]
            compute = self._busy(c, keep=lambda n: not rx.search(n))
            exposed = subtract(clip(union(coll), *w), compute)
            worst = max(worst, measure(exposed) / self.window_s())
        return worst

    # ------------------------------------------------------------- spans

    def idle_gaps_by_span(self, k: int = 10) -> List[List]:
        """Idle seconds of the first chip inside the window, summed by the
        host span that covers each gap's midpoint."""
        w = self.window()
        if w is None or not self.chips:
            return []
        gaps = subtract([w], self._busy(self.chips[0]))
        totals: Dict[str, float] = {}
        for a, b in gaps:
            mid = (a + b) / 2
            owner = next((s["name"] for s in self.spans if s["start"] <= mid < s["end"]), UNATTRIBUTED)
            totals[owner] = totals.get(owner, 0.0) + (b - a)
        return [[n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]
