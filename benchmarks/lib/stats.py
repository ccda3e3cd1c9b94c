"""Percentiles and the serving arithmetic on client timelines. Pure Python.

A timeline is a list of request records (dicts) on the driver's
`time.monotonic()` clock:
  due, sent          when the request was due / actually sent
  token_times        arrival time of every token at the client, in order
  prompt_tokens      length of the prompt
  counted            due inside the measured window
  error              None, or why it failed / was shed / never answered
"""

from __future__ import annotations

import math
import statistics
from typing import Dict, Iterable, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile (q in 0..100); None for no values."""
    xs = sorted(values)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def iqr_share(values: Sequence[float]) -> float:
    """Spread as the contract defines it: (Q3 - Q1) / median."""
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttfts_ms(timeline: Iterable[Dict]) -> List[float]:
    """First token at the client minus the time the request was DUE, for the
    window's requests that got a first token."""
    return [
        (r["token_times"][0] - r["due"]) * 1e3
        for r in timeline
        if r["counted"] and not r.get("error") and r["token_times"]
    ]


def gaps_ms(timeline: Iterable[Dict], w0: float, w1: float) -> List[float]:
    """Gaps between consecutive tokens of one request, pooled over every
    request, for tokens that arrived inside the window."""
    out = []
    for r in timeline:
        tt = r["token_times"]
        out.extend((b - a) * 1e3 for a, b in zip(tt, tt[1:]) if w0 <= b < w1)
    return out


def lateness_ms(timeline: Iterable[Dict]) -> List[float]:
    return [(r["sent"] - r["due"]) * 1e3 for r in timeline if r["counted"] and r.get("sent") is not None]


def stalled_share_pct(gaps: Sequence[float], factor: float = 1.5) -> Optional[float]:
    """Share of gaps longer than `factor` x the run's median gap."""
    if not gaps:
        return None
    med = statistics.median(gaps)
    return 100.0 * sum(1 for g in gaps if g > factor * med) / len(gaps)


def serve_tokens(timeline: Iterable[Dict], w0: float, w1: float) -> int:
    """Prompt tokens of requests whose FIRST token arrived in the window,
    plus every output token that arrived in the window."""
    total = 0
    for r in timeline:
        tt = r["token_times"]
        if tt and w0 <= tt[0] < w1:
            total += r["prompt_tokens"]
        total += sum(1 for t in tt if w0 <= t < w1)
    return total


def attempted_failed(timeline: Iterable[Dict]) -> (int, int):
    """The window's requests, and those of them that failed, were shed or
    got no first token."""
    counted = [r for r in timeline if r["counted"]]
    failed = [r for r in counted if r.get("error") or not r["token_times"]]
    return len(counted), len(failed)
