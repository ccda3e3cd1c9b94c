"""A statistic over the window's benchmark spans of one name (host clock).

args: span; stat = "p50_ms" (median duration) | "mean_arg" (mean of args[arg])
| "ms_per_k" (total duration / total args[arg] x 1000, e.g. ms per 1000 prompt tokens)."""

from ..lib.stats import percentile
from ._common import window_spans


def read(evidence, args):
    spans = window_spans(evidence, args["span"])
    if not spans:
        return None
    stat = args["stat"]
    if stat == "p50_ms":
        return percentile([(s[2] - s[1]) * 1e3 for s in spans], 50)
    if stat == "mean_arg":
        return sum(s[3][args["arg"]] for s in spans) / len(spans)
    if stat == "ms_per_k":
        total = sum(s[3][args["arg"]] for s in spans)
        return None if not total else sum(s[2] - s[1] for s in spans) * 1e3 / (total / 1000.0)
    raise ValueError(f"unknown stat {stat!r}")
