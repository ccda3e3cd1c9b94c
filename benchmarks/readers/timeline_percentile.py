"""A percentile over the client timeline. args: quantity = "ttft_ms" (first
token at the client - time the request was due, the window's requests) |
"gap_ms" (gaps between consecutive tokens of a request, pooled, tokens that
arrived in the window) | "late_ms" (sent - due); q = 0..100."""

from ..lib import stats


def read(evidence, args):
    w0, w1 = evidence["window"]
    tl = evidence.get("timeline")
    if tl is None:
        return None
    values = {
        "ttft_ms": lambda: stats.ttfts_ms(tl),
        "gap_ms": lambda: stats.gaps_ms(tl, w0, w1),
        "late_ms": lambda: stats.lateness_ms(tl),
    }[args["quantity"]]()
    return stats.percentile(values, float(args["q"]))
