"""Tokens served per second, counted at the client: prompt tokens of requests
whose first token arrived in the window + output tokens that arrived in the
window, / the window's seconds."""

from ..lib import stats


def read(evidence, args):
    tl = evidence.get("timeline")
    if tl is None:
        return None
    w0, w1 = evidence["window"]
    return stats.serve_tokens(tl, w0, w1) / (w1 - w0)
