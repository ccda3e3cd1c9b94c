"""100 x (1 - union of device op intervals / traced window), mean over chips."""

from ._common import trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    share = tr.idle_share()
    return None if share is None else 100.0 * share
