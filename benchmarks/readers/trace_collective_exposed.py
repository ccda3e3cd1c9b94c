"""100 x time in which a collective runs and no other op does / traced window, worst chip."""

from ._common import trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None:
        return None
    share = tr.exposed_collective_share(args["pattern"])
    return None if share is None else 100.0 * share
