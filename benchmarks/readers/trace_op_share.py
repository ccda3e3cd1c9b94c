"""100 x device time of the ops whose HLO text matches `pattern` / device busy time."""

from ._common import trace_of


def read(evidence, args):
    tr = trace_of(evidence)
    if tr is None or not tr.busy_s():
        return None
    return 100.0 * tr.op_seconds(args["pattern"]) / tr.busy_s()
