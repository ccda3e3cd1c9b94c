"""Milliseconds a traced step in which a collective under the given scopes runs
(`hlo_category` a collective: on the `XLA Ops` line, or on `Async XLA Ops` from
its start to its done) and no other op of the same chip does; the worst chip,
the traced window's total over its steps (`bench.train_step` spans). args:
`scopes`, as `trace_scope_share` takes them. None where no such collective ran
(one chip, a parent)."""

from ..lib import xplane_meta as xm
from ._common import trace_of


def read(evidence, args):
    table = xm.table_of(evidence)
    if table is None or not table.scoped():
        return None
    ops = [op for op in table.sync + table.flying if op.collective and table.scope_of(op) in args["scopes"]]
    steps = len(trace_of(evidence).spans)
    if not ops or not steps:
        return None
    return 1e3 * table.exposed_s(ops) / steps
