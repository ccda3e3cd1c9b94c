"""100 x device seconds of the ops under the given scopes / device busy seconds.

args, each optional, an op counted where it meets all that are given:
`scopes`, names of the program's table (`transformer.SCOPES`, which reaches
the benchmark as `lib/scopes.json`; `unscoped` for the ops under none of
them), an op under the LAST name of the table on its `tf_op` path; `phase`
(forward | backward | recompute | update); `categories` (`hlo_category`
values). Ops of the `XLA Ops` line, containers left out; on several chips
over the chips' summed busy time. A fusion counts whole where its root lies
(benchmarks/lib/xplane_meta.py). None where the trace holds no op under a
scope of the table (a parent, a CPU rehearsal)."""

from ..lib import xplane_meta as xm


def read(evidence, args):
    table = xm.table_of(evidence)
    if table is None or not table.busy_s() or not table.scoped():
        return None
    scopes, phase, categories = args.get("scopes"), args.get("phase"), args.get("categories")
    ops = [
        op for op in table.sync
        if (scopes is None or table.scope_of(op) in scopes)
        and (phase is None or table.phase_of(op) == phase)
        and (categories is None or op.category in categories)
    ]
    return 100.0 * table.seconds(ops) / table.busy_s()
