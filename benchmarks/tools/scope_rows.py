"""The nine rows that partition a train step's `XLA Ops` time by scope, read from a traced run's evidence:

    python3 benchmarks/tools/scope_rows.py benchmarks/out/<cell>-<seed>

Prints one JSON line: each `train_scope_share_pct.<row>` as the metric's own file reads it (a row the cell does not
list is read with the row's scopes all the same: 0.0 where the step opens none of them), their sum, and the listed
share of the busy time (100 x the seconds of every listed op / busy seconds: what a `while` holds beyond its body's
ops is in no row). Exit code 1 unless the two agree to 1e-6 and every row of the partition has a metric file with
the row's scopes. No chip, no backend; `tools/device_scope_report.py` prints the same trace a row a (scope, phase)."""

from __future__ import annotations

import glob
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def main(argv=None) -> int:
    from benchmarks.lib import evidence as on_disk, spec, xplane_meta as xm
    from benchmarks.readers import trace_scope_share

    (out_prefix,) = argv if argv is not None else sys.argv[1:]
    evidence = on_disk.load(os.path.abspath(out_prefix), spec.find_cell)
    cell, table = evidence["cell"], xm.table_of(evidence)
    stem = os.path.join(cell.bench_dir, "metrics", "train_scope_share_pct.")
    files = {path[len(stem): -len(".json")]: spec.load_json(path) for path in sorted(glob.glob(stem + "*.json"))}
    scopes = sorted(s for mf in files.values() for s in mf["args"]["scopes"])
    rows = {row: trace_scope_share.read(evidence, mf["args"]) for row, mf in files.items()}
    listed = 100.0 * table.seconds(table.sync) / table.busy_s()
    whole = scopes == sorted([*xm.program_scopes(), xm.UNSCOPED])  # each scope of the program's table in exactly one row
    said = {"out": os.path.basename(out_prefix), "rows": rows, "sum": sum(rows.values()), "listed_share_of_busy_pct": listed,
            "every_scope_in_one_row": whole, "in_cell": [m["name"].rpartition(".")[2] for m in cell.per_layer if m["name"].startswith("train_scope_share_pct.")]}
    print(json.dumps(said))
    return 0 if whole and abs(said["sum"] - listed) < 1e-6 else 1


if __name__ == "__main__":
    sys.exit(main())
