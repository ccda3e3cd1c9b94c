"""One traced run, read under two lists of per-layer names: does every quantity
the parent's list read in the cell still stand in the change's list, under the
name the table gives, with the same value, digit for digit?

    python3 benchmarks/tools/same_readings.py benchmarks/out/<cell>-<seed> <parent root> <change root> [--renamed table.json]

`<cell>-<seed>` is the `out` prefix of a `--trace 1` run (run.py leaves
`-evidence.json` beside `-trace/`: lib/evidence.py); a root holds a
`BENCHMARK.json` and the benchmark's directory (a `git archive` of the parent,
the checkout itself). Each root reads the evidence in a process of its own,
with ITS entries, metric files, readers and `lib/` (the tool's own file and
`lib/evidence.py` are the change's): a reader that changed what it computes
shows as a value that differs. The table ({old name: new name}, default
`tools/renamed_pr58.json`; a name not in it keeps itself) is the one thing
taken on trust, and a wrong row of it shows as a value that differs or a name
that is missing. No chip is needed, and no jax backend is opened.

Prints one JSON line: the cell, how many names each side holds there, how many
read the same number, how many are silent on both sides (a reader with nothing
to read: a CPU rehearsal's device metrics), `differ` / `lost` (exit code 1 if
either holds anything) and `added` (the change's names the parent had not).
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import subprocess
import sys

HERE = os.path.abspath(__file__)
RENAMED = os.path.join(os.path.dirname(HERE), "renamed_pr58.json")
SAID = ("unit", "better", "source", "layer", "moves")


def read_under(out_prefix: str, root: str) -> dict:
    """{name: [value or None, the entry's unit, better, source, layer, moves]} for the run's cell, by `root`'s files."""
    sys.path.insert(0, root)
    from benchmarks.lib import spec

    # lib/evidence.py is the change's (a parent has none), loaded into the root's package: its `Cell` is the root's
    described = importlib.util.spec_from_file_location("benchmarks.lib.evidence", os.path.join(os.path.dirname(os.path.dirname(HERE)), "lib", "evidence.py"))
    on_disk = importlib.util.module_from_spec(described)
    described.loader.exec_module(on_disk)
    assert os.path.abspath(spec.ROOT) == os.path.abspath(root), (spec.ROOT, root)
    evidence = on_disk.load(out_prefix, lambda name: spec.find_cell(name, root))
    cell = evidence["cell"]
    return {m["name"]: [spec.read_metric(cell, m["name"], evidence)] + [m[k] for k in SAID] for m in cell.per_layer}


def compare(out_prefix: str, parent: str, change: str, renamed: dict) -> dict:
    readers = [subprocess.Popen([sys.executable, HERE, "--read", out_prefix, os.path.abspath(root)], cwd=root, stdout=subprocess.PIPE,
                                stderr=subprocess.PIPE, text=True, env=dict(os.environ, JAX_PLATFORMS="cpu")) for root in (parent, change)]
    sides = []
    for root, p in zip((parent, change), readers):  # each decodes the trace: side by side
        stdout, stderr = p.communicate()
        if p.returncode != 0:
            raise SystemExit(f"same_readings: reading under {root} failed\n{stderr[-3000:]}")
        sides.append(json.loads(stdout.strip().splitlines()[-1]))
    old, new = sides
    same, silent, differ, lost = [], [], [], []
    for name, was in old.items():
        now_name = renamed.get(name, name)
        now = new.get(now_name)
        if now is None:
            lost.append({"parent": name, "change": now_name})
        elif was != now:  # a float's `repr` round-trips through JSON: equal means digit for digit
            differ.append({"parent": name, "change": now_name, "was": was, "now": now})
        else:
            (silent if was[0] is None else same).append(name)
    named = {renamed.get(name, name) for name in old}
    return {
        "out": os.path.basename(out_prefix), "parent_names": len(old), "change_names": len(new), "same": len(same),
        "silent_on_both": silent, "renamed": sorted(n for n in old if renamed.get(n, n) != n), "differ": differ, "lost": lost,
        "added": {n: v[0] for n, v in new.items() if n not in named},
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("out_prefix")
    ap.add_argument("roots", nargs="+", help="<parent root> <change root>")
    ap.add_argument("--renamed", default=RENAMED)
    ap.add_argument("--read", action="store_true", help="internal: print what ONE root reads")
    args = ap.parse_args(argv)
    out_prefix = os.path.abspath(args.out_prefix)
    if args.read:
        print(json.dumps(read_under(out_prefix, args.roots[0])))
        return 0
    with open(args.renamed) as f:
        renamed = json.load(f)
    verdict = compare(out_prefix, *args.roots, renamed)
    print(json.dumps(verdict))
    return 1 if verdict["differ"] or verdict["lost"] else 0


if __name__ == "__main__":
    sys.exit(main())
