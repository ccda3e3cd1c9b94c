"""Runs one cell with some parameters of its traffic or config file changed,
for the builder's sweeps and trials (the knee of the chat cell, the largest
batch that runs). Prints every metric that has something to read. Never
part of a check: the driver runs benchmarks/run.py, which has no overrides.

    python3 benchmarks/tools/try_cell.py --workload <cell> --seed 1 --seconds 20 --trace 0 \
        --set traffic.rate_rps=1.5 --set traffic.batch_per_chip=3
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    sets = [argv[i + 1] for i, a in enumerate(argv) if a == "--set"]
    rest, skip = [], False
    for a in argv:
        if skip:
            skip = False
        elif a == "--set":
            skip = True
        else:
            rest.append(a)

    def prepare(cell):
        for item in sets:
            path, _, raw = item.partition("=")
            where, _, key = path.partition(".")
            target = {"traffic": cell.traffic, "config": cell.config}[where]
            parts = key.split(".")
            for part in parts[:-1]:
                target = target[part]
            target[parts[-1]] = json.loads(raw)
        print(f"try_cell: {cell.name} with {sets}", flush=True)

    from benchmarks import run

    return run.main(rest, prepare=prepare, every_metric=True)


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
