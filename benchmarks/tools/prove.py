"""Makes the sets of runs that prove a cell's bounds, as the builder's
instructions set out: per set one run for each seed (the same seeds in every
set), every run a process of its own through benchmarks/run.py, all of one
cell in one call. Prints, per metric and set, the median and the spread
(inter-quartile range of statistics.quantiles(n=4) over the median), and
writes every run's line to chiprun_out/prove/<cell>.jsonl.

    chiprun -- python3 benchmarks/tools/prove.py --workload <cell> [--sets 2] [--seeds a,b,...] [--trace-too 1]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.lib.stats import iqr_share  # noqa: E402
SEEDS = "3000000019,2147483659,1000003,4093082899,77777,2863311531"


def one(workload: str, seed: int, seconds, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"), "--workload", workload,
           "--seed", str(seed), "--trace", str(trace)]
    if seconds is not None:
        cmd += ["--seconds", str(seconds)]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    wall = time.monotonic() - t0
    lines = p.stdout.strip().splitlines()
    facts = next((ln for ln in reversed(lines) if ln.startswith("benchmark: facts ")), None)
    row = {"workload": workload, "seed": seed, "trace": trace, "rc": p.returncode, "wall_s": wall,
           "line": json.loads(lines[-1]) if p.returncode == 0 and lines and lines[-1].startswith("{") else None,
           "facts": json.loads(facts[len("benchmark: facts "):]) if facts else None}
    if row["line"] is None:
        row["stderr_tail"] = p.stderr[-3000:]
    return row


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seeds", default=SEEDS)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace-too", type=int, default=0, help="also make one traced run at the end")
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    out_dir = os.path.join(ROOT, "chiprun_out", "prove")
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, args.workload + ".jsonl")
    sets = []
    with open(path, "a") as f:
        for k in range(args.sets):
            rows = []
            for seed in seeds:
                row = dict(one(args.workload, seed, args.seconds, 0), set=k)
                f.write(json.dumps(row) + "\n")
                f.flush()
                line = row["line"] or {}
                print(f"set {k} seed {seed} rc={row['rc']} wall={row['wall_s']:.0f}s correct={line.get('correct')} "
                      f"failed={line.get('failed')}/{line.get('attempted')} "
                      + " ".join(f"{n}={m['value']:.6g}" for n, m in line.get("metrics", {}).items()), flush=True)
                if row["line"] is None:
                    print(row.get("stderr_tail", "")[-1500:], flush=True)
                    return 1  # a cell that does not run is not worth eleven more tries
                rows.append(row)
            sets.append(rows)
        if args.trace_too:
            row = dict(one(args.workload, seeds[0], args.seconds, 1), set="traced")
            f.write(json.dumps(row) + "\n")
            print("traced: " + json.dumps(row["line"])[:6000], flush=True)
    names = list((sets[0][0]["line"] or {}).get("metrics", {}))
    widest = {}
    for name in names:
        for k, rows in enumerate(sets):
            vals = [r["line"]["metrics"][name]["value"] for r in rows if r["line"] and name in r["line"]["metrics"]]
            if len(vals) < 3:
                continue
            med = statistics.median(vals)
            spread = iqr_share(vals)
            warm = vals[1:] if k == 0 else vals  # a cell's very first run may compile
            print(f"{name}: set {k} median {med:.6g} spread {100 * spread:.3f}% min {min(vals):.6g} max {max(vals):.6g} "
                  f"(without the first run: median {statistics.median(warm):.6g})", flush=True)
            widest[name] = max(widest.get(name, 0.0), spread)
    for name, s in widest.items():
        print(f"{name}: widest spread {100 * s:.3f}% -> bound about {max(0.01, 5 * s):.4f}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
