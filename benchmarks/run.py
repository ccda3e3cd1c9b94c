"""Runs ONE cell of BENCHMARK.json once and prints the contract's last line.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

--trace 0 reports the cell's end-to-end metrics, --trace 1 its per-layer
metrics (and `breakdown`, `device.busy_s`, `device.window_s`). Everything
else goes on earlier lines or into benchmarks/out/. This process never opens
a jax backend: the chip belongs to the trainer's worker or the serve replica
(benchmarks/README.md says how the pieces of a cell are found by name).

Exit codes: 0 and a last line; 3 no TPU (or fewer chips than the cell asks
for), nothing printed on stdout's last line; anything else is a failure.
"""

from __future__ import annotations

import time

T_PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def idle_gaps(evidence, tr, k: int = 10):
    """The first chip's idle seconds by what the host was doing: by the
    program's innermost `llm.*` span where its loop lies under spans from start
    to stop (`llm.admit` events, PR 40: then nearly no gap is left unnamed), by
    the benchmark's own `bench.*` spans where it does not (a training cell, an
    older program), whose catch-all is everything between two model calls."""
    from benchmarks.lib.trace import UNATTRIBUTED
    from benchmarks.readers import trace_program_spans as tps

    spans = tps.spans_of(evidence) or []
    if not any(s["name"] == "llm.admit" for s in spans):
        return tr.idle_gaps_by_span(k)
    totals = tps.idle_by_innermost_span(tr, spans)
    return [[UNATTRIBUTED if n == tps.NO_SPAN else n, s] for n, s in sorted(totals.items(), key=lambda kv: -kv[1])[:k]]


def main(argv=None, prepare=None, every_metric=False) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks.lib import driver, spec

    cell = spec.find_cell(args.workload)
    cell.seed, cell.trace, cell.t_process_start = args.seed, bool(args.trace), T_PROCESS_START
    cell.seconds = float(args.seconds if args.seconds is not None else spec.benchmark_json()["run_seconds"])
    if prepare is not None:
        prepare(cell)  # benchmarks/rehearse.py only: tiny widths on a CPU, never a measurement
    os.makedirs(os.path.join(cell.bench_dir, "out"), exist_ok=True)

    evidence = spec.load_runner(cell).run(cell)
    if cell.trace:  # beside the trace: tools/same_readings.py reads two lists of names again from this very run
        from benchmarks.lib import evidence as on_disk

        on_disk.write(cell, evidence)
    if driver.backend_initialized():
        print("benchmark: the driver process opened a jax backend", file=sys.stderr)
        return 4

    wanted = cell.per_layer if cell.trace else cell.end_to_end
    if every_metric:  # benchmarks/tools/try_cell.py: a sweep wants lateness and backlog beside the tails
        wanted = cell.end_to_end + cell.per_layer
    metrics = {}
    for m in wanted:
        value = spec.read_metric(cell, m["name"], evidence)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    worker = evidence["worker"]
    device = dict(worker["device"], memory_peak_bytes=worker["memory_peak_bytes"])
    line = {
        "correct": bool(evidence["correct"]),
        "attempted": int(evidence["attempted"]),
        "failed": int(evidence["failed"]),
        "metrics": metrics,
        "device": device,
    }
    facts = {k: evidence.get(k) for k in ("checks", "training", "served_sample", "ticker_gaps", "load_facts") if evidence.get(k) is not None}
    for k in ("loss", "setup_parts_s", "warmup", "n_params", "arch_file"):
        if worker.get(k) is not None:
            facts[k] = worker[k]
    if cell.trace:
        from benchmarks.readers._common import trace_of

        tr = trace_of(evidence)
        if tr is not None:
            device["busy_s"], device["window_s"] = tr.busy_s(), tr.window_s()
            line["breakdown"] = {"device_ops": tr.top_ops(10), "idle_gaps": idle_gaps(evidence, tr)}
    # Each number that `correct` compared, beside its limit: the line's last key, and stderr's last lines.
    line["compared"] = {name: {"value": value, "limit": limit} for name, (value, limit) in evidence["compared"].items()}
    print("benchmark: facts " + json.dumps(facts, default=str), flush=True)
    print(json.dumps(line), flush=True)
    print("benchmark: checks " + json.dumps(evidence["checks"]), file=sys.stderr, flush=True)
    for name, c in line["compared"].items():
        print(f"benchmark: compared {name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
