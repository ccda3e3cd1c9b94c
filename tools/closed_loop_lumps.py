"""How far does a closed-loop serving cell's `serve_tok_s` swing with WHERE its
window lies, before any run? A model of the loop on the CPU, no jax, no chip.

    python3 tools/closed_loop_lumps.py --traffic longctx-batch --prefill-ms 36,0.9 --step-ms 10.85,7.8

`benchmarks/lib/stats.serve_tokens` counts a prompt WHOLE at its first token,
so a window of few long prompts is a sum of few lumps, and a run whose
timeline lies a second off its neighbour's (a seed whose weights make the
prefill 3 % faster: `tools/seed_spread.py`; a stall in the lead-in) moves
lumps across the window's two edges. This tool plays
the traffic file's own schedule (`benchmarks/lib/traffic.generate`: every
length as the cell will draw it) through the engine's loop as `engine.py` runs
it: the waiting prompts of an iteration prefilled one after another, whole,
the first always and the next while they fit `--budget`; then one decode step
of every live row. Two speeds describe the program, read off a traced run or
`tools/seed_spread.py`:

  --prefill-ms a,b   a prompt of n thousand tokens takes a*n + b*n*n ms
  --step-ms c,d      a decode step takes c + d * (live positions / 1e6) ms

It prints the tokens/s the window would count with its start shifted by
`--shift-from` .. `--shift-to` seconds (the same shifts at `--speeds` of the
program), their spread as the contract defines it (IQR / median), the first
tokens a window holds and the largest prompt's share of its count. A spread
here near half of the metric's bound says that the cell will be admitted or
refused by the luck of six runs (PERF.md §6, PR 61's second session); the
cure is more requests a window or smaller prompts, not a longer lead-in (try
`--lead-in`). The numbers are a model's: never a device metric.
"""

from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import statistics
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmarks.lib import traffic as traffic_lib  # noqa: E402


def first_tokens_and_steps(traffic, horizon_s, prefill_ms, step_ms, slots, budget, speed=1.0):
    """[(seconds, tokens counted then)]: a prompt at its first token, a decode step's rows at its end."""
    by_client = collections.defaultdict(list)
    for r in traffic_lib.generate(traffic, horizon_s):
        by_client[r.client].append(r)
    sent = {c: 1 for c in by_client}
    waiting = collections.deque((c, by_client[c][0]) for c in sorted(by_client))
    left, positions = {}, {}
    t, events = 0.0, []
    while t < horizon_s:
        room, admitted = budget, []
        while waiting and len(left) + len(admitted) < slots:
            c, r = waiting[0]
            if admitted and r.prompt_tokens > room:
                break
            waiting.popleft()
            room -= r.prompt_tokens
            admitted.append((c, r))
        for c, r in admitted:
            n = r.prompt_tokens / 1e3
            t += (prefill_ms[0] * n + prefill_ms[1] * n * n) / 1e3 / speed
            events.append((t, r.prompt_tokens + 1))
            left[c], positions[c] = r.max_new_tokens - 1, r.prompt_tokens + 1
        if not left:
            break
        t += (step_ms[0] + step_ms[1] * sum(positions.values()) / 1e6) / 1e3 / speed
        events.append((t, len(left)))
        for c in list(left):
            left[c] -= 1
            positions[c] += 1
            if left[c] <= 0:
                del left[c], positions[c]
                waiting.append((c, by_client[c][sent[c]]))
                sent[c] += 1
    return events


def _floats(text: str):
    return tuple(float(x) for x in text.split(","))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--traffic", required=True, help="name of a file under benchmarks/traffic (closed loop)")
    ap.add_argument("--prefill-ms", required=True)
    ap.add_argument("--step-ms", required=True)
    ap.add_argument("--slots", type=int, default=64)
    ap.add_argument("--budget", type=int, default=16384, help="the configuration's prefill_token_budget")
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--lead-in", type=float, default=None, help="default: the file's lead_in_s")
    ap.add_argument("--shift-from", type=float, default=-1.0)
    ap.add_argument("--shift-to", type=float, default=8.0)
    ap.add_argument("--speeds", default="0.98,1,1.02")
    args = ap.parse_args()
    with open(os.path.join(ROOT, "benchmarks", "traffic", args.traffic + ".json")) as f:
        traffic = json.load(f)
    lead = float(traffic["lead_in_s"]) if args.lead_in is None else args.lead_in
    rates, lumps, largest = [], [], []
    for speed in _floats(args.speeds):
        events = first_tokens_and_steps(
            traffic, lead + args.seconds + args.shift_to + 5, _floats(args.prefill_ms), _floats(args.step_ms), args.slots, args.budget, speed
        )
        times = [t for t, _n in events]
        shift = args.shift_from
        while shift <= args.shift_to:
            inside = events[bisect.bisect_left(times, lead + shift) : bisect.bisect_left(times, lead + shift + args.seconds)]
            count = sum(n for _t, n in inside)
            prompts = [n for _t, n in inside if n > args.slots]
            rates.append(count / args.seconds / speed)
            lumps.append(len(prompts))
            largest.append(100.0 * max(prompts, default=0) / max(1, count))
            shift += 0.25
    q1, _q2, q3 = statistics.quantiles(rates, n=4)
    out = {
        "traffic": args.traffic, "lead_in_s": lead, "windows": len(rates),
        "model_tokens_per_s": {"median": statistics.median(rates), "min": min(rates), "max": max(rates)},
        "spread_iqr_over_median": (q3 - q1) / statistics.median(rates),
        "first_tokens_a_window": [min(lumps), max(lumps)],
        "largest_prompt_pct_of_count": statistics.median(largest),
    }
    print("closed_loop_lumps: " + json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
