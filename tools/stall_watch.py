"""Did the machine stand still, or one process of a benchmark run?

    python3 tools/stall_watch.py --out chiprun_out/stall_watch.jsonl &   # before the runs; kill it after them

A serving cell's driver keeps a 50 ms ticker (`benchmarks/lib/serve_driver.Ticker`)
and prints its largest gaps with the run's facts (`ticker_gaps`: [gap_s, at],
`at` on `time.monotonic()`). That ticker is a thread of the process that also
drives the cell's clients, so a gap there says "this process or the whole
host" and no more. This tool is the other witness: a process of its own that
does nothing but tick on the same clock (CLOCK_MONOTONIC is the machine's, not
a process's). A gap of the run's ticker that this one shows too, at the same
instant, is the machine's (or every core's); one it does not show is the
driver process's own. Every gap over `--gap` seconds is one JSON line: `gap_s`,
`at`, and what `/proc/stat`'s cpu line counted across it in jiffies (`steal`
is time the hypervisor gave to others) over the `over_s` seconds that end with
it, `/proc/loadavg` after it. Every ten seconds a line of the same
counters over the last second (`"tick": true`), so that a busy host shows
without a gap.
"""

from __future__ import annotations

import argparse
import json
import time

PERIOD_S = 0.05  # the driver's ticker's
EVERY_S = 10.0
FIELDS = ("user", "nice", "system", "idle", "iowait", "irq", "softirq", "steal")


def cpu() -> dict:
    with open("/proc/stat") as f:
        return dict(zip(FIELDS, (int(x) for x in f.readline().split()[1:9])))


def loadavg() -> str:
    with open("/proc/loadavg") as f:
        return f.read().strip()


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    ap.add_argument("--gap", type=float, default=0.5)
    args = ap.parse_args()
    last = base_at = wrote_at = time.monotonic()
    base = cpu()  # refreshed about once a second: what a line's jiffies are counted from
    with open(args.out, "a") as f:
        while True:
            time.sleep(PERIOD_S)
            now = time.monotonic()
            gap = now - last
            if gap > args.gap or now - wrote_at > EVERY_S:
                counted = cpu()
                line = {"gap_s": gap, "at": now, "over_s": now - base_at, "jiffies": {k: counted[k] - base[k] for k in FIELDS}, "loadavg": loadavg()}
                if gap <= args.gap:
                    line["tick"] = True
                f.write(json.dumps(line) + "\n")
                f.flush()
                wrote_at, base, base_at = now, counted, now
            elif now - base_at > 1.0:
                base, base_at = cpu(), now
            last = now


if __name__ == "__main__":
    main()
