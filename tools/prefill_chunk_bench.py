"""One prefill of a serving cell's model at several sizes of the big chunk, in
one process: what `transformer.PREFILL_CHUNK_CAP` was chosen from (PERF.md §6,
PR 62).

    chiprun -- python3 tools/prefill_chunk_bench.py --workload mimov25-serve-longctx-batch --prompt 6912 [--rows 256,512,1024] [--whole-ring-product 1]

Builds the cell's PagedLM at its configuration's widths (tools/seed_spread.py's
`cell_paged_lm`), then for each size puts `transformer.prefill_big_chunk_tokens` in this tool's
hands (the program has no switch for it), drops the compiled executables and times
`--reps` prefills of one `--prompt`-token prompt (the cell's mean prompt) into
row 0, on the host's clock around calls that end in the token's transfer. 256
is the walk in small chunks alone. `--whole-ring-product 1` also times each
size with a window layer's ring attended as ONE masked product of all the
chunk's rows, not in bands (`_ring_chunk`); `--trace-dir` leaves a device trace
of one prefill a size. One JSON line a size. Not part of
any check.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--prompt", type=int, required=True)
    ap.add_argument("--rows", default="256,512,1024")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147484600)
    ap.add_argument("--whole-ring-product", type=int, default=0)
    ap.add_argument("--trace-dir", default="", help="also trace one prefill a size under <dir>/<rows>-<ring>: read it with tools/device_scope_report.py")
    args = ap.parse_args()

    import jax
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.model import PromptTokens
    from tools.seed_spread import cell_paged_lm

    lm, _ = cell_paged_lm(args.workload, args.seed)
    pages = list(range(1, 1 + -(-args.prompt // lm.page_tokens)))
    prompt = PromptTokens([int(t) for t in np.random.default_rng(0).integers(1, lm.vocab, args.prompt)])
    prompt.slot = 0
    ring = tfm.KINDS["window"]

    def whole_ring_product(cfg, ctx):  # `_ring_chunk` cuts its blocks where it is called: a block as long as the chunk
        floor, tfm.PREFILL_CHUNK_TOKENS = tfm.PREFILL_CHUNK_TOKENS, ctx["rows"]
        try:
            return ring.chunk(cfg, ctx)
        finally:
            tfm.PREFILL_CHUNK_TOKENS = floor

    forms = [("bands", ring)] + ([("whole_ring_product", ring._replace(chunk=whole_ring_product))] if args.whole_ring_product else [])
    for rows in (int(r) for r in args.rows.split(",")):
        for form, row in forms:
            tfm.prefill_big_chunk_tokens = lambda cfg, page_tokens, rows=rows: rows if rows > tfm.PREFILL_CHUNK_TOKENS else 0
            tfm.KINDS["window"] = row
            lm._prefill_jits.clear()
            lm._big_chunk, lm._prefill_big_jit = tfm.prefill_big_chunk_tokens(lm.cfg, lm.page_tokens), None
            t0 = time.monotonic()
            first = lm.prefill(prompt, pages, 0)  # the first call of a shape compiles
            compile_s = time.monotonic() - t0
            times = []
            for _ in range(args.reps):
                t0 = time.monotonic()
                lm.prefill(prompt, pages, 0)
                times.append(1e3 * (time.monotonic() - t0))
            if args.trace_dir:
                with jax.profiler.trace(os.path.join(args.trace_dir, f"{rows}-{form}")):
                    lm.prefill(prompt, pages, 0)
            print("prefill_chunk_bench: " + json.dumps({
                "workload": args.workload, "prompt": args.prompt, "rows": rows, "ring": form, "chunks": first.counters["prefill_chunks"],
                "prefill_ms": statistics.median(times), "min_ms": min(times), "ms_per_ktok": statistics.median(times) / first.computed_tokens * 1e3,
                "first_call_s": compile_s, "token": int(first), "device": jax.devices()[0].device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
