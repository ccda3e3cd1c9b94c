"""One prefill and a few decode steps of a serving cell under several seeds'
weights, in one process: does the time of the program's own work follow the
seed? (PERF.md T1: a router's selecting bias once made a prefill cost 0.86-0.91
s by the seed, which spread a whole cell's `serve_tok_s` by 4.5-7 %; two
chip-minutes here show such a thing before six runs of the cell do.)

    chiprun -- python3 tools/seed_spread.py --workload gigachat35-serve-longanswer-batch --seeds 8 [--seed-list a,b,c] [--prompt 2048] [--steps 8]

Builds the cell's PagedLM at its configuration's widths once (the benchmark's
own mapping and weights: `benchmarks/lib/correct.init_weights`), then for each
seed puts that seed's weights in its place, prefills one prompt of `--prompt`
tokens into row 0 and runs `--steps` decode steps with every row live at
`--prompt` positions (each row its own pages), on the host's clock around
calls that end in the tokens' transfer. One JSON line a seed, then the spread
(IQR / median) of each. Not part of any check.
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


def cell_paged_lm(workload: str, seed: int):
    """(the cell's PagedLM at its configuration's widths under `seed`'s weights, seed -> that seed's weights): the
    benchmark's own mapping and weights. tools/prefill_chunk_bench.py builds its model here too."""
    import jax

    from benchmarks.lib import correct, spec
    from benchmarks.lib.worker_train import seeded_key
    from ray_tpu.models import transformer as tfm
    from ray_tpu.serve.llm.model import PagedLM

    cell = spec.find_cell(workload)
    cfg = cell.arch.model_config(cell.config)
    eng = {k: v["value"] for k, v in cell.config["assumed"].items()}
    init = jax.jit(lambda k: correct.init_weights(tfm, cfg, k))
    lm = PagedLM(cfg, init(seeded_key(seed)), num_pages=eng["pool_pages"], page_tokens=eng["page_tokens"],
                 max_slots=eng["max_slots"], max_pages_per_seq=eng["max_pages_per_seq"])
    return lm, lambda seed: init(seeded_key(seed))


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--first-seed", type=int, default=2147484600)
    ap.add_argument("--seed-list", default="", help="these seeds, comma-separated, in place of --seeds ones from --first-seed: the seeds of a cell's own runs, to set each run's reading beside its seed's prefill (six seeds once read 1.1 % where six others read 4.6 %: give it a dozen; PERF.md §6, PR 61)")
    ap.add_argument("--prompt", type=int, default=2048)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()

    import jax
    import numpy as np

    from benchmarks.lib.stats import iqr_share
    from ray_tpu.serve.llm.model import PromptTokens

    seeds = [int(x) for x in args.seed_list.split(",")] if args.seed_list else [args.first_seed + i for i in range(args.seeds)]
    lm, weights_of = cell_paged_lm(args.workload, seeds[0])
    B, T, P = lm.max_slots, lm.page_tokens, lm.max_pages_per_seq
    n_pages = -(-(args.prompt + args.steps) // T)
    tables = [[1 + row * P + j for j in range(n_pages)] for row in range(B)]
    rng = np.random.default_rng(0)
    rows = []
    for i, seed in enumerate(seeds):
        if i:
            lm.params = None  # the last seed's weights go before this one's come: two sets do not fit
            lm.params = weights_of(seed)
        prompt = PromptTokens([int(t) for t in rng.integers(1, lm.vocab, args.prompt)])
        prompt.slot = 0
        lm.prefill(prompt, tables[0][: -(-args.prompt // T)], 0)  # the first call of a shape compiles
        t0 = time.monotonic()
        lm.prefill(prompt, tables[0][: -(-args.prompt // T)], 0)
        prefill_s = time.monotonic() - t0
        tokens = [int(t) for t in rng.integers(1, lm.vocab, B)]
        lm.decode(tokens, [args.prompt] * B, tables)
        steps = []
        for j in range(args.steps):
            t0 = time.monotonic()
            tokens = list(lm.decode(tokens, [args.prompt + 1 + j] * B, tables))
            steps.append(time.monotonic() - t0)
        rows.append({"seed": seed, "prefill_ms": 1e3 * prefill_s, "decode_step_ms": 1e3 * statistics.median(steps)})
        print("seed_spread: " + json.dumps(rows[-1]), flush=True)
    out = {name: {"median": statistics.median(r[name] for r in rows), "spread": iqr_share([r[name] for r in rows])} for name in ("prefill_ms", "decode_step_ms")}
    print("seed_spread: " + json.dumps({"workload": args.workload, "prompt": args.prompt, "device": jax.devices()[0].device_kind, **out}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
