"""The routed FFN's two serving forms alone on the chip, and the grouped kernels against their bytes.

    python3 tools/grouped_matmul_bench.py [--shapes trinity,solar] [--rows 64,128,256] [--tiles 16,64,128] [--layers 4] [--buffers 3 --block-mb 4]

For each served shape (Trinity-Mini: 128 experts held of 128, top-8, d 2048, f
1024, sigmoid router; Solar-Open2: 40 held of 320, top-8, d 4096, f 1280) and
each row count of a call, times over `--layers` layers inside one jit (a scan,
the expert stacks in place as a serving step holds them):

- `_routed_ffn` whole, in each of its two forms (the cut moved under and over
  the rows): router, the every-expert products and their weighted sum; or
  router, sort, dispatch, the grouped kernels, combine. Microseconds a layer,
  and the largest difference between the two forms' results;
- the grouped kernels alone on the first layer's routing (`grouped_swiglu`:
  gate and up; `grouped_matmul`: down), per row tile in `--tiles`:
  microseconds a call beside the touched experts' bytes and the share of the
  chip's HBM peak that is (benchmarks/lib/peaks.json, keyed by device kind).

Random normal rows and weights: the router's skew is that of random weights,
as in the cells. Refuses to run off a TPU: a CPU time is not a device number.
A builder's tool; no test and no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPES = {
    "trinity": dict(d_model=2048, d_ff=1024, n_experts=128, n_experts_per_tok=8, router_score="sigmoid", norm_topk_prob=True, route_scale=2.826),
    "solar": dict(d_model=4096, d_ff=1280, n_experts=320, n_experts_per_tok=8, router_score="sigmoid", norm_topk_prob=True, n_experts_held=40, first_expert=80),
}


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="trinity,solar")
    ap.add_argument("--rows", default="64,128,256")
    ap.add_argument("--tiles", default="16,64,128")
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--buffers", type=int, default=0, help="the kernels' BUFFERS, where not the module's")
    ap.add_argument("--block-mb", type=float, default=0, help="the kernels' BLOCK_BYTES in MiB, where not the module's")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax import lax

    from benchmarks.lib import peaks
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import grouped_matmul as gm

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("grouped_matmul_bench: no TPU; a CPU time is not a device number", file=sys.stderr)
        return 3
    bw = peaks.for_kind(dev.device_kind)["hbm_bytes_per_s"]
    gm.BUFFERS = args.buffers or gm.BUFFERS
    gm.BLOCK_BYTES = int(args.block_mb * 2**20) or gm.BLOCK_BYTES
    print("grouped_matmul_bench: " + json.dumps({"buffers": gm.BUFFERS, "block_bytes": gm.BLOCK_BYTES}), flush=True)
    L, bf16 = args.layers, jnp.bfloat16

    def timed(run, *operands):
        jax.block_until_ready(run(*operands))
        t0 = time.perf_counter()
        for _ in range(args.reps):
            out = run(*operands)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / (args.reps * L) * 1e6

    for shape in args.shapes.split(","):
        cfg = tfm.tiny(n_layers=L, dtype=bf16, **SHAPES[shape])
        d, f, E, held = cfg.d_model, cfg.d_ff, cfg.n_experts, cfg.experts_held
        ks = jax.random.split(jax.random.PRNGKey(len(shape)), 6)
        riding = {
            "router": jax.random.normal(ks[0], (L, d, E), jnp.float32) * d**-0.5,
            "router_bias": jax.random.normal(ks[1], (L, E), jnp.float32) * 0.01,
        }
        stack = {
            "w_gate": jax.random.normal(ks[2], (L, held, d, f), bf16) * d**-0.5,
            "w_up": jax.random.normal(ks[3], (L, held, d, f), bf16) * d**-0.5,
            "w_down": jax.random.normal(ks[4], (L, held, f, d), bf16) * f**-0.5,
        }

        def whole_at(cut):
            """A function of its own each form: a trace is kept by the function traced, and the cut is read at trace time."""

            def whole(h, riding, stack):
                def layer(_, xs):
                    i, h, mp = xs
                    return None, tfm._routed_ffn(h, mp, cfg, counts=True, experts=(stack, i))

                tfm.GROUPED_FROM_ROWS = cut
                return lax.scan(layer, None, (jnp.arange(L), h, riding))[1]

            return jax.jit(whole)

        for rows in [int(r) for r in args.rows.split(",")]:
            h = jax.random.normal(ks[5], (L, rows, 1, d), bf16)
            results = {}
            for form, cut in (("every_expert", 1 << 30), ("grouped", 0)):
                run = whole_at(cut)
                results[form] = run(h, riding, stack)
                us = timed(run, h, riding, stack)
                print("grouped_matmul_bench: " + json.dumps({"shape": shape, "rows": rows, "form": form, "us_a_layer": round(us, 1)}), flush=True)
            (a, counts), (b, _) = results["every_expert"], results["grouped"]
            diff = float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32))) / jnp.max(jnp.abs(a.astype(jnp.float32))))
            sizes = counts[0][cfg.first_expert : cfg.first_expert + held]
            touched = int(jnp.sum(sizes > 0))
            print("grouped_matmul_bench: " + json.dumps({
                "shape": shape, "rows": rows, "max_rel_diff_between_forms": diff, "experts_touched": touched, "held": held,
                "pairs_on_held": int(jnp.sum(sizes)), "largest_group": int(jnp.max(sizes)),
            }), flush=True)
            m = rows * cfg.n_experts_per_tok
            xs = jax.random.normal(ks[5], (m, d), bf16)
            act = jax.random.normal(ks[4], (m, f), bf16)
            for tile in [int(t) for t in args.tiles.split(",")]:
                def swiglu(xs, stack, sizes):
                    plan = gm.visits(sizes, m, tile)
                    return lax.map(lambda i: gm.grouped_swiglu(xs, stack["w_gate"], stack["w_up"], i, plan, tile_rows=tile), jnp.arange(L))

                def down(act, stack, sizes):
                    plan = gm.visits(sizes, m, tile)
                    return lax.map(lambda i: gm.grouped_matmul(act, stack["w_down"], i, plan, tile_rows=tile), jnp.arange(L))

                for name, run, rows_in, matrices in (("grouped_swiglu", swiglu, xs, 2), ("grouped_matmul", down, act, 1)):
                    us = timed(jax.jit(run), rows_in, stack, sizes)
                    least = matrices * touched * d * f * 2
                    print("grouped_matmul_bench: " + json.dumps({
                        "shape": shape, "rows": rows, "kernel": name, "tile_rows": tile, "us_a_call": round(us, 1),
                        "touched_bytes": least, "least_us": round(least / bw * 1e6, 1), "hbm_peak_share_pct": round(100 * least / bw / (us * 1e-6), 1),
                    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
