"""The power-retention kernels alone on the chip: the decode step against its
own bytes, and (`--prefill 1`) a prefill chunk against its own operations.

    python3 tools/power_retention_bench.py [--heads 40 --kv-heads 8] [--live 8,16,32] [--rows 16,32,64] [--xla 1]
    python3 tools/power_retention_bench.py --prefill 1 [--chunk 256] [--phi-rows 13,5] [--xla 1]
    python3 tools/power_retention_bench.py --prompt 2048 [--workload brumby14b-serve-longgen-batch] [--seed 5] [--float32-layers 2]

For `--slots` rows of which `--live` are live (each its own state in a pool of
`--layers` layers), times `ops/power_retention.power_retention_decode` over
all layers inside one jit (so that dispatch is not what is timed; the pool
donated, updated in place) and prints microseconds a layer's call, the state
bytes a call must move (read once, written once, the least any layout holds:
d (d + 1) / 2 pairs x (d + 1) float32 a K/V head) and the share of the chip's
HBM peak that is (benchmarks/lib/peaks.json, keyed by device kind), per value
of the kernel's `ROWS` in `--rows`; with `--xla 1` also the plain
`transformer.retention_step` over a gathered copy. Also the largest
difference of the kernel's outputs from that expression on the same inputs.
With `--prefill 1`: one chunk of `--chunk` rows of one sequence a layer, from a
carried state, `ops/power_retention.power_retention_prefill` per value of
its `PHI_ROWS` in `--phi-rows` and (`--xla 1`) the plain
`transformer.retention_chunk` stored with `.at[].set`: microseconds a layer's
call, the share of the chip's bf16 peak that the products over phi's axis are
at six passes (2 c n_heads D hd + 2 c n_kv_heads D (hd + 1) operations), and
the largest differences of y and of the state from the plain expression.
With `--prompt n`: ONE prefill of an n-token prompt through `forward_prefill`
at the widths and depth of `--workload`'s configuration (seeded weights as the
cell's workers make them), once with the kernel and once with
`can_tile_prefill` answering no (the plain expression): the last position's
logits and the slot's state of the two (a layer: the first layer's inputs are
the same bfloat16 numbers on both sides, a later layer's are not), read as
the cell's `correct` reads a served token (the margin of the kernel's greedy token under the plain
expression's logits, beside the traffic file's q100 limit), and each call's
milliseconds. In bfloat16 the two programs' q, k and v already differ in their
last bit here and there (XLA keeps or drops a rounding between two fusions as
it fuses them), which the layers amplify; `--float32-layers n` runs the first
n layers of the configuration in float32, where the two sides' inputs are the
same numbers and what is left is the kernel's own difference.
Refuses to run off a TPU: a CPU time is not a device number. A builder's
tool; no test and no metric reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=40)
    ap.add_argument("--kv-heads", type=int, default=8)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--slots", type=int, default=32)
    ap.add_argument("--live", default="8,32")
    ap.add_argument("--rows", default="32")
    ap.add_argument("--xla", type=int, default=0)
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--prefill", type=int, default=0)
    ap.add_argument("--chunk", type=int, default=256)
    ap.add_argument("--phi-rows", default="13")
    ap.add_argument("--prompt", type=int, default=0)
    ap.add_argument("--workload", default="brumby14b-serve-longgen-batch")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--float32-layers", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import peaks
    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import power_retention as pr

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("power_retention_bench: no TPU; a CPU time is not a device number", file=sys.stderr)
        return 3
    if args.prompt:
        return prompt(args, jax, jnp, tfm, pr)
    bw = peaks.for_kind(dev.device_kind)["hbm_bytes_per_s"]
    H, KV, hd, L, B = args.heads, args.kv_heads, args.head_dim, args.layers, args.slots
    D = tfm.retention_state_dim(hd)
    if args.prefill:
        return prefill(args, jax, jnp, tfm, pr, peaks.for_kind(dev.device_kind)["bf16_flops_per_s"])
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(ks[i], (B, h, hd), jnp.bfloat16) for i, h in enumerate((H, KV, KV)))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (B, KV)))
    slots = jnp.arange(1, B + 1, dtype=jnp.int32)

    def pool():
        return (jax.random.normal(ks[4], (L, B + 1, KV, hd, D), jnp.float32), jnp.abs(jax.random.normal(ks[5], (L, B + 1, KV, D), jnp.float32)))

    def all_layers(step):
        def run(s, z, live):
            ys = []
            for layer in range(L):
                y, s, z = step(s, z, layer, live)
                ys.append(y)
            return jnp.stack(ys), s, z

        return jax.jit(run, donate_argnums=(0, 1))

    def kernel(s, z, layer, live):
        return pr.power_retention_decode(q, k, v, log_g, s, z, layer, slots, live, eps=tfm.RETENTION_EPS)

    def plain(s, z, layer, live):
        at = jnp.where(live, slots, 0)
        y, s_new, z_new = tfm.retention_step(q, k, v, log_g, s[layer, at], z[layer, at])
        return y, s.at[layer, at].set(s_new), z.at[layer, at].set(z_new)

    def timed(run, live):
        s, z = pool()
        y, s, z = run(s, z, live)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            y, s, z = run(s, z, live)
        jax.block_until_ready((y, s))
        return (time.perf_counter() - t0) / (args.reps * L) * 1e6

    for n_live in [int(x) for x in args.live.split(",")]:
        live = jnp.arange(B) < n_live
        least = n_live * 2 * KV * (hd * (hd + 1) // 2) * (hd + 1) * 4
        s, z = pool()
        want = all_layers(plain)(s, z, live)[0][:, :n_live]
        for rows in [int(x) for x in args.rows.split(",")]:
            pr.ROWS = rows
            s, z = pool()
            got = all_layers(kernel)(s, z, live)[0][:, :n_live]
            err = float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))
            us = timed(all_layers(kernel), live)
            print("power_retention_bench: " + json.dumps({
                "kernel_rows": rows, "slots": B, "live": n_live, "us_a_call": round(us, 1), "state_bytes": least,
                "hbm_peak_share_pct": round(100 * least / bw / (us * 1e-6), 1), "max_rel_diff_from_plain": err,
            }), flush=True)
        if args.xla:
            us = timed(all_layers(plain), live)
            print("power_retention_bench: " + json.dumps({
                "plain_xla": True, "slots": B, "live": n_live, "us_a_call": round(us, 1),
                "hbm_peak_share_pct": round(100 * least / bw / (us * 1e-6), 1),
            }), flush=True)
    return 0


def _rel(jnp, a, b) -> float:
    return float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b)))


def prefill(args, jax, jnp, tfm, pr, peak) -> int:
    H, KV, hd, L, c = args.heads, args.kv_heads, args.head_dim, args.layers, args.chunk
    D, slot = tfm.retention_state_dim(hd), 1
    ks = jax.random.split(jax.random.PRNGKey(0), 6)
    q, k, v = (jax.random.normal(ks[i], (c, h, hd), jnp.bfloat16) for i, h in enumerate((H, KV, KV)))
    log_g = jax.nn.log_sigmoid(jax.random.normal(ks[3], (c, KV)) + 3.0)
    valid = jnp.arange(c) < c - 3

    def pool():
        return (jax.random.normal(ks[4], (L, 3, KV, hd, D), jnp.float32), jnp.abs(jax.random.normal(ks[5], (L, 3, KV, D), jnp.float32)))

    def all_layers(chunk):
        def run(s, z, carried):
            ys = []
            for layer in range(L):
                y, s, z = chunk(s, z, layer, carried)
                ys.append(y)
            return jnp.stack(ys), s, z

        return jax.jit(run, donate_argnums=(0, 1))

    def kernel(s, z, layer, carried):
        return pr.power_retention_prefill(q, k, v, log_g, s, z, layer, slot, carried, valid, eps=tfm.RETENTION_EPS)

    def plain(s, z, layer, carried):
        y, s_out, z_out = tfm.retention_chunk(q, k, v, log_g, jnp.where(carried, s[layer, slot], 0.0), jnp.where(carried, z[layer, slot], 0.0), valid)
        return y, s.at[layer, slot].set(s_out), z.at[layer, slot].set(z_out)

    def timed(run):
        y, s, z = run(*pool(), True)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(args.reps):
            y, s, z = run(s, z, True)
        jax.block_until_ready((y, s))
        return (time.perf_counter() - t0) / (args.reps * L) * 1e6

    operations = 6 * (2 * c * H * D * hd + 2 * c * KV * D * (hd + 1))
    want = {carried: all_layers(plain)(*pool(), carried) for carried in (True, False)}
    for phi_rows in (int(x) for x in args.phi_rows.split(",")):
        pr.PHI_ROWS = phi_rows
        diffs = {}
        for carried, (want_y, want_s, want_z) in want.items():
            y, s, z = all_layers(kernel)(*pool(), carried)
            diffs["carried" if carried else "from_nothing"] = [_rel(jnp, y[:, : c - 3], want_y[:, : c - 3]), _rel(jnp, s[:, slot], want_s[:, slot]), _rel(jnp, z[:, slot], want_z[:, slot])]
        us = timed(all_layers(kernel))
        print("power_retention_bench: " + json.dumps({
            "prefill_phi_rows": phi_rows, "chunk": c, "us_a_call": round(us, 1), "six_pass_operations": operations,
            "bf16_peak_share_pct": round(100 * operations / peak / (us * 1e-6), 1), "max_rel_diff_y_s_z": diffs,
        }), flush=True)
    if args.xla:
        print("power_retention_bench: " + json.dumps({"plain_xla": True, "chunk": c, "us_a_call": round(timed(all_layers(plain)), 1)}), flush=True)
    return 0


def prompt(args, jax, jnp, tfm, pr) -> int:
    from benchmarks.lib import correct, spec

    cell = spec.find_cell(args.workload)
    cfg = cell.arch.model_config(cell.config)
    if args.float32_layers:
        cfg = cfg.replace(dtype=jnp.float32, n_layers=args.float32_layers)
    page_tokens = cell.config["assumed"]["page_tokens"]["value"]
    limit = cell.traffic["correctness"]["served_margin_tolerance"]["q100"]
    n, slot = args.prompt, 2
    key = jax.random.PRNGKey(args.seed)
    params = jax.jit(lambda k: correct.init_weights(tfm, cfg, k))(key)
    tokens = jnp.zeros((1, page_tokens), jnp.int32).at[0, :n].set(jax.random.randint(jax.random.fold_in(key, 1), (n,), 1, cfg.vocab_size, jnp.int32))

    def prefill(path):
        tile = pr.can_tile_prefill
        pr.can_tile_prefill = tile if path == "kernel" else (lambda *a: False)
        try:
            assert tfm.prefill_paths(cfg, page_tokens)["prefill_attention"] == {"kernel": "retention_kernel", "plain": "xla_chunk"}[path]
            run = jax.jit(lambda p, t, kv: tfm.forward_prefill(p, t, cfg, kv, jnp.array([slot]), n, 0), donate_argnums=(2,))
            ms = []
            for _ in range(2):  # the second call is timed: the first compiles
                kv = jax.tree_util.tree_map(lambda a: a + jnp.nan, tfm.init_kv_pages(cfg, 4, page_tokens))  # a slot's last owner left NaN
                jax.block_until_ready(kv)
                t0 = time.perf_counter()
                logits, kv = run(params, tokens, kv)
                jax.block_until_ready(logits)
                ms.append((time.perf_counter() - t0) * 1e3)
            return logits[0].astype(jnp.float32), {name: kv[name][:, slot] for name in ("s", "z")}, ms[1]
        finally:
            pr.can_tile_prefill = tile

    (got, got_state, got_ms), (want, want_state, want_ms) = prefill("kernel"), prefill("plain")
    margin = float(jnp.max(want) - want[jnp.argmax(got)])
    print("power_retention_bench: " + json.dumps({
        "prompt_tokens": n, "workload": args.workload, "layers": cfg.n_layers, "dtype": str(jnp.dtype(cfg.dtype)), "seed": args.seed,
        "kernel_ms": round(got_ms, 2), "plain_ms": round(want_ms, 2),
        "served_margin": margin, "served_margin_limit_q100": limit, "same_token": bool(jnp.argmax(got) == jnp.argmax(want)),
        "logits_max_abs_diff": float(jnp.max(jnp.abs(got - want))), "logits_max_abs": float(jnp.max(jnp.abs(want))),
        # a layer: the first layer's inputs are the same on both sides, a later one's differ by what bfloat16 made of the one before
        "state_max_rel_diff_a_layer": {name: [_rel(jnp, got_state[name][i], want_state[name][i]) for i in range(cfg.n_layers)] for name in ("s", "z")},
        "state_finite": bool(all(jnp.all(jnp.isfinite(a)) for a in got_state.values())),
        "within_the_cells_limit": margin <= limit,
    }), flush=True)
    return 0 if margin <= limit else 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
