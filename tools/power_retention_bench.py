"""The power-retention decode kernel alone on the chip, against its own bytes.

    python3 tools/power_retention_bench.py [--heads 40 --kv-heads 8] [--live 8,16,32] [--rows 16,32,64] [--xla 1]

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
    bw = peaks.for_kind(dev.device_kind)["hbm_bytes_per_s"]
    H, KV, hd, L, B = args.heads, args.kv_heads, args.head_dim, args.layers, args.slots
    D = tfm.retention_state_dim(hd)
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


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
