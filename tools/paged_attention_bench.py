"""The paged-attention decode kernel alone on the chip, against its own bytes.

    python3 tools/paged_attention_bench.py [--heads 32 --kv-heads 32] [--pages-per-block 4,8,16]

For batch 4 / 16 and live lengths 256 / 1 024 / 4 096 (every slot at that
length, pages scattered over the pool), times `ops/paged_attention.py`
(one layer, `--layers` calls inside one jit so that dispatch is not what is
timed) and prints microseconds a call, the K/V bytes a call must read and
the share of the chip's HBM peak that is (benchmarks/lib/peaks.json, keyed by
device kind; an unknown kind is an error). Also the largest difference from
transformer.paged_attention_gather on the same inputs. Refuses to run off a
TPU: a CPU time is not a device number. A builder's tool; no test and no
metric reads it.
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
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--max-pages", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--pages-per-block", default="")
    ap.add_argument("--reps", type=int, default=20)
    a = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import paged_attention as pa

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"paged_attention_bench: needs a TPU, found {dev.platform}", file=sys.stderr)
        return 2
    with open(os.path.join(ROOT, "benchmarks", "lib", "peaks.json")) as f:
        bw = json.load(f)["peaks"][dev.device_kind]["hbm_bytes_per_s"]
    H, G, hd, T, P, N, L = a.heads, a.kv_heads, a.head_dim, a.page_tokens, a.max_pages, a.pool_pages, a.layers
    F = G * hd
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (1, N, T, F), dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 1), (1, N, T, F), dtype)
    ppbs = [int(x) for x in a.pages_per_block.split(",") if x] or [None]
    print(f"device {dev.device_kind}, peak {bw / 1e9:.0f} GB/s; heads {H}:{G} x {hd}, pages of {T}, pool {N} pages, {L} calls a jit")
    rng = np.random.default_rng(0)
    for B in (4, 16):
        for length in (256, 1024, 4096):
            n = -(-length // T)
            if B * n > N - 1:
                # the pool cannot hold B slots of that length: slots share pages (the bytes read are the same)
                perm = np.concatenate([rng.permutation(np.arange(1, N))[:n] for _ in range(B)])
            else:
                perm = rng.permutation(np.arange(1, N))[: B * n]
            bt = np.zeros((B, P), np.int32)
            bt[:, :n] = perm.reshape(B, n)
            bt, lens = jnp.asarray(bt), jnp.full((B,), length, jnp.int32)
            q = jax.random.normal(jax.random.fold_in(key, B * length), (B, H, hd), dtype)
            ref = tfm.paged_attention_gather(q, kp[0], vp[0], bt, lens, G).astype(jnp.float32)
            for ppb in ppbs:
                @jax.jit
                def run(q, kp, vp, bt, lens):
                    def step(q, _):
                        o = pa.paged_attention(q, kp, vp, 0, bt, lens, n_kv_heads=G, pages_per_block=ppb)
                        return q + (o * 1e-3).astype(q.dtype), o
                    _, os_ = jax.lax.scan(step, q, None, length=L)
                    return os_[0]

                out = run(q, kp, vp, bt, lens)
                err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref)))
                jax.block_until_ready(out)
                t0 = time.perf_counter()
                for _ in range(a.reps):
                    out = run(q, kp, vp, bt, lens)
                jax.block_until_ready(out)
                us = (time.perf_counter() - t0) / (a.reps * L) * 1e6
                nbytes = 2 * B * length * F * jnp.dtype(dtype).itemsize
                print(json.dumps({
                    "batch": B, "live_length": length, "pages_per_block": ppb or pa.pick_pages_per_block(T, F, P, dtype),
                    "us_per_call": round(us, 1), "kv_bytes": nbytes, "hbm_peak_share_pct": round(100 * nbytes / bw / (us * 1e-6), 1),
                    "max_abs_diff_vs_gather": round(err, 5),
                }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
