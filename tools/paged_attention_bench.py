"""The paged-attention kernels alone on the chip: decode against its own
bytes, prefill (`--prefill`) against its own operations.

    python3 tools/paged_attention_bench.py [--heads 32 --kv-heads 32] [--pages-per-block 4,8,16]
    python3 tools/paged_attention_bench.py --prefill [--layouts 32:32,32:8,16:16] [--chunks 256,512,1024] [--block-q 256,512]
    python3 tools/paged_attention_bench.py --latent [--batches 8,32] [--lengths 8192,16384,24576] [--pages-per-block 2,4,8] [--positions 2,4,8]

For batch 4 / 16 and live lengths 256 / 1 024 / 4 096 (`--batches`, `--lengths`;
every slot at that length, pages scattered over the pool; `--window` gives the
kernel an attention window and counts the bytes it leaves), times `ops/paged_attention.py`
(one layer, `--layers` calls inside one jit so that dispatch is not what is
timed) and prints microseconds a call, the K/V bytes a call must read and
the share of the chip's HBM peak that is (benchmarks/lib/peaks.json, keyed by
device kind; an unknown kind is an error). Also the largest difference from
transformer.paged_attention_gather on the same inputs. `--prefill` times
`paged_prefill_attention` for one chunk of a prompt (its rows the LAST of
the live length, as a prefix hit's are) over live lengths 512 / 1 024 /
2 560 / 4 096, at each head layout (DeepSeek MHA, Mistral GQA, OLMoE), and
prints microseconds a call, the causal FLOPs, their share of the chip's
bf16 peak, and the largest difference from
transformer.paged_prefill_attention_gather. Refuses to run off a TPU: a
CPU time is not a device number. `--latent` times the latent kernels of
`ops/latent_attention.py` (128 heads over rows of 640 lanes, the value the
first 512; pages of 128): the decode kernel over rows x context, against
`max(FLOPs / peak FLOP/s, bytes / peak bytes/s)` of the absorbed step (242
FLOP a byte: the ridge), and one 256-row chunk's kernel behind that context,
against its absorbed FLOPs. A builder's tool; no test and no metric
reads it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def err_and_us(run, args, ref, reps, calls_a_jit):
    """The largest difference of run(*args) from ref, and microseconds a
    kernel call over `reps` runs of a jit that makes `calls_a_jit` calls."""
    import jax
    import jax.numpy as jnp

    out = run(*args)
    err = float(jnp.max(jnp.abs(out[: ref.shape[0]].astype(jnp.float32) - ref)))
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = run(*args)
    jax.block_until_ready(out)
    return err, (time.perf_counter() - t0) / (reps * calls_a_jit) * 1e6


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--heads", type=int, default=32)
    ap.add_argument("--kv-heads", type=int, default=32)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--v-head-dim", type=int, default=0, help="a V head's width where it is not --head-dim (MiMo-V2: 128 beside K heads stored 256 wide)")
    ap.add_argument("--needed-head-dim", type=int, default=0, help="a K head's own width where --head-dim is what the pages pad it to (192 in 256): scales the scores, counts the bytes and FLOPs")
    ap.add_argument("--page-tokens", type=int, default=16)
    ap.add_argument("--max-pages", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=1024)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--pages-per-block", default="")
    ap.add_argument("--batches", default="4,16", help="decode: slots a call")
    ap.add_argument("--lengths", default="256,1024,4096", help="decode: live length of every slot")
    ap.add_argument("--window", type=int, default=0, help="decode: attention window (0: none); bytes are counted clipped to it")
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--prefill", action="store_true")
    ap.add_argument("--layouts", default="32:32,32:8,16:16")
    ap.add_argument("--chunks", default="256,512,1024")
    ap.add_argument("--block-q", default="")
    ap.add_argument("--heads-unrolled", default="")
    ap.add_argument("--latent", action="store_true")
    ap.add_argument("--positions", default="", help="latent prefill: positions of a chunk a grid step")
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
        peaks = json.load(f)["peaks"][dev.device_kind]
    if a.latent:
        return latent(a, dev, peaks)
    if a.prefill:
        return prefill(a, dev, peaks["bf16_flops_per_s"])
    bw = peaks["hbm_bytes_per_s"]
    H, G, hd, T, P, N, L = a.heads, a.kv_heads, a.head_dim, a.page_tokens, a.max_pages, a.pool_pages, a.layers
    F = G * hd
    hv, hk = a.v_head_dim or hd, a.needed_head_dim or hd
    how = {"scale": hk**-0.5} if hk != hd else {}
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    kp = jax.random.normal(key, (1, N, T, F), dtype)
    vp = jax.random.normal(jax.random.fold_in(key, 1), (1, N, T, G * hv), dtype)
    ppbs = [int(x) for x in a.pages_per_block.split(",") if x] or [None]
    print(f"device {dev.device_kind}, peak {bw / 1e9:.0f} GB/s; heads {H}:{G} x {hd} (K needed {hk}, V {hv}), pages of {T}, pool {N} pages, {L} calls a jit")
    rng = np.random.default_rng(0)
    window = jnp.int32(a.window) if a.window else None
    for B in (int(x) for x in a.batches.split(",")):
        for length in (int(x) for x in a.lengths.split(",")):
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
            # the expression gathers every slot's whole table in float32: held to the first 4 slots
            ref = tfm.paged_attention_gather(q[:4], kp[0], vp[0], bt[:4], lens[:4], G, window, **how).astype(jnp.float32)
            for ppb in ppbs:
                @jax.jit
                def run(q, kp, vp, bt, lens):
                    def step(q, _):
                        o = pa.paged_attention(q, kp, vp, 0, bt, lens, n_kv_heads=G, window=window, pages_per_block=ppb, **how)
                        return q.at[..., :hv].add((o * 1e-3).astype(q.dtype)), o
                    _, os_ = jax.lax.scan(step, q, None, length=L)
                    return os_[0]

                err, us = err_and_us(run, (q, kp, vp, bt, lens), ref, a.reps, L)
                nbytes = B * min(length, a.window or length) * G * (hk + hv) * jnp.dtype(dtype).itemsize  # the NEEDED bytes: no padding
                print(json.dumps({
                    "batch": B, "live_length": length, "window": a.window, "pages_per_block": ppb or pa.pick_pages_per_block(T, F, P, dtype),
                    "us_per_call": round(us, 1), "kv_bytes": nbytes, "hbm_peak_share_pct": round(100 * nbytes / bw / (us * 1e-6), 1),
                    "max_abs_diff_vs_gather": round(err, 5),
                }), flush=True)
    return 0


def prefill(a, dev, peak_flops) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models import transformer as tfm
    from ray_tpu.ops import paged_attention as pa

    hd, T, P, N, L = a.head_dim, a.page_tokens, a.max_pages, a.pool_pages, a.layers
    dtype = jnp.bfloat16
    key = jax.random.PRNGKey(0)
    rng = np.random.default_rng(0)
    bqs = [int(x) for x in a.block_q.split(",") if x] or [None]
    ppbs = [int(x) for x in a.pages_per_block.split(",") if x] or [None]
    unrolls = [int(x) for x in a.heads_unrolled.split(",") if x] or [None]
    for layout in a.layouts.split(","):
        H, G = (int(x) for x in layout.split(":"))
        F = G * hd
        hv, hk = a.v_head_dim or hd, a.needed_head_dim or hd
        how = {"scale": hk**-0.5} if hk != hd else {}
        kp = jax.random.normal(key, (1, N, T, F), dtype)
        vp = jax.random.normal(jax.random.fold_in(key, 1), (1, N, T, G * hv), dtype)
        print(f"device {dev.device_kind}, peak {peak_flops / 1e12:.0f} TFLOP/s; heads {H}:{G} x {hd} (K needed {hk}, V {hv}), pages of {T}, pool {N} pages, {L} calls a jit")
        for C in (int(x) for x in a.chunks.split(",")):
            for length in (512, 1024, 2560, 4096):
                if length < C:
                    continue
                start = length - C
                bt = np.zeros((P,), np.int32)
                bt[: length // T] = rng.permutation(np.arange(1, N))[: length // T]
                bt = jnp.asarray(bt)
                q = jax.random.normal(jax.random.fold_in(key, C * length), (C, H, hd), dtype)
                ref = tfm.paged_prefill_attention_gather(q, kp[0], vp[0], bt, start, G, **how).astype(jnp.float32)
                for bq in bqs:
                    if bq and C % bq:
                        continue
                    for ppb, unroll in ((p, u) for p in ppbs for u in unrolls):
                        @jax.jit
                        def run(q, kp, vp, bt):
                            def step(q, _):
                                o = pa.paged_prefill_attention(
                                    q, kp, vp, 0, bt, start, length, n_kv_heads=G, block_q=bq, pages_per_block=ppb,
                                    heads_unrolled=unroll, **how)
                                return q.at[..., :hv].add((o * 1e-3).astype(q.dtype)), o
                            _, os_ = jax.lax.scan(step, q, None, length=L)
                            return os_[0]

                        err, us = err_and_us(run, (q, kp, vp, bt), ref, a.reps, L)
                        flops = 2 * (hk + hv) * H * sum(range(start + 1, length + 1))  # q.K and P.V, row i over i + 1 keys
                        picked = pa.pick_prefill_blocks(C, T, F, P, dtype)
                        print(json.dumps({
                            "heads": layout, "chunk": C, "live_length": length, "block_q": bq or picked[0],
                            "pages_per_block": ppb or picked[1], "heads_unrolled": unroll or pa.PREFILL_HEADS_UNROLLED, "us_per_call": round(us, 1), "causal_gflop": round(flops / 1e9, 2),
                            "mxu_peak_share_pct": round(100 * flops / peak_flops / (us * 1e-6), 1),
                            "max_abs_diff_vs_gather": round(err, 5),
                        }), flush=True)
    return 0


def latent(a, dev, peaks) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.ops import latent_attention as la

    H, c, rope, T, P, N, L, C = 128, 512, 64, 128, 196, 2048, a.layers, 256
    W, dtype, scale = la.row_width(c, rope), jnp.bfloat16, 0.135
    key, rng = jax.random.PRNGKey(0), np.random.default_rng(0)
    pool = jax.random.normal(key, (1, N, T, W), dtype).at[..., c + rope :].set(0)
    ppbs = [int(x) for x in a.pages_per_block.split(",") if x] or [None]
    positions = [int(x) for x in a.positions.split(",") if x] or [None]
    batches = [int(x) for x in (a.batches if a.batches != "4,16" else "8,32").split(",")]
    lengths = [int(x) for x in (a.lengths if a.lengths != "256,1024,4096" else "8192,16384,24576").split(",")]
    print(f"device {dev.device_kind}, peaks {peaks['bf16_flops_per_s'] / 1e12:.0f} TFLOP/s, {peaks['hbm_bytes_per_s'] / 1e9:.0f} GB/s; "
          f"{H} heads over rows of {W} lanes ({c} the value), pages of {T}, pool {N} pages, {L} calls a jit")
    pair = 2.0 * H * (c + rope + c)  # FLOPs of one (query position, cached position) pair, absorbed
    for length in lengths:
        n = -(-length // T)
        for B in batches:
            # the pool cannot hold B slots of that length: slots share pages (the bytes read are the same)
            bt = np.zeros((B, P), np.int32)
            bt[:, :n] = np.stack([rng.permutation(np.arange(1, N))[:n] for _ in range(B)])
            bt, lens = jnp.asarray(bt), jnp.full((B,), length, jnp.int32)
            q = jax.random.normal(jax.random.fold_in(key, B * length), (B, H, W), dtype)
            ref = la.latent_attention_gather(q[:2], pool[0], bt[:2], lens[:2], scale=scale, v_width=c).astype(jnp.float32)
            for ppb in ppbs:
                @jax.jit
                def run(q, pool, bt, lens):
                    def step(q, _):
                        o = la.paged_latent_attention(q, pool, 0, bt, lens, scale=scale, v_width=c, pages_per_block=ppb)
                        return q.at[..., :c].add((o * 1e-3).astype(q.dtype)), o
                    return jax.lax.scan(step, q, None, length=L)[1][0]

                try:
                    err, us = err_and_us(run, (q, pool, bt, lens), ref, a.reps, L)
                except Exception as e:  # noqa: BLE001 - a block the compiler refuses is a line of the sweep, not its end
                    print(json.dumps({"kernel": la.KERNEL_NAME, "rows": B, "context": length, "pages_per_block": ppb, "refused": f"{type(e).__name__}: {e}"[:200]}), flush=True)
                    continue
                flops, nbytes = B * length * pair, B * length * (c + rope) * 2
                least = max(flops / peaks["bf16_flops_per_s"], nbytes / peaks["hbm_bytes_per_s"])
                print(json.dumps({"kernel": la.KERNEL_NAME, "rows": B, "context": length, "pages_per_block": ppb or max(1, la.BLOCK_TOKENS // T),
                                  "us_per_call": round(us, 1), "gflop": round(flops / 1e9, 2), "latent_bytes": nbytes,
                                  "roofline_pct": round(100 * least / (us * 1e-6), 1), "max_abs_diff_vs_gather": round(err, 5)}), flush=True)
        # one chunk of C rows, the last of the context (a prefix hit's suffix; a miss's last chunk)
        start = (length - C) // T * T
        table = np.zeros((P,), np.int32)
        table[:n] = rng.permutation(np.arange(1, N))[:n]
        table = jnp.asarray(table)
        q = jax.random.normal(jax.random.fold_in(key, length), (C, H, W), dtype)
        ref = la.latent_prefill_attention_gather(q[:8], pool[0], table, start, scale=scale, v_width=c).astype(jnp.float32)
        for R, ppb in ((r, p) for r in positions for p in ppbs):
            @jax.jit
            def run(q, pool, table):
                def step(q, _):
                    o = la.paged_latent_prefill_attention(q, pool, 0, table, start, start + C, scale=scale, v_width=c, positions_per_block=R, pages_per_block=ppb)
                    return q.at[..., :c].add((o * 1e-3).astype(q.dtype)), o
                return jax.lax.scan(step, q, None, length=L)[1][0]

            try:
                err, us = err_and_us(run, (q, pool, table), ref, a.reps, L)
            except Exception as e:  # noqa: BLE001
                print(json.dumps({"kernel": la.PREFILL_KERNEL_NAME, "context": start + C, "positions_per_block": R, "pages_per_block": ppb, "refused": f"{type(e).__name__}: {e}"[:200]}), flush=True)
                continue
            flops = pair * sum(range(start + 1, start + C + 1))
            print(json.dumps({"kernel": la.PREFILL_KERNEL_NAME, "chunk": C, "context": start + C, "positions_per_block": R or la.PREFILL_POSITIONS,
                              "pages_per_block": ppb or max(1, la.BLOCK_TOKENS // T), "us_per_call": round(us, 1), "absorbed_gflop": round(flops / 1e9, 1),
                              "mxu_peak_share_pct": round(100 * flops / peaks["bf16_flops_per_s"] / (us * 1e-6), 1), "max_abs_diff_vs_gather": round(err, 5)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
