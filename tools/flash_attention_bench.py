"""The three causal flash kernels alone on the chip, at the training cells' shapes.

    python3 tools/flash_attention_bench.py [--shapes mistral,olmoe] [--tiles 1024:256,1024:0,512:128]
        [--parent-dir .chipcheck/parent] [--parts 1] [--module name=path/to/flash_attention.py[@block:sub]] [--reps 10]

For each shape (`mistral`: q [3 x 32, 4096, 128] over 8 kv heads a sequence;
`olmoe`: [4 x 16, 4096, 128], 16 kv heads) and each `block:sub` of `--tiles`
(0: the diagonal blocks computed whole), times `_flash_fwd_impl` and the two
calls of `_flash_bwd_impl` of `ray_tpu/ops/flash_attention.py`, each under a
jit of its own, in the kernels' own [b*h, s, d] layout (no transposes), on
seeded bfloat16 inputs: milliseconds a call and the share of the chip's
bfloat16 peak (benchmarks/lib/peaks.json, keyed by device kind) that
`benchmarks/lib/flops.flash_kernel_flops` is at that time (what
`flash_attn_roofline*` reads from a trace, there over the calls of a step),
beside `causal_work_ratio`, the score elements computed over the s^2 / 2
needed. With `--parent-dir` (a `git archive` of the parent commit unpacked
there), the parent's file is loaded beside this tree's and timed in its own
signature at its own 1024 x 1024 blocks, and every variant's outputs are
compared with the parent's (the largest difference over the largest value;
a kernel that changes no term differs by the order of a float32 sum).
`--parts 1` adds two forms between the parent's and this tree's, from this
tree's kernels at the shipped block: `mask` (diagonal blocks whole; the mask
built only in them; skipped steps' blocks still fetched: the index maps
patched here to the parent's) and `mask+fetch` (the same with this tree's
index maps), so that mask -> mask+fetch -> shipped reads what the skipped
fetches and the sub-tiled diagonal gave; parent -> mask is the mask's part
together with the row statistics' layout (PERF.md section 6, PR 55, has
the two apart, from files of the kernels' earlier forms). `--module name=path` times another file with this
tree's signatures (an experiment). Refuses to run off a TPU: a CPU time is not
a device number. A builder's tool; no test, no cell and no metric reads it.
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

SHAPES = {"mistral": (3, 32, 8), "olmoe": (4, 16, 16)}  # batch, heads, kv heads of the training cells' attention
SEQ, HEAD_DIM = 4096, 128
KINDS = {"fwd": 3, "dq": 6, "dkv": 6}  # how many of (q, k, v, lse, dO, delta) a kind's call takes


def _load(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def main(argv) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--shapes", default="mistral,olmoe")
    ap.add_argument("--tiles", default="")
    ap.add_argument("--parent-dir", default="")
    ap.add_argument("--parts", type=int, default=0)
    ap.add_argument("--module", action="append", default=[])
    ap.add_argument("--reps", type=int, default=10)
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp

    import ray_tpu.ops.flash_attention  # noqa: F401 - the package exports the function under the module's name
    from benchmarks.lib import flops, peaks

    fa = sys.modules["ray_tpu.ops.flash_attention"]
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("flash_attention_bench: no TPU; a CPU time is not a device number", file=sys.stderr)
        return 3
    peak = peaks.for_kind(dev.device_kind)["bf16_flops_per_s"]
    scale = HEAD_DIM**-0.5
    shipped = fa._pick_blocks(SEQ, HEAD_DIM, jnp.bfloat16, None, None, False)
    tiles = [(int(b), int(s) or None) for b, s in (t.split(":") for t in args.tiles.split(",") if t)] or [shipped[::2]]

    def calls(module, heads, blocks):
        """{kind: jitted call} of a file with this tree's signatures."""
        return {
            "fwd": jax.jit(lambda q, k, v: module._flash_fwd_impl(q, k, v, True, scale, blocks, False, heads)),
            "dq": jax.jit(lambda q, k, v, lse, do, delta: module._flash_bwd_impl(True, scale, blocks, False, heads, q, k, v, lse, do, delta)[0]),
            "dkv": jax.jit(lambda q, k, v, lse, do, delta: module._flash_bwd_impl(True, scale, blocks, False, heads, q, k, v, lse, do, delta)[1:]),
        }

    def parent_calls(module, heads):
        bq, bk = module.DEFAULT_BLOCK, module.DEFAULT_BLOCK_K
        return {
            "fwd": jax.jit(lambda q, k, v: module._flash_fwd_impl(q, k, v, True, scale, bq, bk, False, heads)),
            "dq": jax.jit(lambda q, k, v, lse, do, delta: module._flash_bwd_impl(True, scale, bq, bk, False, heads, q, k, v, None, lse, do, delta)[0]),
            "dkv": jax.jit(lambda q, k, v, lse, do, delta: module._flash_bwd_impl(True, scale, bq, bk, False, heads, q, k, v, None, lse, do, delta)[1:]),
        }

    def unclamped(heads, blocks):
        """This tree's kernels under the parent's index maps: a skipped step names a block of its own again."""
        kv_index, q_index = fa._kv_index, fa._q_index
        fa._kv_index = lambda h, h_kv, causal, bq, bk: kv_index(h, h_kv, False, bq, bk)
        fa._q_index = lambda h, h_kv, nq, causal, bq, bk: q_index(h, h_kv, nq, False, bq, bk)
        try:
            made = calls(fa, heads, blocks)
            return {kind: f.lower(*abstract[: KINDS[kind]]).compile() for kind, f in made.items()}
        finally:
            fa._kv_index, fa._q_index = kv_index, q_index

    for shape in args.shapes.split(","):
        b, h, kv = SHAPES[shape]
        heads = (h, kv)
        ks = jax.random.split(jax.random.PRNGKey(0), 4)
        q, do = (jax.random.normal(key, (b * h, SEQ, HEAD_DIM), jnp.bfloat16) for key in ks[:2])
        k, v = (jax.random.normal(key, (b * kv, SEQ, HEAD_DIM), jnp.bfloat16) for key in ks[2:])
        need = flops.flash_kernel_flops(h, HEAD_DIM, b, SEQ)

        variants = []  # (name, {kind: call}, work ratio)
        if args.parent_dir:
            parent = _load("parent_flash_attention", os.path.join(args.parent_dir, "ray_tpu/ops/flash_attention.py"))
            variants.append(("parent", parent_calls(parent, heads), fa.causal_work_ratio(SEQ, parent.DEFAULT_BLOCK, parent.DEFAULT_BLOCK_K, None)))
        o, lse = calls(fa, heads, shipped)["fwd"](q, k, v)
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1, keepdims=True)
        abstract = tuple(jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (q, k, v, lse, do, lse))  # delta in lse's layout
        if args.parts:
            whole = (shipped[0], shipped[1], None)
            variants.append(("mask", unclamped(heads, whole), fa.causal_work_ratio(SEQ, *whole)))
            variants.append(("mask+fetch", calls(fa, heads, whole), fa.causal_work_ratio(SEQ, *whole)))
        for block, sub in tiles:
            variants.append((f"{block}:{sub or 0}", calls(fa, heads, (block, block, sub)), fa.causal_work_ratio(SEQ, block, block, sub)))
        for spec in args.module:
            name, _, path = spec.partition("=")
            path, _, tile = path.partition("@")
            blocks = (int(tile.split(":")[0]),) * 2 + (int(tile.split(":")[1]) or None,) if tile else shipped
            module = _load("experiment_" + name.replace("@", "_").replace(":", "_"), path)
            variants.append((name, calls(module, heads, blocks), module.causal_work_ratio(SEQ, *blocks)))

        want = None
        for name, made, work in variants:
            line = {"shape": shape, "variant": name, "work_ratio": work}
            try:
                got = {}
                lse = made["fwd"](q, k, v)[1]  # each file's own logsumexp, in the layout it keeps
                operands = (q, k, v, lse, do, jnp.broadcast_to(delta, lse.shape))
                for kind, n in KINDS.items():
                    out = made[kind](*operands[:n])
                    jax.block_until_ready(out)
                    t0 = time.perf_counter()
                    for _ in range(args.reps):
                        out = made[kind](*operands[:n])
                    jax.block_until_ready(out)
                    ms = (time.perf_counter() - t0) / args.reps * 1e3
                    line[kind + "_ms"] = round(ms, 4)
                    line[kind + "_peak_share_pct"] = round(100 * need[kind] / peak / (ms * 1e-3), 2)
                    got[kind] = jax.tree_util.tree_leaves(out)
                total = sum(line[kind + "_ms"] for kind in KINDS)
                line["all_ms"] = round(total, 4)
                line["all_peak_share_pct"] = round(100 * sum(need.values()) / peak / (total * 1e-3), 2)
                if want is None:
                    want = got
                else:
                    line["max_diff_over_max_from_" + variants[0][0]] = {
                        kind: max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - w.astype(jnp.float32))) / jnp.max(jnp.abs(w.astype(jnp.float32)))) for a, w in zip(got[kind], want[kind]))
                        for kind in KINDS
                    }
            except Exception as e:  # noqa: BLE001 - a tile the chip's compiler refuses is a line of the sweep
                line["failed"] = f"{type(e).__name__}: {str(e)[:300]}"
            print("flash_attention_bench: " + json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
