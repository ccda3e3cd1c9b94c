"""How close the program comes to the OLMoE reference at the published widths
on the chip, and which comparison can tell a wrong model from the right one:
the builder's measurement behind PERF.md sections 4 and 7 (PR 27). Never part
of a check: the driver runs benchmarks/run.py.

For each seed, weights and batch are made exactly as lib/worker_train.py makes
them, then every norm's scale is drawn from [0.5, 1.5] (init_params starts
them at 1, and an RMSNorm over a fan-in-scaled projection with unit scale is
nearly the identity: only drawn scales show whether q/k-norm is computed).
Against the float32 "highest" reference (benchmarks/archs/olmoe.py) on every
sequence of the batch:

- `loss`: what lib/worker_train.py compares, the batch's mean next-token loss
  less the reference's. A random head behind the final RMSNorm pins the
  logits' variance and the targets are random, so this mean barely depends
  on the layers below: the readings of right and wrong models overlap.
- `logits`: per position, |z - z_ref| / |z_ref| over the vocabulary (Euclidean
  norms of the float32 next-token logits), and its quantiles over the
  positions of the batch; `agreeing` = over the positions whose token took
  the reference's SET of experts in every layer (`transformer.routing_stats`
  against `archs/olmoe.routed_experts`), where a difference is arithmetic
  and not a near-tie of the router resolved the other way.

Both are read for the program (bfloat16, as trained), for the three nearest
wrong programs (top-(k-1), renormalised top-k, no q/k-norm) and for
`reference_in_fp8`: the reference itself with every weight matrix rounded to
float8_e4m3's 3 mantissa bits (`lax.reduce_precision` at bfloat16's exponent
range: what an fp8 path with well-chosen scales keeps, the mildest form of
the nearest precision below the configuration's bfloat16), the second
reading a limit is set from. `paged` runs `forward_prefill` on a
1 021-token prompt and six `forward_decode` steps through the paged cache
across a page boundary (the serve path's programs, 2 decode rows against 64
expert groups) and compares their logits the same way.

    chiprun -- python3 benchmarks/tools/olmoe_checks.py --seeds 11,2147483659
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

CONFIG = "olmoe-1b-7b-0125-L2"
TRAFFIC = "train-fixed-batch-moe"
QUANTILES = (0.5, 0.9, 0.99, 1.0)
PAGE_TOKENS = 16


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--tiny", type=int, default=0, help="TINY widths on whatever backend there is (a rehearsal of this tool)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import rehearsal, spec
    from benchmarks.lib.worker_train import seeded_key
    from ray_tpu.models import transformer as tfm

    cell = spec.Cell(
        name="olmoe-train-seq4k-1chip", chips=1, config_name=CONFIG, traffic_name=TRAFFIC,
        config=spec.load_config(os.path.join(spec.BENCH_DIR, "configs", CONFIG + ".json")),
        traffic=spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", TRAFFIC + ".json")),
        end_to_end=[], per_layer=[],
    )
    if args.tiny:
        rehearsal.shrink(cell)
    config, arch = cell.config, cell.arch
    seq, batch = int(cell.traffic["seq_len"]), int(cell.traffic["batch_per_chip"])
    cfg = arch.model_config(config, max_seq_len=seq)
    k = cfg.n_experts_per_tok
    programs = {
        "program": cfg,
        f"top-{k - 1}": cfg.replace(n_experts_per_tok=k - 1),
        "renormalised": cfg.replace(norm_topk_prob=not cfg.norm_topk_prob),
        "no-qk-norm": cfg.replace(qk_norm=False),
    }
    device = jax.devices()[0]

    def draw_norm_scales(params, key):
        leaves, treedef = jax.tree_util.tree_flatten_with_path(params)
        drawn = [
            jax.random.uniform(jax.random.fold_in(key, i), leaf.shape, jnp.float32, 0.5, 1.5).astype(leaf.dtype)
            if "norm" in jax.tree_util.keystr(path) else leaf
            for i, (path, leaf) in enumerate(leaves)
        ]
        return jax.tree_util.tree_unflatten(treedef, drawn)

    def in_fp8(params):
        # not astype(float8).astype(bf16): under jit XLA drops that pair (xla_allow_excess_precision)
        return jax.tree_util.tree_map_with_path(
            lambda path, a: a if "norm" in jax.tree_util.keystr(path) else jax.lax.reduce_precision(a, exponent_bits=8, mantissa_bits=3),
            params)

    @jax.jit
    def reference(p, s):
        """One sequence: logits [s, V], experts [L, s, k] sorted, mean next-token loss."""
        z = arch.logits_at(p, s, jnp.arange(s.shape[0]), config)
        return z, jnp.sort(arch.routed_experts(p, s, config), axis=-1), nll(z, s)

    def nll(z, s):
        return -jnp.mean(jnp.take_along_axis(jax.nn.log_softmax(z[:-1], axis=-1), s[1:, None], axis=-1))

    def program(c):
        @jax.jit
        def run(p, s):
            z = tfm.forward(p, s[None], c)[0]
            return z, nll(z, s)

        return run

    runs = {name: program(c) for name, c in programs.items()}
    prefill = jax.jit(lambda p, t, kv, table, n: tfm.forward_prefill(p, t, cfg, kv, table, n, jnp.int32(0)))
    decode = jax.jit(lambda p, t, pos, kv, bts: tfm.forward_decode(p, t, pos, cfg, kv, bts))

    @jax.jit
    def relative_error(z, z_ref):
        return jnp.linalg.norm(z - z_ref, axis=-1) / jnp.linalg.norm(z_ref, axis=-1)

    def quantiles(x):
        return {str(q): float(np.quantile(x, q)) for q in QUANTILES} if x.size else None

    def paged(p, s, prompt_len, steps, want):
        """Prefill then `steps` teacher-forced decode steps in slot 1 of 2
        (slot 0 inactive), as benchmarks/tests/test_parity_olmoe.py does at
        TINY widths: relative error of each step's logits, and whether the
        most probable token is the reference's."""
        pages_per_seq = -(-(prompt_len + steps) // PAGE_TOKENS)
        pages = tfm.init_kv_pages(cfg, 1 + pages_per_seq, PAGE_TOKENS)
        table = jnp.arange(1, 1 + pages_per_seq, dtype=jnp.int32)
        n_prompt = -(-prompt_len // PAGE_TOKENS)
        padded = jnp.zeros((1, n_prompt * PAGE_TOKENS), jnp.int32).at[0, :prompt_len].set(s[:prompt_len])
        logits, pages = prefill(p, padded, pages, table[:n_prompt], jnp.int32(prompt_len))
        got = [logits[0]]
        tables = jnp.stack([jnp.zeros_like(table), table])
        for pos in range(prompt_len, prompt_len + steps):
            step, pages = decode(p, jnp.asarray([0, s[pos]], jnp.int32), jnp.asarray([-1, pos], jnp.int32), pages, tables)
            got.append(step[1])
        got = jnp.stack(got).astype(jnp.float32)
        return {
            "prompt_tokens": prompt_len, "decode_steps": steps, "page_tokens": PAGE_TOKENS,
            "relative_error": np.asarray(relative_error(got, want)).tolist(),
            "same_argmax": np.asarray(jnp.argmax(got, -1) == jnp.argmax(want, -1)).tolist(),
        }

    def check(params, tokens, seed):
        fp8 = jax.jit(in_fp8)(params)
        err = {name: [] for name in list(runs) + ["reference_in_fp8"]}
        loss = {name: [] for name in list(runs) + ["reference", "reference_in_fp8"]}
        same = {"program": [], "reference_in_fp8": []}
        for i in range(batch):
            s = tokens[i]
            z_ref, e_ref, l_ref = reference(params, s)
            loss["reference"].append(float(l_ref))
            for name, run in runs.items():
                z, l = run(params, s)
                err[name].append(np.asarray(relative_error(z, z_ref)))
                loss[name].append(float(l))
            got = jnp.sort(tfm.routing_stats(params, s[None], cfg)["experts"], axis=-1)
            same["program"].append(np.asarray(jnp.all(got == e_ref, axis=(0, 2))))
            z8, e8, l8 = reference(fp8, s)
            err["reference_in_fp8"].append(np.asarray(relative_error(z8, z_ref)))
            same["reference_in_fp8"].append(np.asarray(jnp.all(e8 == e_ref, axis=(0, 2))))
            loss["reference_in_fp8"].append(float(l8))
            if i == 0:
                prompt_len, steps = (min(1021, seq - 7), 6) if not args.tiny else (13, 6)
                at = jnp.arange(prompt_len - 1, prompt_len + steps)
                paged_facts = paged(params, s, prompt_len, steps, z_ref[at])
        err = {name: np.concatenate(v) for name, v in err.items()}
        same = {name: np.concatenate(v) for name, v in same.items()}
        ref_loss = float(np.mean(loss["reference"]))
        return {
            "seed": seed, "device": f"{device.platform} {device.device_kind}", "batch": batch, "seq_len": seq,
            "widths": "TINY" if args.tiny else "published", "positions": int(err["program"].size),
            "loss": {"reference": ref_loss, "minus_reference": {n: float(np.mean(v)) - ref_loss for n, v in loss.items() if n != "reference"}},
            "logits": {
                name: dict(
                    {"all": quantiles(e)},
                    **({"agreeing": quantiles(e[same[name]]), "agreeing_share": float(same[name].mean())} if name in same else {}),
                )
                for name, e in err.items()
            },
            "paged": paged_facts,
        }

    out_dir = os.path.join(ROOT, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "olmoe_checks.jsonl"), "a") as f:
        for seed in (int(s) for s in args.seeds.split(",")):
            key = seeded_key(seed)
            tokens = jax.jit(lambda kk: jax.random.randint(kk, (batch, seq), 0, cfg.vocab_size, jnp.int32))(jax.random.fold_in(key, 1))
            params = jax.jit(lambda kk: draw_norm_scales(tfm.init_params(kk, cfg), jax.random.fold_in(kk, 2)))(key)
            line = json.dumps(check(params, tokens, seed))
            print("olmoe_checks: " + line, flush=True)
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
