"""The readings that every limit of a traffic file's `correctness` is set
between, for any cell of BENCHMARK.json, through the functions a run of the
cell calls (`lib/correct.py`, `lib/worker_train.FirstSteps`, the serving
runner itself). Never part of a check: the driver runs benchmarks/run.py.

    chiprun -- python3 benchmarks/tools/control.py --workload <cell> --seeds 11,2147483659,... [--control-seeds 3]

A training cell, all seeds in this one process (it owns the chips). For each
seed, weights and batch as the cell's worker makes them, then

- `program`: the cell's own compiled step driven through its first steps
  (losses, the first gradient's norms from adam's first moment, the
  parameters' change) against `training_reference`: the lower reading;
- for the first `--control-seeds` seeds, the reference put in the program's
  place with something wrong, against the same reference: `control`, its
  weight matrices at fp8's 3 mantissa bits (`lib/correct.in_fp8`), the
  precision below the configuration's bfloat16; `half_batch`, half of the rows
  left out and the mean taken over the rest; on several chips
  `exchange_left_out`, every chip training on chip 0's rows alone, which is
  what chip 0 computes when no gradient crosses chips. A state left unchanged
  reads 1 for the parameters' change by the measure's definition;
- with `--wrong 1`, on the first `--wrong-seeds` seeds, a routed model's
  nearest wrong PROGRAM (top-(k-1)) through the same first steps; with
  `--wrong 2` also renormalised and no q/k-norm.

A serving cell, one process a seed (a replica owns the chip): the cell's own
runner over a window of `--seconds`, with `correctness.control` set, so that
the replica also reads, at every position of the sample, the margin of the
token that the fp8-precision reference puts first.

Every line says, per compared number, the reading, the cell's limit and the
verdict that limit gives. Lines go to stdout and chiprun_out/control.jsonl.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def emit(line: dict) -> None:
    text = json.dumps(line)
    print("control: " + text, flush=True)
    if "--tiny" in sys.argv:  # a rehearsal: nothing to keep
        return
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "control.jsonl"), "a") as f:
        f.write(text + "\n")


def verdicts(compared: dict) -> dict:
    return {name: {"value": value, "limit": limit, "passes": bool(value <= limit)} for name, (value, limit) in compared.items()}


def train_cell(cell, seeds, control_seeds: int, wrong: int, wrong_seeds: int) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmarks.lib import correct
    from benchmarks.lib.worker_train import FirstSteps, cache_everything, leaf_names, seeded_key
    from ray_tpu.models import transformer as tfm
    from ray_tpu.train import zero

    cache_everything()
    n = cell.chips
    mesh = Mesh(np.array(jax.devices()[:n]), ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    seq, per_chip = int(cell.traffic["seq_len"]), int(cell.traffic["batch_per_chip"])
    batch = per_chip * n
    arch, config, tol = cell.arch, cell.config, cell.traffic["correctness"]
    cfg = arch.model_config(config, max_seq_len=seq)
    lr = config["assumed"]["learning_rate"]["value"]
    tx = optax.adamw(lr, **correct.ADAMW)
    zero_axis = "data" if n > 1 else None
    init = jax.jit(lambda k: correct.init_weights(tfm, cfg, k), out_shardings=rep)
    fp8 = jax.jit(correct.in_fp8, out_shardings=rep)
    reference = correct.training_reference(arch, config, mesh, lr, FirstSteps.STEPS)
    device = jax.devices()[0]

    def program_of(c):
        _init_state, step = tfm.build_train_step(c, tx, mesh, zero_axis=zero_axis)

        def run(key, tokens):
            params = init(key)
            opt_state = jax.jit(tx.init, out_shardings=rep)(params) if zero_axis is None else zero.init_opt_state(tx, params, mesh, zero_axis)
            first = FirstSteps(lambda: init(key))
            for i in range(FirstSteps.STEPS):
                params, opt_state, loss = step(params, opt_state, tokens)
                first.after_step(i + 1, float(loss), params, opt_state)
            return first.readings()

        return run

    program, wrong_programs = program_of(cfg), {}
    if wrong and cfg.n_experts:
        k = cfg.n_experts_per_tok
        wrong_programs[f"program_top{k - 1}"] = program_of(cfg.replace(n_experts_per_tok=k - 1))
        if wrong > 1:
            wrong_programs["program_renormalised"] = program_of(cfg.replace(norm_topk_prob=not cfg.norm_topk_prob))
            if cfg.qk_norm:
                wrong_programs["program_no_qk_norm"] = program_of(cfg.replace(qk_norm=False))

    def rows_repeated(tokens, keep: int):
        """The batch with only its first `keep` rows in it, each as often as the others, where that divides; else those rows alone."""
        if batch % keep == 0:
            return jax.device_put(jnp.tile(tokens[:keep], (batch // keep, 1)), rows)
        return jax.device_put(tokens[:keep], rep if keep % n else rows)

    for i, seed in enumerate(seeds):
        key = seeded_key(seed)
        names = leaf_names(init, key)
        tokens = jax.jit(lambda k: jax.random.randint(k, (batch, seq), 0, cfg.vocab_size, jnp.int32), out_shardings=rows)(jax.random.fold_in(key, 1))
        t0 = time.monotonic()
        read = {"program": program(key, tokens)}
        if i < wrong_seeds:
            read.update({name: run(key, tokens) for name, run in wrong_programs.items()})
        gc.collect()
        t1 = time.monotonic()
        ref = reference(lambda: init(key), tokens)
        t2 = time.monotonic()
        if i < control_seeds:
            read["control"] = reference(lambda: fp8(init(key)), tokens)
            read["half_batch"] = reference(lambda: init(key), rows_repeated(tokens, batch - batch // 2))
            if n > 1:
                read["exchange_left_out"] = reference(lambda: init(key), rows_repeated(tokens, per_chip))
        out = {"workload": cell.name, "seed": seed, "device": f"{device.platform} {device.device_kind}", "reference_losses": ref["losses"],
               "seconds": {"programs": t1 - t0, "reference": t2 - t1}}
        for name, got in read.items():
            compared, facts = correct.compare_training(got, ref, tol, names)
            out[name] = dict(verdicts(compared), worst_leaves={k: facts[k]["worst_leaf"] for k in ("grad_norm_gap", "param_change_gap")},
                             medians={k: facts[k]["median"] for k in ("grad_norm_gap", "param_change_gap")}, losses=got["losses"])
        out["leaves_not_counted_in_change"] = facts["leaves_not_counted_in_change"]
        emit(out)
        del read, ref
        gc.collect()


def serve_one(argv) -> int:
    """One run of a serving cell with the control read beside the program (a process of its own)."""
    from benchmarks import run

    def prepare(cell):
        cell.traffic["correctness"]["control"] = True
        if "--tiny" in argv:
            from benchmarks.lib import rehearsal

            rehearsal.shrink(cell)

    return run.main([a for a in argv if a != "--tiny"], prepare=prepare)


def serve_cell(cell, seeds, seconds: float, tiny: bool) -> None:
    from benchmarks.lib import correct

    for seed in seeds:
        cmd = [sys.executable, os.path.abspath(__file__), "one", "--workload", cell.name, "--seed", str(seed), "--seconds", str(seconds),
               "--trace", "0"] + (["--tiny"] if tiny else [])
        p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        facts = [ln for ln in p.stdout.splitlines() if ln.startswith("benchmark: facts ")]
        if p.returncode or not facts:
            emit({"workload": cell.name, "seed": seed, "failed": p.returncode, "stderr": p.stderr[-1500:]})
            continue
        sample = json.loads(facts[-1][len("benchmark: facts "):])["served_sample"]
        tol = sample["limits"]
        emit({
            "workload": cell.name, "seed": seed, "seconds": seconds, "requests": len(sample["requests"]),
            "program": dict(sample["margins"], passes=correct.judge(sample["margins"], tol)),
            "control": dict(sample["control"], passes=correct.judge(sample["control"], tol)),
            "limits": tol, "reference_seconds": sample["seconds"], "line": json.loads(p.stdout.strip().splitlines()[-1]),
        })


def main() -> int:
    if len(sys.argv) > 1 and sys.argv[1] == "one":
        return serve_one(sys.argv[2:])
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="11")
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--wrong", type=int, default=0, help="a routed model: 1 = top-(k-1), 2 = renormalised and no q/k-norm too")
    ap.add_argument("--wrong-seeds", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=10.0, help="a serving cell's window: long enough to finish the mix's longest requests")
    ap.add_argument("--tiny", type=int, default=0, help="TINY widths on whatever backend there is (a rehearsal of this tool)")
    args = ap.parse_args()

    from benchmarks.lib import rehearsal, spec

    cell = spec.find_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    if cell.traffic["runner"] != "train_steps":
        serve_cell(cell, seeds, args.seconds, bool(args.tiny))
        return 0
    if args.tiny:
        rehearsal.shrink(cell)
    train_cell(cell, seeds, args.control_seeds, args.wrong, args.wrong_seeds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
