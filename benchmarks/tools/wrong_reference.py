"""The wrong models a cell's `correct` has to refuse, for any architecture
file named here: each a copy of `archs/<arch>.py` with ONE line of its
reference altered (the program and a reference that differ by one term,
whichever side is wrong), and the precision control in the same form: the
reference with every weight matrix at fp8's 3 mantissa bits (`tools/control.py`
reads that control from a second, rounded copy of the weights, which 6.6 GB of
weights leave little room for beside themselves and both caches).
`tools/wrong_models.py` (afmoe) and `tools/wrong_retention.py` (brumby) are
this for one architecture each; a later architecture adds its lines to `WRONG`
here, not a fourth file.

    chiprun -- python3 benchmarks/tools/wrong_reference.py --workload solaropen2-serve-reasoning-batch \\
        --wrong no_decay,top_7,fp8_weights --seed 2147483700 [--seconds 10]

makes a copy of the benchmark under `.chipcheck/wrong/` (git-ignored) in which
each named wrong model is a configuration and a cell of its own, new files
only, runs each through that copy's `run.py`, and says per run what `correct`
compared and decided. Lines go to stdout and chiprun_out/wrong_reference.jsonl.
Never part of a check. The tests (`tests/test_kda.py`,
`benchmarks/tests/test_solar_open2_cell.py`) use `source`, `load` and
`add_cells` at TINY widths.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import types

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

FP8 = ("    return w.astype(F32)\n", "    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=3).astype(F32)\n")
# arch: {name: (the sound line, the line in its place)}
WRONG = {
    "solar_open2": {
        "no_decay": ('    g = -jnp.exp(_f32(a["a_log"]))[None, :, None] * jax.nn.softplus(f + _f32(a["dt_bias"])).reshape(s, h, hd)  # [M]\n',
                     "    g = jnp.zeros((s, h, hd), F32) * jnp.sum(f)\n"),
        "beta_in_0_1": ('    beta = 2.0 * jax.nn.sigmoid(hn @ _f32(a["w_b"]))  # [M] in (0, 2)\n', '    beta = jax.nn.sigmoid(hn @ _f32(a["w_b"]))\n'),
        "no_conv": ('    q, k, v = (_short_conv(hn @ _f32(a["w" + n]), a["conv_" + n]).reshape(s, h, hd) for n in "qkv")\n',
                    '    q, k, v = (jax.nn.silu(hn @ _f32(a["w" + n])).reshape(s, h, hd) for n in "qkv")\n'),
        "no_l2norm": ("    q, k = _l2norm(q) / math.sqrt(hd), _l2norm(k)  # [M]\n", "    q, k = q / math.sqrt(hd), k\n"),
        "top_7": ('    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"])[1]  # [M] the bias selects; it never weighs\n',
                  '    top_e = jax.lax.top_k(scores + _f32(mlp["router_bias"]), m["k"] - 1)[1]\n'),
        "no_shared_expert": ('    return x + _experts(hn, _router_weights(hn, w["mlp"], m), stacks, index, m) + _swiglu(hn, w["mlp"]["shared"])\n',
                             '    return x + _experts(hn, _router_weights(hn, w["mlp"], m), stacks, index, m)\n'),
        "no_attention_gate": ('    o = _attention(q, k, v) * jax.nn.sigmoid(hn @ _f32(a["wg"]))  # [M] the gate: elementwise, on the layer\'s normed input\n',
                              "    o = _attention(q, k, v)\n"),
        # the held experts weighed as the next rank's: the router's columns 40-79 for rank 0 of 8 (in the reference's sum
        # alone: `dims` also tells the program which experts it holds)
        "next_ranks_experts": ('        return acc + term(hn, *(stacks[name][(*index, e)] for name in EXPERT_WEIGHTS), weights[:, m["first"] + e]), None\n',
                               '        return acc + term(hn, *(stacks[name][(*index, e)] for name in EXPERT_WEIGHTS), weights[:, (m["first"] + m["held"]) % m["E"] + e]), None\n'),
        "fp8_weights": FP8,
    },
}


def arch_path(arch: str) -> str:
    return os.path.join(ROOT, "benchmarks", "archs", arch + ".py")


def source(arch: str, name: str) -> str:
    """`archs/<arch>.py` with the wrong model's one line in place."""
    src = open(arch_path(arch)).read()
    sound, broken = WRONG[arch][name]
    if src.count(sound) != 1:
        raise ValueError(f"archs/{arch}.py holds the line of {name!r} {src.count(sound)} times, not once")
    return src.replace(sound, broken)


def load(arch: str, name: str):
    """The wrong model's architecture file as a module (never written to disk)."""
    module = types.ModuleType(f"benchmarks.archs.{arch}_{name}")
    module.__package__ = "benchmarks.archs"
    exec(compile(source(arch, name), f"<{arch}_{name}>", "exec"), module.__dict__)
    return module


def add_cells(root: str, workload: str, names) -> dict:
    """In the checkout at `root`, adds for each wrong model its architecture
    file, a configuration naming it and a cell like `workload` that reports
    `serve_tok_s` and the cell's mean decode batch; returns {name: cell}. New
    files and BENCHMARK.json entries only."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    cells = {}
    for name in names:
        arch, cname = f"{config['arch']}_{name}", f"{conf['name']}-{name}"
        with open(os.path.join(root, "benchmarks", "archs", arch + ".py"), "w") as f:
            f.write(source(config["arch"], name))
        with open(os.path.join(root, "benchmarks", "configs", cname + ".json"), "w") as f:
            json.dump(dict(config, arch=arch), f)
        bench["configs"].append(dict(conf, name=cname, file=f"benchmarks/configs/{cname}.json"))
        cells[name] = f"{workload}-{name}"
        bench["workloads"].append(dict(cell, name=cells[name], config=cname))
        for m in bench["end_to_end"] + bench["per_layer"]:
            if workload in m.get("workloads", []) and m["name"] in ("serve_tok_s", "decode_batch_mean"):
                m["workloads"].append(cells[name])
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return cells


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--wrong", default="")
    ap.add_argument("--seed", type=int, default=2147483700)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    root = os.path.join(ROOT, ".chipcheck", "wrong")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
    from benchmarks.lib import spec

    names = args.wrong.split(",") if args.wrong else sorted(WRONG[spec.find_cell(args.workload).config["arch"]])
    cells = add_cells(root, args.workload, names)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    for i, name in enumerate(names):
        cmd = [sys.executable, os.path.join(root, "benchmarks", "run.py"), "--workload", cells[name], "--seed", str(args.seed + i),
               "--seconds", str(args.seconds), "--trace", "0"]
        p = subprocess.run(cmd, cwd=root, capture_output=True, text=True)
        last = (p.stdout.strip().splitlines() or [""])[-1]
        out = {"wrong": name, "seed": args.seed + i, "rc": p.returncode}
        if p.returncode == 0 and last.startswith("{"):
            line = json.loads(last)
            facts = [ln for ln in p.stdout.splitlines() if ln.startswith("benchmark: facts ")]
            sample = json.loads(facts[-1][len("benchmark: facts "):])["served_sample"]
            out.update(correct=line["correct"], failed=line["failed"], compared=line["compared"], margins=sample["margins"],
                       reference_seconds=sample["seconds"], serve_tok_s=line["metrics"].get("serve_tok_s", {}).get("value"))
        else:
            out["stderr"] = p.stderr[-1500:]
        text = json.dumps(out)
        print("wrong_reference: " + text, flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "wrong_reference.jsonl"), "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
