"""The wrong models an `afmoe` cell's `correct` has to refuse, each a copy of
`archs/afmoe.py` with ONE line of its reference altered (the program and a
reference that differ by one term, whichever side is wrong), and the
precision control in the same form: the reference with every weight matrix
at fp8's 3 mantissa bits. (`tools/control.py` reads that control in the
program's place from a second, rounded copy of the weights, which this
configuration's 8.5 GB of weights leave no room for beside themselves and
the pool: PERF.md section 7.)

    chiprun -- python3 benchmarks/tools/wrong_models.py --workload trinitymini-serve-agent-turns \\
        --wrong top7,no_shared_expert,no_window,rope_on_full --seed 2147483700 [--seconds 10]

makes a copy of the benchmark under `.chipcheck/wrong/` (git-ignored) in
which each named wrong model is a configuration and a cell of its own, new
files only, runs each through that copy's `run.py`, and says per run what
`correct` compared and decided. Lines go to stdout and
chiprun_out/wrong_models.jsonl. Never part of a check. The tests
(`tests/test_afmoe.py`, `benchmarks/tests/test_afmoe_cell.py`) use `source`
and `load` at TINY widths.
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
ARCH = os.path.join(ROOT, "benchmarks", "archs", "afmoe.py")

PER_LAYER = '            window, rope = m["windows"][layer], m["rope"][layer]\n'
# name: (the sound line, the line in its place)
WRONG = {
    "top7": ('    top_e = jax.lax.top_k(ranked, m["k"])[1]\n', '    top_e = jax.lax.top_k(ranked, m["k"] - 1)[1]\n'),
    "no_shared_expert": ('    out = out + _swiglu(hn, w["mlp"]["shared"])\n', "    out = out\n"),
    "no_window": (PER_LAYER, '            window, rope = 0, m["rope"][layer]\n'),
    "window_on_full": (PER_LAYER, '            window, rope = m["windows"][layer] or max(m["windows"]), m["rope"][layer]\n'),
    "rope_on_full": (PER_LAYER, '            window, rope = m["windows"][layer], True\n'),
    "no_attention_gate": ('    o = o * jax.nn.sigmoid(hn @ _f32(a["wg"]))\n', "    o = o\n"),
    "no_route_scale": ('    top_s = top_s * m["route_scale"]\n', "    top_s = top_s\n"),
    "no_selection_bias": ('    ranked = scores + _f32(mlp["router_bias"])  # the bias selects; it never weighs\n', "    ranked = scores\n"),
    "fp8_weights": ("    return w.astype(F32)\n", "    return jax.lax.reduce_precision(w, exponent_bits=8, mantissa_bits=3).astype(F32)\n"),
}


def source(name: str) -> str:
    """`archs/afmoe.py` with the wrong model's one line in place."""
    src = open(ARCH).read()
    sound, broken = WRONG[name]
    if src.count(sound) != 1:
        raise ValueError(f"archs/afmoe.py holds the line of {name!r} {src.count(sound)} times, not once")
    return src.replace(sound, broken)


def load(name: str):
    """The wrong model's architecture file as a module (never written to disk)."""
    module = types.ModuleType(f"benchmarks.archs.afmoe_{name}")
    module.__package__ = "benchmarks.archs"
    exec(compile(source(name), f"<afmoe_{name}>", "exec"), module.__dict__)
    return module


def add_cells(root: str, workload: str, names) -> dict:
    """In the checkout at `root`, adds for each wrong model its architecture
    file, a configuration naming it and a cell like `workload`; returns
    {name: cell}. New files and BENCHMARK.json entries only."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    conf = next(c for c in bench["configs"] if c["name"] == cell["config"])
    with open(os.path.join(root, conf["file"])) as f:
        config = json.load(f)
    cells = {}
    for name in names:
        arch, cname = f"afmoe_{name}", f"{conf['name']}-{name}"
        with open(os.path.join(root, "benchmarks", "archs", arch + ".py"), "w") as f:
            f.write(source(name))
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
    ap.add_argument("--wrong", default=",".join(WRONG))
    ap.add_argument("--seed", type=int, default=2147483700)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args()
    names = args.wrong.split(",")
    root = os.path.join(ROOT, ".chipcheck", "wrong")
    shutil.rmtree(root, ignore_errors=True)
    os.makedirs(root)
    shutil.copytree(os.path.join(ROOT, "benchmarks"), os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    os.symlink(os.path.join(ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
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
        print("wrong_models: " + text, flush=True)
        with open(os.path.join(ROOT, "chiprun_out", "wrong_models.jsonl"), "a") as f:
            f.write(text + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
