"""The command itself: the last line's keys, the refusal without a chip, and
a fifth cell and a new architecture, each added with new files only."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.lib import spec

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse_one(root, workload, trace, devices=1, facts=False):
    env = dict(ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(spec.ROOT, ".jax_cache", "cpu_rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "rehearse.py"), "one", "--workload", workload,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    if facts:
        said = next(ln for ln in reversed(lines) if ln.startswith("benchmark: facts "))
        return line, json.loads(said[len("benchmark: facts "):])
    return line


def copy_of_the_benchmark(root):
    """A checkout in `root` with the benchmark's files copied, and what each held."""
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(spec.ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()
    return before


def add_files(root, bench, files):
    for rel, data in files.items():
        with open(os.path.join(root, "benchmarks", rel), "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)


def test_last_line_has_exactly_the_contracts_keys():
    cell = spec.find_cell("mistral7b-train-seq4k-1chip")
    line = rehearse_one(spec.ROOT, cell.name, 0)
    assert set(line) == LINE_KEYS
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in line["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(line["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    assert line["correct"] is True and line["attempted"] > 0 and line["failed"] == 0
    traced = rehearse_one(spec.ROOT, cell.name, 1)
    assert set(traced) - {"breakdown"} == LINE_KEYS
    assert set(traced["metrics"]) <= {m["name"] for m in cell.per_layer}
    assert "train_step_p50_ms" in traced["metrics"] and "train_mfu_pct" not in traced["metrics"]  # no device number from a CPU


def test_without_a_chip_the_command_exits_non_zero_and_prints_no_result():
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmarks", "run.py"), "--workload", "mistral7b-train-seq4k-1chip",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=ENV, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "never runs on a CPU" in p.stderr


def test_a_fifth_cell_is_added_with_new_files_only(tmp_path):
    """A configuration, a mix, a metric and a cell: four new files and one
    entry each in BENCHMARK.json; no file that exists is edited."""
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    bench = spec.benchmark_json()
    new_cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "deepseek-llm-7b-chat-L8.json"))
    new_cfg["num_hidden_layers"] = 6
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa-batch.json"))
    mix.update(clients=2, turns_per_session=2, schedule_seed=7)
    metric = {"layer": "paged forward", "moves": "serve_tok_s", "cells": ["fifth-cell"], "reader": "span_stat",
              "args": {"span": "bench.prefill", "stat": "mean_arg", "arg": "cached_tokens"}}
    bench["configs"].append({"name": "fifth-config", "source": new_cfg["source"], "file": "benchmarks/configs/fifth-config.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "fifth-cell", "config": "fifth-config", "traffic": "fifth-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "prefill_cached_tokens_mean", "unit": "tokens", "better": "higher", "source": "program_span",
                               "layer": "paged forward", "moves": "serve_tok_s", "workloads": ["fifth-cell"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dsllm7b-serve-docqa-batch" in m.get("workloads", []) and m["name"] in ("serve_tok_s", "decode_batch_mean"):
            m["workloads"].append("fifth-cell")
    add_files(root, bench, {"configs/fifth-config.json": new_cfg, "traffic/fifth-mix.json": mix,
                            "metrics/prefill_cached_tokens_mean.json": metric})

    line = rehearse_one(root, "fifth-cell", 1)
    assert line["correct"] is True
    assert "prefill_cached_tokens_mean" in line["metrics"] and "decode_batch_mean" in line["metrics"]
    assert set(rehearse_one(root, "fifth-cell", 0)["metrics"]) == {"serve_tok_s", "setup_s"}
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


GPTJ = {  # https://huggingface.co/EleutherAI/gpt-j-6b/blob/main/config.json
    "source": "https://huggingface.co/EleutherAI/gpt-j-6b/blob/main/config.json", "arch": "gptj_block",
    "architectures": ["GPTJForCausalLM"], "model_type": "gptj", "n_embd": 4096, "n_head": 16, "n_layer": 2, "n_inner": None,
    "n_positions": 2048, "rotary_dim": 64, "vocab_size": 50400, "layer_norm_epsilon": 1e-05, "activation_function": "gelu_new",
    "tie_word_embeddings": False, "torch_dtype": "bfloat16", "reduced_from": {"n_layer": 28},
}


def test_a_new_architecture_is_added_with_new_files_only(tmp_path):
    """An architecture the program runs and the benchmark does not (GPT-J's
    block: one pre-norm feeding attention and an ungated gelu MLP in
    parallel): an architecture file with its own mapping, plain reference and
    counts, a configuration naming it, a train cell and a closed-loop serve
    cell, and entries in BENCHMARK.json; no file that exists is edited. The
    run names the file that decided `correct`, and a copy of it whose
    reference adds the MLP after attention instead of beside it decides
    `correct` false: the harness used the named file, and the tolerances
    tell two blocks apart."""
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    arch_src = open(os.path.join(spec.BENCH_DIR, "tests", "new_arch", "gptj_block.py")).read()
    parallel = "            x = x + attn + _gelu_new(hn @ w[\"mlp\"][\"w_up\"]) @ w[\"mlp\"][\"w_down\"]  # the parallel block\n"
    sequential = (
        "            x = x + attn\n"
        "            hn = _layer_norm(x, w[\"attn_norm\"][\"scale\"], m[\"eps\"])\n"
        "            x = x + _gelu_new(hn @ w[\"mlp\"][\"w_up\"]) @ w[\"mlp\"][\"w_down\"]\n"
    )
    assert arch_src.count(parallel) == 1
    dense = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "deepseek-llm-7b-chat-L8.json"))
    assumed = dict(dense["assumed"], learning_rate={"value": 1e-4, "why": "test"})
    files = {
        "archs/gptj_block.py": arch_src,
        "archs/gptj_sequential.py": arch_src.replace(parallel, sequential),
        "configs/gptj-L2.json": dict(GPTJ, assumed=assumed),
        "configs/gptj-L2-wrong-reference.json": dict(GPTJ, arch="gptj_sequential", assumed=assumed),
        "traffic/gptj-train.json": spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "train-fixed-batch.json")),
        "traffic/gptj-docqa.json": spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa-batch.json")),
    }
    bench = spec.benchmark_json()
    cells = {"gptj-train": ("gptj-train", ("train_tok_s_chip", "train_step_p50_ms")),
             "gptj-serve": ("gptj-docqa", ("serve_tok_s", "decode_batch_mean"))}
    for config, suffix in (("gptj-L2", ""), ("gptj-L2-wrong-reference", "-wrong")):
        bench["configs"].append({"name": config, "source": GPTJ["source"], "file": f"benchmarks/configs/{config}.json",
                                 "reduced": ["n_layer"], "why": "test"})
        for cell, (traffic, reported) in cells.items():
            bench["workloads"].append({"name": cell + suffix, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
            for m in bench["end_to_end"] + bench["per_layer"]:
                if m["name"] in reported:
                    m["workloads"].append(cell + suffix)
    add_files(root, bench, files)

    for cell in cells:
        line, facts = rehearse_one(root, cell, 1, facts=True)
        assert line["correct"] is True and line["failed"] == 0, facts
        assert facts["arch_file"] == "benchmarks/archs/gptj_block.py"
        wrong, facts = rehearse_one(root, cell + "-wrong", 1, facts=True)
        assert wrong["correct"] is False, facts
        assert facts["arch_file"] == "benchmarks/archs/gptj_sequential.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"
