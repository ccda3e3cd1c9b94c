"""The command itself: the last line's keys, the refusal without a chip, and
a fifth cell added with new files only."""

import json
import os
import shutil
import subprocess
import sys

from benchmarks.lib import spec

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def rehearse_one(root, workload, trace, devices=1):
    env = dict(ENV, XLA_FLAGS=f"--xla_force_host_platform_device_count={devices}",
               JAX_COMPILATION_CACHE_DIR=os.path.join(spec.ROOT, ".jax_cache", "cpu_rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "rehearse.py"), "one", "--workload", workload,
         "--seed", "3000000019", "--seconds", "2", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


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
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(spec.ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"))
    before = {}
    for d, _dirs, files in os.walk(os.path.join(root, "benchmarks")):
        for f in files:
            before[os.path.join(d, f)] = open(os.path.join(d, f), "rb").read()

    bench = spec.benchmark_json()
    new_cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "deepseek-llm-7b-chat-L8.json"))
    new_cfg["num_hidden_layers"] = 6
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa-batch.json"))
    mix.update(clients=2, turns_per_session=2, schedule_seed=7)
    metric = {"layer": "paged forward", "moves": "serve_tok_s", "cells": ["fifth-cell"], "reader": "span_stat",
              "args": {"span": "bench.prefill", "stat": "mean_arg", "arg": "cached_tokens"}}
    for rel, data in (("configs/fifth-config.json", new_cfg), ("traffic/fifth-mix.json", mix),
                      ("metrics/prefill_cached_tokens_mean.json", metric)):
        with open(os.path.join(root, "benchmarks", rel), "w") as f:
            json.dump(data, f)
    bench["configs"].append({"name": "fifth-config", "source": new_cfg["source"], "file": "benchmarks/configs/fifth-config.json",
                             "reduced": ["num_hidden_layers"], "why": "test"})
    bench["workloads"].append({"name": "fifth-cell", "config": "fifth-config", "traffic": "fifth-mix", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "prefill_cached_tokens_mean", "unit": "tokens", "better": "higher", "source": "program_span",
                               "layer": "paged forward", "moves": "serve_tok_s", "workloads": ["fifth-cell"]})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "dsllm7b-serve-docqa-batch" in m.get("workloads", []) and m["name"] in ("serve_tok_s", "decode_batch_mean"):
            m["workloads"].append("fifth-cell")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    line = rehearse_one(root, "fifth-cell", 1)
    assert line["correct"] is True
    assert "prefill_cached_tokens_mean" in line["metrics"] and "decode_batch_mean" in line["metrics"]
    assert set(rehearse_one(root, "fifth-cell", 0)["metrics"]) == {"serve_tok_s", "setup_s"}
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"
