"""The command itself: the last line's keys, the refusal without a chip, and
a fifth cell and a new architecture, each added with new files only."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import spec

ENV = dict(os.environ, JAX_PLATFORMS="cpu")
LINE_KEYS = {"correct", "attempted", "failed", "metrics", "device", "compared"}


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
    assert list(line)[-1] == "compared"
    assert set(line["compared"]) == {"loss_step1", "loss_step2", "loss_step3", "grad_norm_gap", "param_change_gap"}
    assert all(set(c) == {"value", "limit"} and c["value"] <= c["limit"] for c in line["compared"].values())
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
    """A configuration, a mix, a metric and a cell: three new files, an entry
    each in BENCHMARK.json, and the cell's name appended to the `workloads` of
    the generic entries it reports (no entry copied, no suffix); no file that
    exists is edited."""
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    bench = spec.benchmark_json()
    new_cfg = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "deepseek-llm-7b-chat-L8.json"))
    new_cfg["num_hidden_layers"] = 6
    mix = spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa-batch.json"))
    mix.update(clients=2, turns_per_session=2, schedule_seed=7)
    metric = {"layer": "paged forward", "moves": "serve_tok_s", "reader": "span_stat",
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


def test_a_routed_models_cells_are_not_correct_against_a_top_k_minus_1_reference(tmp_path):
    """The blindness of a mean loss, shown and cured, and the proof that a
    SERVED routed model can be admitted; new files only. A copy of
    `archs/olmoe.py` whose reference routes each token to one expert fewer
    stands for the nearest wrong model (the program and a reference that
    differ by one expert a token, whichever side is wrong). Training cell at
    TINY widths: the first step's loss alone passes it, the first gradient's
    norms do not. Serving: an OLMoE configuration with engine sizes on a
    closed-loop mix is `correct` against the right reference, by the tokens
    its window served, and not against the wrong one."""
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    arch_src = open(os.path.join(spec.BENCH_DIR, "archs", "olmoe.py")).read()
    top_k = 'top_p, top_e = jax.lax.top_k(probs, m["k"])'
    assert arch_src.count(top_k) == 1
    olmoe = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "olmoe-1b-7b-0125-L2.json"))
    engine = spec.load_json(os.path.join(spec.BENCH_DIR, "configs", "deepseek-llm-7b-chat-L8.json"))["assumed"]
    served = dict(olmoe, assumed=dict(engine, **olmoe["assumed"]))
    files = {
        "archs/olmoe_top_k_minus_1.py": arch_src.replace(top_k, top_k.replace('m["k"]', 'm["k"] - 1')),
        "configs/olmoe-wrong-reference.json": dict(olmoe, arch="olmoe_top_k_minus_1"),
        "configs/olmoe-served.json": served,
        "configs/olmoe-served-wrong-reference.json": dict(served, arch="olmoe_top_k_minus_1"),
        "traffic/olmoe-docqa.json": spec.load_json(os.path.join(spec.BENCH_DIR, "traffic", "docqa-batch.json")),
    }
    bench = spec.benchmark_json()
    cells = {"olmoe-train-wrong": ("olmoe-wrong-reference", "train-fixed-batch-moe", ("train_tok_s_chip", "train_step_p50_ms")),
             "olmoe-serve": ("olmoe-served", "olmoe-docqa", ("serve_tok_s", "decode_batch_mean")),
             "olmoe-serve-wrong": ("olmoe-served-wrong-reference", "olmoe-docqa", ("serve_tok_s", "decode_batch_mean"))}
    for config in sorted({c for c, _t, _m in cells.values()}):
        bench["configs"].append({"name": config, "source": olmoe["source"], "file": f"benchmarks/configs/{config}.json",
                                 "reduced": ["num_hidden_layers"], "why": "test"})
    for cell, (config, traffic, reported) in cells.items():
        bench["workloads"].append({"name": cell, "config": config, "traffic": traffic, "chips": 1, "why": "test"})
        for m in bench["end_to_end"] + bench["per_layer"]:
            if m["name"] in reported:
                m["workloads"].append(cell)
    add_files(root, bench, files)

    line, facts = rehearse_one(root, "olmoe-train-wrong", 0, facts=True)
    assert line["correct"] is False, facts
    assert facts["checks"]["loss_step1"] is True and facts["checks"]["grad_norm_gap"] is False, facts
    line, facts = rehearse_one(root, "olmoe-serve", 0, facts=True)
    assert line["correct"] is True and line["failed"] == 0, facts
    assert facts["served_sample"]["margins"]["positions"] >= 100
    line, facts = rehearse_one(root, "olmoe-serve-wrong", 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


STEP = "            params, opt_state, loss = step(params, opt_state, tokens)\n"
FAULTS = {
    # cell, devices, file, sound line, broken line, checks that must fail (every other must pass)
    # the step's new state thrown away (it runs on copies: the step donates its arguments): the state never changes
    "state_unchanged": ("mistral7b-train-seq4k-1chip", 1, "lib/worker_train.py", STEP,
                        "            _p, _o, loss = step(*jax.tree_util.tree_map(jnp.copy, (params, opt_state)), tokens)\n",
                        {"loss_step2", "loss_step3", "grad_norm_gap", "param_change_gap"}),
    # half of the batch left out, the mean taken over the rest (the first half, twice)
    "half_batch": ("mistral7b-train-seq4k-1chip", 1, "lib/worker_train.py", STEP,
                   "            params, opt_state, loss = step(params, opt_state, jnp.concatenate([tokens[: tokens.shape[0] // 2]] * 2))\n",
                   {"grad_norm_gap"}),
    # the exchange between chips left out: every chip keeps its own slice of its own gradient (since PR 57 a leaf's
    # gradient is scattered along one of its own dimensions; the form before it sliced a flat vector and broke there)
    "exchange_left_out": ("mistral7b-train-seq4k-zero-4chip", 4, "lib/worker_train.py",
                          "    _init_state, step = tfm.build_train_step(cfg, tx, mesh, zero_axis=zero_axis)\n",
                          "    jax.lax.psum_scatter = lambda x, axis, scatter_dimension=0, tiled=True: n * jax.lax.dynamic_slice_in_dim(\n"
                          "        x, jax.lax.axis_index(axis) * (x.shape[scatter_dimension] // n), x.shape[scatter_dimension] // n, scatter_dimension)\n"
                          "    _init_state, step = tfm.build_train_step(cfg, tx, mesh, zero_axis=zero_axis)\n",
                          {"grad_norm_gap"}),
    # every decoded token altered where it is produced, under the engine
    "token_altered": ("dsllm7b-serve-docqa-batch", 1, "lib/worker_serve.py",
                      "            out = self.lm.decode(last_tokens, positions, block_tables)\n",
                      "            out = [(t + 1) % self.vocab for t in self.lm.decode(last_tokens, positions, block_tables)]\n",
                      {"served_tokens_within_reference_margin"}),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_run_with_the_timed_path_broken_underneath_is_not_correct(tmp_path, fault):
    """The rest of a run driven over a timed path with a fault planted in a
    copy of the benchmark's worker: `correct` comes out false, by the checks
    that are there to see that fault; no check of another kind fails (a
    loss of a later step may: the fault moves it too)."""
    cell, devices, rel, sound, broken, must_fail = FAULTS[fault]
    root = str(tmp_path)
    copy_of_the_benchmark(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "benchmarks", rel)
    src = open(path).read()
    assert src.count(sound) == 1
    with open(path, "w") as f:
        f.write(src.replace(sound, broken))
    line, facts = rehearse_one(root, cell, 0, devices=devices, facts=True)
    assert line["correct"] is False, facts
    failed = {name for name, ok in facts["checks"].items() if not ok}
    assert must_fail <= failed <= must_fail | {"loss_step1", "loss_step2", "loss_step3", "param_change_gap"}, facts


def test_the_fp8_precision_control_in_the_programs_place_is_not_correct():
    """The control of a served cell through the cell's own comparison, at TINY
    widths (the chip's readings at published widths are in the traffic
    files): `tools/control.py` runs the cell's runner, and the replica reads,
    at every position of the window's sample, the margin of the token that the
    reference with weights at fp8's 3 mantissa bits puts first. The program
    passes the limit, the control does not. (A TRAINED cell's control is read
    on the chip only: at these widths a bfloat16 program and an fp8-precision
    reference lie equally far from the float32 one.)"""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(spec.ROOT, ".jax_cache", "cpu_rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(spec.ROOT, "benchmarks", "tools", "control.py"), "--workload", "dsllm7b-serve-docqa-batch",
         "--seeds", "5,3000000007", "--seconds", "2", "--tiny", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    lines = [json.loads(ln[len("control: "):]) for ln in p.stdout.splitlines() if ln.startswith("control: ")]
    assert len(lines) == 2
    for line in lines:
        assert line["program"]["passes"] is True and line["control"]["passes"] is False, line
        assert line["program"]["positions"] >= 100 and line["line"]["correct"] is True
