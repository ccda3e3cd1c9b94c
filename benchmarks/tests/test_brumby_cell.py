"""The cell `brumby14b-serve-longgen-batch` end to end at `archs/brumby.TINY`
widths on the CPU (its own configuration, traffic and metric files, with
tests/tiny.json's engine sizes and lengths laid over them by the rehearsal):
`correct` against the right reference, with the state's counters read; not
`correct` in a copy of the benchmark whose architecture file computes degree 1
or leaves the gate out (`tools/wrong_retention.py`: new files only); and not
`correct` over a copy of the PROGRAM with a fault of its own planted: the
state not carried across a prefill chunk's border, a slot's old state not
cleared where a prompt starts."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import spec
from benchmarks.tools import wrong_retention

from test_run import ENV, copy_of_the_benchmark  # rootdir-less: pytest puts this directory on the path

CELL = "brumby14b-serve-longgen-batch"


def rehearse_one(root, workload, trace, facts=False):
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=1", PYTHONPATH=root,
               JAX_COMPILATION_CACHE_DIR=os.path.join(spec.ROOT, ".jax_cache", "cpu_rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "rehearse.py"), "one", "--workload", workload,
         "--seed", "3000000019", "--seconds", "3", "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600,
    )
    assert p.returncode == 0, p.stderr[-3000:]
    lines = p.stdout.strip().splitlines()
    line = json.loads(lines[-1])
    if facts:
        said = next(ln for ln in reversed(lines) if ln.startswith("benchmark: facts "))
        return line, json.loads(said[len("benchmark: facts "):])
    return line


def test_the_cell_is_correct_and_reads_its_counters():
    line, facts = rehearse_one(spec.ROOT, CELL, 1, facts=True)
    assert line["correct"] is True and line["failed"] == 0, facts
    assert facts["arch_file"] == "benchmarks/archs/brumby.py"
    assert facts["served_sample"]["margins"]["positions"] >= 100
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # TINY: two layers of 2 K/V heads x 136 pairs x 17 float32 in and out a live row, beside 90 k float32 weights
    assert 30 < got["decode_state_bytes_share_pct"] < 60
    assert 1 <= got["decode_batch_mean"] <= 2 and got["serve_compiles_in_window"] == 0
    assert not [name for name in got if "roofline" in name or "idle" in name or "time_share" in name]  # no device number from a CPU
    assert set(rehearse_one(spec.ROOT, CELL, 0)["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["degree_1", "no_gate"])
def test_a_wrong_reference_is_not_correct(tmp_path, wrong):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    cells = wrong_retention.add_cells(root, CELL, [wrong])
    line, facts = rehearse_one(root, cells[wrong], 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    assert facts["checks"]["no_request_failed"] and facts["arch_file"] == f"benchmarks/archs/brumby_{wrong}.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


# In a copy of the program made to show them. Weights drawn from a seed gate to about a half a token, so a state forgets
# within a few tokens: a lost carry reaches a served token only where a prompt ends just behind a chunk's border, so the
# copy's prefill walks 8-token chunks; and what a slot's last owner left has faded by a prompt's end, so the copy's
# pool starts as NaN, which does not fade (the sound copy never reads it: a prompt starts from nothing and an inactive
# row's result is nobody's). The carry is lost the way that shows at once: a chunk's state is not stored, so the next
# chunk and every decode step start from what the slot held.
# name: (the sound line of models/transformer.py, the line in its place)
CARRIED = "        s_in = jnp.where(c0 > 0, sp[layer, slot], 0.0)\n"
STORED = "        return y[None].astype(cfg.dtype), (sp.at[layer, slot].set(s_out), zp.at[layer, slot].set(z_out))\n"
PROGRAM_FAULTS = {
    "sound": (CARRIED, CARRIED),
    "state_not_carried_across_a_chunk_border": (STORED, "        return y[None].astype(cfg.dtype), (sp, zp)\n"),
    "old_state_not_cleared_where_a_prompt_starts": (CARRIED, "        s_in = sp[layer, slot]\n"),
}


@pytest.mark.parametrize("fault", sorted(PROGRAM_FAULTS))
def test_a_fault_planted_in_a_copy_of_the_program_is_not_correct(tmp_path, fault):
    root = str(tmp_path)
    shutil.copytree(spec.BENCH_DIR, os.path.join(root, "benchmarks"), ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copytree(os.path.join(spec.ROOT, "ray_tpu"), os.path.join(root, "ray_tpu"), ignore=shutil.ignore_patterns("__pycache__", "_build"))
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    path = os.path.join(root, "ray_tpu", "models", "transformer.py")
    src = open(path).read()
    sound, broken = PROGRAM_FAULTS[fault]
    chunk, pool = "PREFILL_CHUNK_TOKENS = 256\n", '"s": jnp.zeros((cfg.n_layers, num_pages, cfg.n_kv_heads, cfg.head_dim, D), jnp.float32)'
    assert src.count(sound) == 1 and src.count(chunk) == 1 and src.count(pool) == 1
    with open(path, "w") as f:
        f.write(src.replace(sound, broken).replace(chunk, "PREFILL_CHUNK_TOKENS = 8\n").replace(pool, pool.replace("jnp.zeros(", "jnp.full(").replace(", jnp.float32)", ", jnp.nan, jnp.float32)")))
    line, facts = rehearse_one(root, CELL, 0, facts=True)
    assert facts["checks"]["no_request_failed"] and facts["checks"]["engine_not_failed"], facts
    assert line["correct"] is (fault == "sound"), facts
    assert facts["checks"]["served_tokens_within_reference_margin"] is (fault == "sound")
