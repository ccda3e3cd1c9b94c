"""The cell `gigachat35-serve-longanswer-batch` end to end at
`archs/gigachat3_5.TINY` widths on the CPU (its own configuration, traffic and
metric files, with tests/tiny.json's engine sizes and lengths laid over them
by the rehearsal): `correct` against the right reference, with both caches'
counters read; not `correct` in a copy of the benchmark whose architecture
file takes the top 7 experts or puts value head j on key head j % the key
heads (`tools/wrong_gigachat3_5.py`: new files only); and not `correct` over a
copy of the PROGRAM with a fault of its own planted: the state not carried
across a prefill chunk's border, a slot's old state not cleared where a prompt
starts, the convolution's tail not carried, the latent layer's gate dropped."""

import os
import shutil

import pytest

from benchmarks.lib import spec
from benchmarks.tools import wrong_gigachat3_5

from test_brumby_cell import rehearse_one  # rootdir-less: pytest puts this directory on the path
from test_run import copy_of_the_benchmark

CELL = "gigachat35-serve-longanswer-batch"


def test_the_cell_is_correct_and_reads_its_counters():
    line, facts = rehearse_one(spec.ROOT, CELL, 1, facts=True)
    assert line["correct"] is True and line["failed"] == 0, facts
    assert facts["arch_file"] == "benchmarks/archs/gigachat3_5.py"
    assert facts["served_sample"]["margins"]["positions"] >= 100
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # TINY: 8 of 16 experts held and 4 picks a token: about half of the picks fall here
    assert 30 < got["decode_held_pick_pct"] < 70
    assert 0 < got["decode_state_bytes_share_pct.hybrid"] < 50 and 0 < got["decode_kv_bytes_share_pct"] < 50
    assert 1 <= got["decode_batch_mean"] <= 4 and got["serve_compiles_in_window"] == 0
    assert not [name for name in got if "roofline" in name or "idle" in name or "time_share" in name]  # no device number from a CPU
    assert set(rehearse_one(spec.ROOT, CELL, 0)["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["top_7", "key_head_j_mod"])
def test_a_wrong_reference_is_not_correct(tmp_path, wrong):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    cells = wrong_gigachat3_5.add_cells(root, CELL, [wrong])
    line, facts = rehearse_one(root, cells[wrong], 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    assert facts["checks"]["no_request_failed"] and facts["arch_file"] == f"benchmarks/archs/gigachat3_5_{wrong}.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


# In a copy of the program made to show them (as test_solar_open2_cell.py's): its prefill walks one-page chunks (16
# tokens at TINY), so that prompts cross many borders, and its state pool starts as 1e3 everywhere, which a sound copy
# never reads into a served token and a slot that is not cleared does.
# name: (the sound line of models/transformer.py, the line in its place)
STORED = "            return o[None], (sp.at[layer, slot].set(state_out), tp.at[layer, slot].set(tails_out))\n"
CLEARED = "            state_in = jnp.where(c0 > 0, sp[layer, slot], jnp.zeros((), sp.dtype))\n"
TAILS = "            tails_in = jnp.where(c0 > 0, tp[layer, slot], jnp.zeros((), tp.dtype))\n"
GATED = "            o = (o.reshape(*gate.shape) * jax.nn.sigmoid(gate)).astype(cfg.dtype)\n"
PROGRAM_FAULTS = {
    "sound": (CLEARED, CLEARED),
    "state_not_carried_across_a_chunk_border": (STORED, "            return o[None], (sp, tp.at[layer, slot].set(tails_out))\n"),
    "old_state_not_cleared_where_a_prompt_starts": (CLEARED, "            state_in = sp[layer, slot]\n"),
    "tail_not_carried_across_a_chunk_border": (TAILS, "            tails_in = jnp.zeros_like(tp[layer, slot])\n"),
    "the_latent_layers_gate_dropped": (GATED, "            o = (o.reshape(*gate.shape) + 0.0 * jax.nn.sigmoid(gate)).astype(cfg.dtype)\n"),
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
    chunk, pool = "PREFILL_CHUNK_TOKENS = 256\n", '"s": jnp.zeros((n_kda, slots, cfg.n_heads, cfg.head_dim, cfg.head_dim), jnp.float32)'
    assert src.count(sound) == 1 and src.count(chunk) == 1 and src.count(pool) == 1
    with open(path, "w") as f:
        f.write(src.replace(sound, broken).replace(chunk, "PREFILL_CHUNK_TOKENS = 8\n").replace(pool, pool.replace("jnp.zeros(", "jnp.full(").replace(", jnp.float32)", ", 1e3, jnp.float32)")))
    line, facts = rehearse_one(root, CELL, 0, facts=True)
    assert facts["checks"]["no_request_failed"] and facts["checks"]["engine_not_failed"], facts
    assert line["correct"] is (fault == "sound"), facts
    assert facts["checks"]["served_tokens_within_reference_margin"] is (fault == "sound")
