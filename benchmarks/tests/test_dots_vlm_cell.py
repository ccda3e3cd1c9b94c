"""The cell `dotsvlm1-serve-longdoc-batch` end to end at `archs/dots_vlm.TINY`
widths on the CPU (its own configuration, traffic and metric files, with
tests/tiny.json's engine sizes and lengths laid over them by the rehearsal):
`correct` against the right reference, with the latent pages' counters read;
not `correct` in a copy of the benchmark whose architecture file takes the top
7 experts or leaves `k_r` out of the scores (`tools/wrong_dots_vlm.py`: new
files only); and not `correct` over a copy of the PROGRAM with a fault of its
own planted: a latent row not written at a chunk's border, the rope part of a
stored row taken from the position beside it."""

import os
import shutil

import pytest

from benchmarks.lib import spec
from benchmarks.tools import wrong_dots_vlm

from test_brumby_cell import rehearse_one  # rootdir-less: pytest puts this directory on the path
from test_run import copy_of_the_benchmark

CELL = "dotsvlm1-serve-longdoc-batch"


def test_the_cell_is_correct_and_reads_its_counters():
    line, facts = rehearse_one(spec.ROOT, CELL, 1, facts=True)
    assert line["correct"] is True and line["failed"] == 0, facts
    assert facts["arch_file"] == "benchmarks/archs/dots_vlm.py"
    assert facts["served_sample"]["margins"]["positions"] >= 100
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # TINY: 8 of 16 experts held (rank 1: half of groups 2 and 3) and 4 picks a token out of 2 of 4 groups
    assert 20 < got["decode_held_pick_pct"] < 80
    assert 0 < got["decode_latent_bytes_share_pct"] < 50
    # tests/tiny.json's documents of 96-176 tokens asked several questions: most prompt pages are hits
    assert got["prefix_hit_page_share_pct"] > 50
    assert 1 <= got["decode_batch_mean"] <= 2 and got["serve_compiles_in_window"] == 0
    assert not [name for name in got if "roofline" in name or "idle" in name or "time_share" in name]  # no device number from a CPU
    assert set(rehearse_one(spec.ROOT, CELL, 0)["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["top_7", "no_rope_key"])
def test_a_wrong_reference_is_not_correct(tmp_path, wrong):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    cells = wrong_dots_vlm.add_cells(root, CELL, [wrong])
    line, facts = rehearse_one(root, cells[wrong], 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    assert facts["checks"]["no_request_failed"] and facts["arch_file"] == f"benchmarks/archs/dots_vlm_{wrong}.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


# In a copy of the program made to show them: its prefill walks one-page chunks (16 tokens at TINY), so that prompts
# cross many borders. name: (the sound line of models/transformer.py, the line in its place)
WRITTEN = "            lp_ = lp.at[layer, dest_page].set(_latent_rows(cfg, c_kv[0], k_r[0]).reshape(pages, T, -1))\n"
PROGRAM_FAULTS = {
    "sound": (WRITTEN, WRITTEN),
    "a_latent_row_not_written_at_a_chunks_border": (WRITTEN, WRITTEN.replace("c_kv[0], k_r[0]", "c_kv[0].at[-1].set(0), k_r[0].at[-1].set(0)")),
    "the_rope_part_from_the_position_beside": (WRITTEN, WRITTEN.replace("k_r[0])", "jnp.roll(k_r[0], 1, axis=0))")),
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
    chunk = "PREFILL_CHUNK_TOKENS = 256\n"
    assert src.count(sound) == 1 and src.count(chunk) == 1
    with open(path, "w") as f:
        f.write(src.replace(sound, broken).replace(chunk, "PREFILL_CHUNK_TOKENS = 8\n"))
    line, facts = rehearse_one(root, CELL, 0, facts=True)
    assert facts["checks"]["no_request_failed"] and facts["checks"]["engine_not_failed"], facts
    assert line["correct"] is (fault == "sound"), facts
    assert facts["checks"]["served_tokens_within_reference_margin"] is (fault == "sound")
