"""The cell `mimov25-serve-longctx-batch` end to end at `archs/mimo_v2.TINY`
widths on the CPU (its own configuration, traffic and metric files, with
tests/tiny.json's engine sizes and lengths laid over them by the rehearsal):
`correct` against the right reference, with both caches' counters read; not
`correct` in a copy of the benchmark whose architecture file leaves the sink
out or groups a window layer's heads as a global layer's
(`tools/wrong_mimo_v2.py`: new files only); and not `correct` over a copy of
the PROGRAM with a fault of its own planted: the ring not carried across a
prefill chunk's border, the sink dropped, a stale ring row read past the
validity mask."""

import os
import shutil

import pytest

from benchmarks.lib import spec
from benchmarks.tools import wrong_mimo_v2

from test_brumby_cell import rehearse_one  # rootdir-less: pytest puts this directory on the path
from test_run import copy_of_the_benchmark

CELL = "mimov25-serve-longctx-batch"


def test_the_cell_is_correct_and_reads_its_counters():
    line, facts = rehearse_one(spec.ROOT, CELL, 1, facts=True)
    assert line["correct"] is True and line["failed"] == 0, facts
    assert facts["arch_file"] == "benchmarks/archs/mimo_v2.py"
    assert facts["served_sample"]["margins"]["positions"] >= 100
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # TINY: 8 of 16 experts held and 4 picks a token: about half of the picks fall here
    assert 30 < got["decode_held_pick_pct"] < 70
    assert 0 < got["decode_state_bytes_share_pct.hybrid"] < 50 and 0 < got["decode_kv_bytes_share_pct"] < 50
    # of ~200 live positions a window layer reads 16: 3 of 7 layers whole and a twelfth of the other 4
    assert 43 < got["decode_kv_window_read_pct"] < 60
    assert 1 <= got["decode_batch_mean"] <= 4 and got["serve_compiles_in_window"] == 0
    assert not [name for name in got if "roofline" in name or "idle" in name or "time_share" in name]  # no device number from a CPU
    assert set(rehearse_one(spec.ROOT, CELL, 0)["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["no_sink", "window_heads_as_global"])
def test_a_wrong_reference_is_not_correct(tmp_path, wrong):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    shutil.copy(os.path.join(spec.ROOT, "BENCHMARK.json"), root)
    cells = wrong_mimo_v2.add_cells(root, CELL, [wrong])
    line, facts = rehearse_one(root, cells[wrong], 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    assert facts["checks"]["no_request_failed"] and facts["arch_file"] == f"benchmarks/archs/mimo_v2_{wrong}.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


# In a copy of the program made to show them (as test_gigachat3_5_cell.py's): its prefill walks one-page chunks (16
# tokens at TINY: one window), so that prompts cross many borders, and its rings start as 1e3 everywhere, which a sound
# copy never reads into a served token and a row read past the validity mask does.
# name: (the sound line of models/transformer.py, the line in its place)
BEFORE = "    before = _ring_rows(c0 - 1, ring)  # what the ring's rows hold as the chunk starts\n"
SINK = '        more = (ap["sink"],) if "sink" in ap else () if gate is None else (jax.nn.log_sigmoid(gate),)\n'
SEEN = "    seen = (back >= 0) & (back < ring) & (k_pos >= 0)[None, :]\n"
PROGRAM_FAULTS = {
    "sound": (BEFORE, BEFORE),
    "ring_not_carried_across_a_chunk_border": (BEFORE, "    before = jnp.full((ring,), -1) + 0 * c0\n"),
    "the_sink_dropped": (SINK, "        more = () if gate is None else (jax.nn.log_sigmoid(gate),)\n"),
    "a_stale_ring_row_read_past_the_validity_mask": (SEEN, "    seen = (back >= 0) & (back < ring)\n"),
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
    chunk, pool = "PREFILL_CHUNK_TOKENS = 256\n", '{"ring_k": jnp.zeros((*shape, kvh * cfg.head_dim), cfg.dtype), "ring_v": jnp.zeros('
    assert src.count(sound) == 1 and src.count(chunk) == 1 and src.count(pool) == 1
    with open(path, "w") as f:
        f.write(src.replace(sound, broken).replace(chunk, "PREFILL_CHUNK_TOKENS = 8\n").replace(pool, pool.replace("jnp.zeros(", "1e3 + jnp.zeros(")))
    line, facts = rehearse_one(root, CELL, 0, facts=True)
    assert facts["checks"]["no_request_failed"] and facts["checks"]["engine_not_failed"], facts
    assert line["correct"] is (fault == "sound"), facts
    assert facts["checks"]["served_tokens_within_reference_margin"] is (fault == "sound")
