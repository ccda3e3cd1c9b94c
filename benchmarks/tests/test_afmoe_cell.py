"""The cell `trinitymini-serve-agent-turns` end to end at `archs/afmoe.TINY`
widths on the CPU (its own configuration, traffic and metric files, with
tests/tiny.json's engine sizes and lengths laid over them by the rehearsal):
`correct` against the right reference, with the counters of the routed FFN and
the windows read; and not `correct` in a copy of the benchmark whose
architecture file leaves the window out of the sliding layers, or routes each
token to one expert fewer (`tools/wrong_models.py`: new files only)."""

import json
import os
import subprocess
import sys

import pytest

from benchmarks.lib import spec
from benchmarks.tools import wrong_models

from test_run import ENV, copy_of_the_benchmark  # rootdir-less: pytest puts this directory on the path

CELL = "trinitymini-serve-agent-turns"


def rehearse_one(root, workload, trace, facts=False):
    """test_run.rehearse_one with a window of 4 s: a request of this cell at
    TINY widths (a prompt of 1.1-1.7 k tokens, 64 decode steps of six layers
    over 2 048-position tables) takes about 2 s on the CPU, and the window has
    to finish some."""
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=1",
               JAX_COMPILATION_CACHE_DIR=os.path.join(spec.ROOT, ".jax_cache", "cpu_rehearsal"))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "rehearse.py"), "one", "--workload", workload,
         "--seed", "3000000019", "--seconds", "4", "--trace", str(trace)],
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
    assert facts["arch_file"] == "benchmarks/archs/afmoe.py"
    assert facts["served_sample"]["margins"]["positions"] >= 100
    got = {name: m["value"] for name, m in line["metrics"].items()}
    # every context (1 122 tokens and more) lies past TINY's window of 16 on five of six layers
    assert 100 / 6 < got["decode_kv_window_read_pct"] < 25
    assert 0 < got["decode_experts_touched_pct"] <= 100
    assert got["serve_compiles_in_window"] == 0 and got["prefix_hit_page_share_pct"] > 50
    assert not [name for name in got if "roofline" in name or "idle" in name]  # no device number from a CPU
    assert set(rehearse_one(spec.ROOT, CELL, 0)["metrics"]) == {"serve_tok_s", "setup_s"}


@pytest.mark.parametrize("wrong", ["no_window", "top7"])
def test_a_wrong_reference_is_not_correct(tmp_path, wrong):
    root = str(tmp_path)
    before = copy_of_the_benchmark(root)
    with open(f"{root}/BENCHMARK.json", "w") as f:
        f.write(open(f"{spec.ROOT}/BENCHMARK.json").read())
    cells = wrong_models.add_cells(root, CELL, [wrong])
    line, facts = rehearse_one(root, cells[wrong], 0, facts=True)
    assert line["correct"] is False and facts["checks"]["served_tokens_within_reference_margin"] is False, facts
    assert facts["checks"]["no_request_failed"] and facts["arch_file"] == f"benchmarks/archs/afmoe_{wrong}.py"
    for path, content in before.items():
        assert open(path, "rb").read() == content, f"{path} was edited"


class RecordedOps:
    """A trace's ops as `lib/trace.Trace` hands them to a reader: the HLO text
    an event is named by (operands with their shapes, as a v5e trace shows
    them) and its seconds. The texts are the decode and prefill steps' expert
    products as compiled for v5e at published widths (`rehearse.py aot`)."""

    STACK_UP, STACK_DOWN = "bf16[4,128,2048,1024]{3,2,1,0:T(8,128)(2,1)}", "bf16[4,128,1024,2048]{3,2,1,0:T(8,128)(2,1)}"
    OPS = [
        (f"%fusion.418 = bf16[128,64,1024]{{2,1,0:T(8,128)(2,1)S(1)}} fusion({STACK_UP} %get-tuple-element.1267, s32[] %select_n.285, bf16[64,2048]{{1,0}} %fusion.415), kind=kOutput", 0.8e-3),
        (f"%fusion.419 = bf16[64,2048]{{1,0:T(8,128)(2,1)S(1)}} fusion({STACK_DOWN} %get-tuple-element.1268, s32[] %select_n.285, f32[64,128]{{1,0}} %copy.85, bf16[128,64,1024]{{2,1,0}} %fusion.418, {STACK_UP} %get-tuple-element.1266, bf16[64,2048]{{1,0}} %fusion.415), kind=kOutput", 1.6e-3),
        (f"%fusion.77 = bf16[128,256,1024]{{2,1,0}} fusion({STACK_UP} %get-tuple-element.9, s32[] %select_n.2, bf16[256,2048]{{1,0}} %fusion.70), kind=kOutput", 3e-3),  # a prefill chunk's
        (f"%while.5 = (s32[], bf16[64,1,2048], {STACK_UP}, {STACK_UP}, {STACK_DOWN}) while(%tuple.104), condition=%cond, body=%body", 9e-3),
        ("%fusion.12 = bf16[64,2048]{1,0} fusion(bf16[64,1,2048]{2,0,1} %x), kind=kLoop", 0.2e-3),
    ]

    def op_calls(self, pattern):
        import re

        return [(hlo, s) for hlo, s in self.OPS if re.search(pattern, hlo)]

    def busy_s(self):
        return 6e-3


def test_the_expert_products_of_a_decode_step_are_told_by_their_operands():
    """Gate, and up + down fused with the combine: three matrices of each
    expert a layer, by the stack operands the two ops name. A prefill chunk's
    product, the loop that carries the stacks and an op of the same result
    shape that reads no stack are left out."""
    from benchmarks.readers import trace_expert_products as reader

    cell = spec.find_cell(CELL)
    held, touched = 4 * 128, 4 * 80.0
    engine = lambda steps: {"clocks": {"decode_experts": {"touched": touched * steps, "held": held * steps, "steps": steps}}}
    evidence = {"_trace": RecordedOps(), "marks": [{"engine": engine(0)}, {"engine": engine(10)}],
                "worker": {"device": {"platform": "tpu", "kind": "TPU v5 lite"}}}
    read = lambda stat: reader.read(evidence, {"stat": stat, "cell": cell})
    assert read("time_share_pct") == pytest.approx(100 * 2.4e-3 / 6e-3)
    matrices, x = 128 * 2048 * 1024 * 2, 64 * 2048 * 2
    assert read("streamed_roofline") == pytest.approx(100 * (3 * matrices + 2 * x) / 819e9 / 2.4e-3)
    assert read("needed_roofline") == pytest.approx(100 * (3 * matrices * 80 / 128 + 2 * x) / 819e9 / 2.4e-3)
    assert read("needed_roofline") < read("streamed_roofline") < 100
    assert reader.read(dict(evidence, marks=[]), {"stat": "time_share_pct", "cell": cell}) is None  # a program without the counter
    for name in ("moe_decode_matmul_roofline", "moe_decode_matmul_streamed_roofline", "moe_decode_matmul_time_share_pct"):
        assert spec.load_json(f"{spec.BENCH_DIR}/metrics/{name}.json")["reader"] == "trace_expert_products"
