"""The trace -> metrics reduction on the small trace recorded on a v5e chip
(benchmarks/tools/record_trace.py): 3 train steps, 1 prefill and 4 decode
steps of a 2-layer model with head_dim 128, with the benchmark's spans."""

import os

import pytest

from benchmarks.lib import spec, trace as tr

PATH = os.path.join(spec.BENCH_DIR, "recorded", "tiny_v5e.xplane.pb.gz")
FLASH = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def t():
    return tr.Trace(PATH)


def test_interval_arithmetic():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 7), (9, 9)]) == [(0, 3), (5, 7)]
    assert tr.measure([(0, 3), (5, 7)]) == 5
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]
    assert tr.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]


def test_display_name():
    hlo = "%convert.48 = f32[4096,16,32,128]{3,2,1,0:T(8,128)} convert(bf16[4096,16,32,128]{3,2,1,0} %x)"
    assert tr.display_name(hlo) == "convert.48_f32_4096_16_32_128_"
    assert tr.display_name("%copy-done.3") == "copy-done.3"


def test_spans_and_their_arguments(t):
    names = [s["name"] for s in t.spans]
    assert names == ["bench.train_step"] * 3 + ["bench.prefill"] + ["bench.decode"] * 4
    assert t.spans[3]["args"] == {"prompt_tokens": 100, "bucket_tokens": 128, "cached_tokens": 0}
    assert [s["args"]["kv_tokens"] for s in t.spans[4:]] == [101, 102, 103, 104]


def test_window_busy_and_idle(t):
    assert t.chips == ["/device:TPU:0"]
    assert 0.012 < t.window_s() < 0.018  # first span start to last span end: ~15 ms
    assert 0 < t.busy_s() < t.window_s()
    # 3 train steps of ~0.24 ms, a 0.06 ms prefill, 4 decode steps of 0.02 ms: a tiny model leaves the chip idle
    assert 0.0005 < t.busy_s() < 0.0012
    assert 0.9 < t.idle_share() < 0.97


def test_flash_kernels_are_found_and_told_apart(t):
    import re

    kinds = spec.load_json(os.path.join(spec.BENCH_DIR, "metrics", "flash_attn_roofline.json"))["args"]["kinds"]
    calls = t.op_calls(FLASH)
    found = [next(k for k, rx in kinds.items() if re.search(rx, hlo)) for hlo, _s in calls]
    # 3 steps x 2 layers x (fwd, dq, dkv), and the prefill's 2 fwd calls
    assert 0.0007 < t.skew_s < 0.0009  # device clock ~0.78 ms ahead of the host's in this trace, taken out
    assert found.count("fwd") - 2 == found.count("dq") == found.count("dkv") == 6
    assert all(1e-6 < s < 2e-5 for _h, s in calls)
    assert 0 < t.op_seconds(FLASH) < t.busy_s()


def test_decode_spans_are_one_a_step_with_the_steps_sizes(t):
    """The contract of a `bench.decode` span (benchmarks/README.md): one executed decode step with that step's
    `live` and `kv_tokens`; nothing says when the host waits, so no reader clips device time to it any more (PR 45).
    The device's own line says how long a step ran: readers/trace_modules.py."""
    decodes = [s for s in t.spans if s["name"] == "bench.decode"]
    assert len(decodes) == 4 and all(s["args"]["live"] == 1 and s["args"]["kv_tokens"] > 0 for s in decodes)


def test_breakdown(t):
    top = t.top_ops(10)
    assert len(top) == 10 and all(isinstance(n, str) and s > 0 for n, s in top)
    assert top == sorted(top, key=lambda x: -x[1]) and not any(n.startswith("while") for n, _s in top)
    gaps = t.idle_gaps_by_span(10)
    assert {n for n, _s in gaps} <= {"bench.train_step", "bench.prefill", "bench.decode", tr.UNATTRIBUTED}
    assert abs(sum(s for _n, s in gaps) - (t.window_s() - t.busy_s())) < 1e-6


def test_no_collective_on_one_chip(t):
    assert t.exposed_collective_share(r"(all-gather|reduce-scatter|all-reduce)") == 0.0
