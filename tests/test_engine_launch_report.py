"""tools/engine_launch_report.py on the recorded engine trace (benchmarks/recorded,
taken on the chip with PR 40's engine: deliveries in front of the dispatch)."""

import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
RECORDED = os.path.join(ROOT, "benchmarks", "recorded", "tiny_v5e_engine.xplane.pb.gz")


@pytest.fixture(scope="module")
def report():
    from tools.engine_launch_report import report

    return report(RECORDED)


def test_decode_steps_and_prefills_are_split_by_what_ran_before_them(report):
    # the recording: one request alone, then three at once (benchmarks/tools/record_engine_trace.py)
    assert {k: v["n"] for k, v in report["decode"].items()} == {"after_decode": 4, "after_prefill": 3}
    assert {k: v["n"] for k, v in report["prefill"].items()} == {"after_decode": 3, "after_prefill": 1}
    assert sum(v["n"] for v in report["decode"].values()) == report["spans"]["llm.decode"]["n"]
    for group in list(report["decode"].values()) + list(report["prefill"].values()):
        # no execution starts before its dispatch: the device's clock is shifted by the least amount that says so
        assert 0.0 <= group["launch_ms"]["p50"] <= group["launch_ms"]["p90"] < 5.0
        assert group["dispatch_ms"]["p50"] > 0.0 and group["prep_ms"]["p50"] > 0.0


def test_a_recording_from_before_pr_43_has_no_delivery_under_a_step(report):
    assert report["emit"]["n"] == report["spans"]["llm.emit"]["n"] == 7
    assert report["emit"]["under_step_n"] == 0
    assert all(g["emit_under_ms"]["p90"] == 0.0 for g in report["decode"].values())
    assert "llm.decide" not in report["spans"]
