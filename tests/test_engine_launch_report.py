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


# ------------------------------------------- a hand-made trace, two steps in flight (PR 46)

MS = 1e-3


def _span(name, a, b, **args):
    return {"name": name, "start": a * MS, "end": b * MS, "args": args}


def _two_in_flight():
    """One engine thread (ms): a prefill, then three decode steps of 8 ms that the device runs back to back from
    13.5 on. Step k+1 is launched before step k is read: the `llm.decode.wait` inside step k+1's `llm.decode`
    carries step k, then its decide and its emit (0.5 ms, under the step in flight); the last step is read under
    `llm.step`, with nothing left to launch. The device's clock runs 1.5 ms late against the host's."""
    spans = [
        _span("llm.step", 0, 40, admitted=1, live=1),
        _span("llm.prefill", 0, 10, rid=1), _span("llm.prefill.prep", 0, 1), _span("llm.prefill.dispatch", 1, 2), _span("llm.prefill.wait", 2, 10),
        _span("llm.decode", 11, 14, step=1, live=1, kv_tokens=8, after_prefill=1),
        _span("llm.decode.prep", 11, 12), _span("llm.decode.dispatch", 12, 13, step=1),
        _span("llm.decode", 15, 23.5, step=2, live=1, kv_tokens=9, after_prefill=0),
        _span("llm.decode.prep", 15, 16), _span("llm.decode.dispatch", 16, 17, step=2),
        _span("llm.decode.wait", 17, 22.5, step=1), _span("llm.decide", 22.5, 23, tokens=1), _span("llm.emit", 23, 23.5, tokens=1, under_step=1),
        _span("llm.decode", 24.5, 31.5, step=3, live=1, kv_tokens=10, after_prefill=0),
        _span("llm.decode.prep", 24.5, 25.5), _span("llm.decode.dispatch", 25.5, 26.5, step=3),
        _span("llm.decode.wait", 26.5, 30.5, step=2), _span("llm.decide", 30.5, 31, tokens=1), _span("llm.emit", 31, 31.5, tokens=1, under_step=1),
        _span("llm.decode.wait", 32, 38.5, step=3), _span("llm.decide", 38.5, 39, tokens=1), _span("llm.emit", 39, 39.5, tokens=1, under_step=0),
    ]
    late = 1.5
    mods = [{"name": "jit_llm_prefill_p8(1)", "start": (3 + late) * MS, "end": (9 + late) * MS}] + [
        {"name": "jit_llm_decode(2)", "start": (12 + 8 * k + late) * MS, "end": (20 + 8 * k + late) * MS} for k in range(3)]
    return sorted(spans, key=lambda s: s["start"]), mods


def test_two_steps_in_flight_are_joined_by_their_flights_not_their_spans():
    """A step's dispatch and wait are the ones that carry its ordinal, and its limits for the join are its flight:
    the host does not wait inside the span that launched the step."""
    from tools.engine_launch_report import summarize

    out = summarize(*_two_in_flight())
    assert {k: v["n"] for k, v in out["decode"].items()} == {"after_prefill": 1, "after_decode": 2}
    assert {k: v["n"] for k, v in out["prefill"].items()} == {"after_decode": 1}
    first, later = out["decode"]["after_prefill"], out["decode"]["after_decode"]
    # the device's clock is set back by the least that puts every execution behind its dispatch: step 1's launch reads 0
    assert out["device_clock_shift_s"] == pytest.approx(-1.5 * MS)
    assert first["launch_ms"]["p50"] == pytest.approx(0.0) and first["emit_under_ms"]["p50"] == 0.0
    # steps 2 and 3 were dispatched 4 and 2.5 ms before the device was free for them, and each has the step before's delivery under it
    assert (later["launch_ms"]["p50"], later["launch_ms"]["p90"]) == (pytest.approx(3.25), pytest.approx(3.85))
    assert later["emit_under_ms"]["p50"] == later["emit_under_ms"]["p90"] == pytest.approx(0.5)
    assert later["dispatch_ms"]["p50"] == pytest.approx(1.0) and later["prep_ms"]["p50"] == pytest.approx(1.0)
    # the result's way back and the host's lateness: every step was read 2.5 ms after its execution ended
    assert first["result_ms"]["p50"] == pytest.approx(2.5) and (later["result_ms"]["p50"], later["result_ms"]["p90"]) == (pytest.approx(2.5), pytest.approx(2.5))
    assert out["emit"] == dict(out["emit"], n=3, under_step_n=2) and [e["inside"] for e in out["emit"]["at_once"]] == [["llm.step"]]


def test_without_the_flights_a_step_read_under_a_later_launch_has_no_wait_of_its_own(monkeypatch):
    """What the repair answers: judged by its `llm.decode` span alone, every step of the same trace lacks its wait."""
    from benchmarks.readers import trace_modules as tm
    from tools.engine_launch_report import summarize

    spans, mods = _two_in_flight()
    inside_its_span = {s["args"]["step"]: [w for w in spans if w["name"] == "llm.decode.wait" and s["start"] <= w["start"] < s["end"]]
                       for s in spans if s["name"] == "llm.decode"}
    assert [[w["args"]["step"] for w in ws] for _k, ws in sorted(inside_its_span.items())] == [[], [1], [2]]
    flights = tm.step_flights(spans, "llm.decode")
    assert flights == {1: (12 * MS, 22.5 * MS), 2: (16 * MS, 30.5 * MS), 3: (25.5 * MS, 38.5 * MS)}
    assert len(tm.join([s for s in spans if s["name"] == "llm.decode"], mods[1:], 0.0, flights)) == 3 == sum(v["n"] for v in summarize(spans, mods)["decode"].values())
