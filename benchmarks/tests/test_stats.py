"""Percentile, pooled-gap and serve_tok_s arithmetic on hand-made timelines,
among them PR 22's failure: one 8 s stall in a 40 s window."""

import random
import statistics

from benchmarks.lib import stats

W0, W1 = 100.0, 140.0


def timeline(stall_at=None, stall_s=8.0, seed=0):
    """~1 request/s for 40 s, TTFT ~0.2 s, 64 tokens at ~0.11 s (every tenth
    gap doubled by a prefill). A stall freezes everything: tokens that would
    have arrived during it arrive at its end."""
    rng = random.Random(seed)
    out, due = [], W0 - 5.0
    while due < W1 + 5:
        due += rng.expovariate(1.0)
        t = due + 0.2 + rng.uniform(0, 0.01)
        times = []
        for i in range(64):
            times.append(t)
            t += 0.11 * (2 if rng.random() < 0.11 else 1) + rng.uniform(0, 0.002)
        if stall_at is not None:
            end = stall_at + stall_s
            times = [end + 0.001 * k if stall_at <= x < end else (x if x < stall_at else x + 0.0) for k, x in enumerate(times)]
            times = sorted(times)
        out.append({"due": due, "sent": due + 0.001, "token_times": times, "prompt_tokens": 800,
                    "counted": W0 <= due < W1, "error": None})
    return out


def test_percentile_matches_hand_values():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile([1, 2, 3, 4], 50) == 2.5
    assert stats.percentile([10], 95) == 10
    assert stats.percentile([], 50) is None
    assert abs(stats.percentile(list(range(101)), 95) - 95) < 1e-9


def test_pooled_gaps_ttft_lateness_and_rate_on_a_two_request_timeline():
    tl = [
        {"due": 100.0, "sent": 100.002, "token_times": [100.3, 100.4, 100.6], "prompt_tokens": 10, "counted": True, "error": None},
        {"due": 99.0, "sent": 99.0, "token_times": [99.5, 100.1, 139.9, 140.2], "prompt_tokens": 7, "counted": False, "error": None},
        {"due": 139.5, "sent": 139.5, "token_times": [], "prompt_tokens": 5, "counted": True, "error": "shed"},
    ]
    assert [round(x) for x in stats.ttfts_ms(tl)] == [300]
    assert sorted(round(g) for g in stats.gaps_ms(tl, W0, W1)) == [100, 200, 600, 39800]
    assert [round(x, 3) for x in stats.lateness_ms(tl)] == [2.0, 0.0]
    # prompt tokens only where the FIRST token is in the window: 10 (not 7); output tokens in window: 3 + 2
    assert stats.serve_tokens(tl, W0, W1) == 10 + 3 + 2
    assert stats.attempted_failed(tl) == (2, 1)


def test_stalled_share():
    gaps = [100.0] * 90 + [260.0] * 10
    assert stats.stalled_share_pct(gaps) == 10.0
    assert stats.stalled_share_pct([]) is None


def test_one_8s_stall_in_40s_moves_the_judged_metrics_less_than_the_clean_iqr_and_shows_in_p90():
    def metrics(tl):
        ttft, gaps = stats.ttfts_ms(tl), stats.gaps_ms(tl, W0, W1)
        return stats.percentile(ttft, 50), stats.percentile(gaps, 95), stats.percentile(ttft, 90), stats.percentile(ttft, 75)

    clean_tl = timeline(seed=3)
    q_ttft = statistics.quantiles(stats.ttfts_ms(clean_tl), n=4)
    q_gap = statistics.quantiles(stats.gaps_ms(clean_tl, W0, W1), n=4)
    base = metrics(clean_tl)
    stalled = metrics(timeline(stall_at=118.0, seed=3))
    assert abs(stalled[0] - base[0]) < q_ttft[2] - q_ttft[0]
    assert abs(stalled[1] - base[1]) < q_gap[2] - q_gap[0]
    assert stalled[2] > base[2] + 1000  # the tail metric that decides nothing does see it


def test_spread_is_the_contracts():
    vals = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    q1, _, q3 = statistics.quantiles(vals, n=4)
    assert stats.iqr_share(vals) == (q3 - q1) / statistics.median(vals)
