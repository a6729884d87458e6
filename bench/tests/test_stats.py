"""Every percentile and rate comes from ``harness.window_metrics``."""

import dataclasses

from bench.harness import window_metrics


@dataclasses.dataclass
class R:
    due: float
    token_times: list


def _steady(n_req: int, seconds: float, gap: float, stall_at=None, stall=0.0):
    recs = []
    for i in range(n_req):
        t, ts = 0.01 * i, []
        while t <= seconds:
            ts.append(t)
            t += gap
            if stall_at is not None and ts[-1] < stall_at <= t:
                t += stall
        recs.append(R(0.0, ts))
    return recs


def test_steady_rate_and_gaps():
    m = window_metrics(_steady(4, 10.0, 0.02), 10.0)
    assert abs(m["output_tok_s"] - 4 / 0.02) < 5
    assert abs(m["itl_p95_ms"] - 20.0) < 1e-6


def test_a_stall_moves_the_tail_when_it_is_5pct_of_gaps_and_always_the_rate():
    base = window_metrics(_steady(4, 10.0, 0.5), 10.0)
    hit = window_metrics(_steady(4, 10.0, 0.5, stall_at=5.0, stall=2.0), 10.0)
    assert hit["itl_samples"] and 4 / hit["itl_samples"] >= 0.05
    assert hit["itl_p95_ms"] > base["itl_p95_ms"] + 1000
    assert hit["output_tok_s"] < base["output_tok_s"]
    # rare stall: the tail may hold, the rate still moves
    base = window_metrics(_steady(1, 10.0, 0.001), 10.0)
    hit = window_metrics(_steady(1, 10.0, 0.001, stall_at=5.0, stall=2.0), 10.0)
    assert hit["itl_p95_ms"] < 2000
    assert hit["output_tok_s"] < 0.85 * base["output_tok_s"]


def test_ttft_counts_unserved_requests_with_their_wait():
    recs = [R(1.0, [1.5, 1.6]), R(2.0, []), R(9.0, [])]
    m = window_metrics(recs, 10.0)
    assert m["ttft_samples"] == 3
    assert abs(m["ttft_p50_ms"] - 1000.0) < 1e-6     # samples 0.5, 8.0 and 1.0 s
    recs_late = [R(11.0, [])]
    assert window_metrics(recs_late, 10.0)["ttft_samples"] == 0


def test_the_window_opens_after_the_preroll():
    # tokens at 0.5 s steps from 0; window (3, 8]: tokens 3.5..8.0, and the
    # gap 3.0 -> 3.5 whose later token is inside; a request due before the
    # window gives no TTFT sample
    recs = [R(0.0, [0.5 * i for i in range(40)]), R(4.0, [5.0, 5.5])]
    m = window_metrics(recs, 5.0, start=3.0)
    assert m["tokens"] == 10 + 2
    assert m["itl_samples"] == 10 + 1
    assert m["ttft_samples"] == 1 and abs(m["ttft_p50_ms"] - 1000.0) < 1e-6
