"""Percentile, TPOT and rate arithmetic on hand-made event lists."""

import pytest

from benchmarks.metrics import Sample, end_to_end, percentile, spread


def test_percentile_interpolates():
    assert percentile([], 50) is None
    assert percentile([7.0], 95) == 7.0
    assert percentile([1, 2, 3, 4, 5], 50) == 3
    assert percentile([4, 1, 3, 2], 50) == 2.5
    assert percentile(list(range(101)), 95) == 95


def sample(due, times, counts, budget=None, **kw):
    s = Sample(due=due, sent=due, budget=budget if budget is not None else sum(counts), **kw)
    s.events = list(zip(times, counts))
    s.done = times[-1] + 0.001
    return s


def test_ttft_and_tpot_of_one_request():
    s = sample(10.0, [10.2, 10.5, 10.8, 11.1], [1, 4, 4, 4])
    assert s.ttft_ms == pytest.approx(200.0)
    # (last event - first event) / (tokens - 1) = 0.9 s / 12
    assert s.tpot_ms == pytest.approx(75.0)
    assert s.ok
    assert sample(0.0, [0.1], [1]).tpot_ms is None  # one token has no gap


def test_short_or_failed_requests_are_not_ok():
    assert not sample(0.0, [0.1, 0.2], [1, 3], budget=8).ok  # fewer than its budget
    s = sample(0.0, [0.1, 0.2], [1, 3])
    s.error = "ClientTimeoutError"
    assert not s.ok
    t = sample(0.0, [0.1, 0.2], [1, 3])
    t.text_ok = False
    assert not t.ok
    u = sample(0.0, [0.1, 0.2], [1, 3], realised_output_tokens=5)
    assert not u.ok


def test_end_to_end_over_a_window():
    t0, seconds = 100.0, 10.0
    samples = [
        sample(100.0 + i, [100.3 + i, 100.7 + i, 101.1 + i], [1, 4, 4]) for i in range(9)
    ]
    # the last request's final event falls outside the window: its tokens
    # do not count toward the rate, its latencies still do
    late = sample(109.5, [109.8, 110.4], [1, 4])
    out = end_to_end(samples + [late], t0, seconds, chips=1, setup_s=42.0)
    assert out["setup_s"] == 42.0
    assert out["ttft_p50_ms"] == pytest.approx(300.0)
    assert out["tpot_p95_ms"] == pytest.approx(127.5)  # 100 x 9 and one 150
    assert out["out_tok_s_per_chip"] == pytest.approx((9 * 9 + 1) / 10.0)
    four = end_to_end(samples, t0, seconds, chips=4, setup_s=1.0)
    assert four["out_tok_s_per_chip"] == pytest.approx(81 / 10.0 / 4)
    assert "ttft_p50_ms" not in end_to_end([], t0, seconds, 1, 1.0)
    # a request begun in the ramp-in: its tokens inside the window count
    # toward the rate, its latencies toward nothing
    ramp = sample(95.0, [96.0, 101.0, 102.0], [1, 4, 4])
    both = end_to_end(samples, t0, seconds, 1, 1.0, everything=samples + [ramp])
    assert both["out_tok_s_per_chip"] == pytest.approx((81 + 8) / 10.0)
    assert both["ttft_p50_ms"] == pytest.approx(300.0)


def test_spread_is_the_contracts():
    # statistics.quantiles(n=4) on six values: exclusive method
    values = [100, 101, 102, 103, 104, 110]
    assert spread(values) == pytest.approx((105.5 - 100.75) / 102.5)


def test_tails_keep_requests_that_failed_or_were_cut():
    t0 = 0.0
    fine = [sample(float(i), [i + 0.1, i + 0.5, i + 0.9], [1, 4, 4]) for i in range(9)]
    cut = sample(9.0, [12.0, 14.0], [1, 4], budget=64)  # the slowest, never finished
    cut.done, cut.error = None, "not finished when the drain ended"
    assert not cut.ok
    out = end_to_end(fine + [cut], t0, 20.0, 1, 1.0)
    assert out["tpot_p95_ms"] > 100.0 + 1e-6  # 2 s / 4 tokens of the cut one pull the tail
    assert out["ttft_p95_ms"] > 1000.0
