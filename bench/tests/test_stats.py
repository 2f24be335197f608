"""Arithmetic of the serving metrics and of the schedule."""
import math

import numpy as np
import pytest

from bench import stats, traffic
from bench.stats import RequestLog


def test_percentile_nearest_rank():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 90) == 90
    assert stats.percentile(vals, 95) == 95
    assert stats.percentile([5.0], 90) == 5.0
    assert stats.percentile([3, 1, 2, 4], 50) == 2
    with pytest.raises(ValueError):
        stats.percentile([], 90)


def test_ttft_counts_misses_and_failures_at_the_drain_end():
    logs = [RequestLog(due=0.0, token_times=[0.2, 0.3]),
            RequestLog(due=1.0, token_times=[1.5]),
            RequestLog(due=2.0),  # never answered
            RequestLog(due=3.0, token_times=[3.1], failed=True),
            RequestLog(due=11.0, token_times=[11.1])]  # due after the window
    got = stats.ttfts(logs, window_s=10.0, drain_end=12.0)
    assert got == pytest.approx([0.2, 0.5, 10.0, 9.0])


def test_gaps_close_inside_the_window():
    logs = [RequestLog(due=0.0, token_times=[1.0, 1.0, 1.5, 9.9, 10.2])]
    assert stats.token_gaps(logs, 10.0) == pytest.approx([0.0, 0.5, 8.4])


def test_rate_is_over_the_whole_window():
    logs = [RequestLog(due=0.0, token_times=[0.1, 0.2, 0.3]),
            RequestLog(due=5.0, token_times=[9.9, 10.5])]
    m = stats.serving_metrics(logs, window_s=10.0, drain_end=11.0)
    assert m["output_tok_s"] == pytest.approx(4 / 10.0)
    assert m["ttft_p90_ms"] == pytest.approx(4900.0)
    assert m["itl_p99_ms"] == pytest.approx(100.0)


def test_seeds_order_the_same_sizes_and_gaps():
    mix = {"rate_per_s": 3.0,
           "prompt": {"dist": "lognormal", "median": 768, "sigma": 0.5,
                      "min": 512, "max": 2048},
           "output": {"dist": "uniform", "min": 16, "max": 64}}
    a = traffic.serving_schedule(mix, 1, 40.0, 1000)
    b = traffic.serving_schedule(mix, 2**40 + 7, 40.0, 1000)
    assert len(a) == len(b) == 120
    for f in (lambda r: len(r.prompt), lambda r: r.max_new_tokens):
        assert sorted(f(r) for r in a) == sorted(f(r) for r in b)
        assert [f(r) for r in a] != [f(r) for r in b]
    gaps = [np.diff([r.due for r in s]) for s in (a, b)]
    assert not np.allclose(gaps[0], gaps[1])
    assert not np.array_equal(a[0].prompt, b[0].prompt)
    due = [r.due for r in a]
    assert due == sorted(due) and due[0] == 0.0 and due[-1] < 40.0
    lens = [len(r.prompt) for r in a]
    assert lens != sorted(lens)  # shuffled, not in quantile order
    again = traffic.serving_schedule(mix, 1, 40.0, 1000)
    assert [r.due for r in again] == due
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, again))


def test_quantile_sizes():
    ln = {"dist": "lognormal", "median": 128, "sigma": 0.8, "min": 16,
          "max": 1024}
    s = traffic.quantile_sizes(ln, 1001)
    assert s.min() >= 16 and s.max() <= 1024
    assert abs(np.median(s) - 128) < 8
    u = traffic.quantile_sizes({"dist": "uniform", "min": 10, "max": 19}, 10)
    assert list(u) == list(range(10, 20))
    assert math.isclose(float(np.mean(traffic.exp_gaps(2.0, 10000))), 0.5,
                        rel_tol=0.01)
