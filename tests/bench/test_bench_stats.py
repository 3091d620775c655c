"""Percentiles and goodput on the client side: failed and unfinished
requests miss their limit and rank as slowest."""
import pytest

from bench import stats


def test_percentile_nearest_rank():
    v = list(range(1, 101))
    assert stats.percentile(v, 50) == 50
    assert stats.percentile(v, 90) == 90
    assert stats.percentile(v, 100) == 100
    assert stats.percentile([3.0], 90) == 3.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _req(due, done, limit=1.0, followed_to=100.0):
    return {"due": due, "done": done, "limit": limit, "followed_to": followed_to}


def test_unfinished_rank_slowest():
    reqs = [_req(0, 0.5), _req(0, 0.7), _req(1, None, followed_to=3.0), _req(0, 2.0)]
    lat = stats.client_latencies(reqs)
    # the unfinished one was followed for 2.0 s, the slowest finished took
    # 2.0 s: it ties the slowest and nothing ranks above it
    assert lat == [0.5, 0.7, 2.0, 2.0]
    assert stats.percentile(lat, 90) == 2.0


def test_unfinished_outranks_every_finished():
    reqs = [_req(0, 5.0), _req(4.5, None, followed_to=5.0)]
    lat = stats.client_latencies(reqs)
    assert lat[1] >= max(lat)


def test_goodput_counts_met_limits_only():
    reqs = [_req(0, 0.5), _req(0, 1.5), _req(0, None), _req(0, 1.0)]
    assert stats.goodput(reqs, 2.0) == 1.0  # two met, over two seconds


def test_spread_is_iqr_over_median():
    v = [10.0, 11.0, 12.0, 13.0, 14.0, 15.0]
    import statistics
    q1, q2, q3 = statistics.quantiles(v, n=4)
    assert stats.spread(v) == pytest.approx((q3 - q1) / q2)


def test_emitted_counts_the_window_only():
    from bench.spans import Spans

    sp = Spans()
    sp.rows += [("prefill", 0.5, 0.6, {"tokens": 40, "emitted": 1}),
                ("decode", 0.9, 1.0, {"emitted": 3}),
                ("decode", 1.0, 1.1, {"emitted": 4}),
                ("prefill", 1.5, 1.6, {"tokens": 30, "emitted": 0}),
                ("decode", 2.0, 2.1, {"emitted": 5}),
                ("search", 1.2, 1.3, {})]
    assert stats.emitted(sp, 1.0, 2.0) == 4
    assert stats.emitted(sp, 0.0, 3.0) == 13
