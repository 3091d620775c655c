"""The traffic generator: deterministic per seed, the same work for every
seed, and the burst schedule's rates."""
import math
from collections import Counter

import pytest

from bench import traffic
from tiny import TINY_TRAFFIC

MIX = dict(TINY_TRAFFIC, rate_per_s=40.0, lead_s=2, tail_s=5,
           bursts={"period_s": 10, "length_s": 2, "first_s": 5, "high": 2.5, "low": 0.625})


def test_same_seed_same_schedule():
    assert traffic.schedule(MIX, 7, 30.0) == traffic.schedule(MIX, 7, 30.0)


def test_large_seed_accepted():
    assert traffic.schedule(MIX, 2**33 + 5, 10.0)


@pytest.mark.parametrize("other", [8, 2**31 + 3])
def test_other_seed_reorders_the_same_work(other):
    a, b = traffic.schedule(MIX, 7, 30.0), traffic.schedule(MIX, other, 30.0)
    assert a != b
    slots = lambda s: Counter(math.floor(t) for t, _, _ in s)
    assert slots(a) == slots(b)
    assert Counter(w for _, w, _ in a) == Counter(w for _, w, _ in b)
    # the window holds the same requests (identity and workflow)
    win = lambda s: sorted((i, w) for t, w, i in s if 0 <= t < 30.0)
    assert win(a) == win(b)


def test_rate_at_follows_the_burst_schedule():
    r = MIX["rate_per_s"]
    assert traffic.rate_at(MIX, -1.0) == r
    assert traffic.rate_at(MIX, 4.9) == r * 0.625
    assert traffic.rate_at(MIX, 5.0) == r * 2.5
    assert traffic.rate_at(MIX, 6.99) == r * 2.5
    assert traffic.rate_at(MIX, 7.0) == r * 0.625
    assert traffic.rate_at(MIX, 15.5) == r * 2.5
    # the schedule's mean over a period is the stated rate
    mean = sum(traffic.rate_at(MIX, 5 + i / 10) for i in range(100)) / 100
    assert mean == pytest.approx(r)


def test_burst_slots_get_their_rate():
    s = traffic.schedule(dict(MIX, rate_per_s=400.0), 3, 40.0)
    hi = sum(1 for t, _, _ in s if 0 <= t < 40 and traffic.rate_at(MIX, t) > 40)
    lo = sum(1 for t, _, _ in s if 0 <= t < 40 and 0 < traffic.rate_at(MIX, t) < 40)
    # 4 bursts of 2 s at 1000 req/s, 32 s at 250 req/s: Poisson counts
    # within 5% of their means
    assert hi == pytest.approx(8 * 1000, rel=0.05)
    assert lo == pytest.approx(32 * 250, rel=0.05)


def test_window_bounds_and_order():
    s = traffic.schedule(MIX, 1, 10.0)
    times = [t for t, _, _ in s]
    assert times == sorted(times)
    assert min(times) >= -MIX["lead_s"] and max(times) < 10.0 + MIX["tail_s"]


def test_profile_keys_lengths_by_identity():
    idents = [5, 3, 9]
    p = traffic.profile(MIX, idents)
    q = traffic.profile(MIX, list(range(10)))
    for rid, ident in enumerate(idents):
        assert p.prompt_tokens(rid, 1) == q.prompt_tokens(ident, 1)
        assert p.gen_tokens(rid, 1, 256) == q.gen_tokens(ident, 1, 256)
        assert p.iterations(rid) == q.iterations(ident)
