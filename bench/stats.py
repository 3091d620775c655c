"""Client-side arithmetic: percentiles, goodput, spread.

A request that failed, was shed or had not finished when it was last
followed misses its limit and ranks as slowest: it is given the largest
time any request of the window was observed for.
"""
from __future__ import annotations

import math
import statistics


def percentile(values: list, q: float) -> float:
    """Nearest-rank ``q``-th percentile (0 < q <= 100) of ``values``."""
    if not values:
        raise ValueError("percentile of no values")
    s = sorted(values)
    return float(s[max(0, math.ceil(q / 100.0 * len(s)) - 1)])


def client_latencies(reqs: list) -> list:
    """Latency (s) per request, misses ranked slowest.  Each request is a
    dict with ``due``, ``done`` (None if not finished) and ``followed_to``."""
    seen = [(r["done"] if r["done"] is not None else r["followed_to"]) - r["due"]
            for r in reqs]
    slowest = max(seen, default=0.0)
    return [r["done"] - r["due"] if r["done"] is not None else slowest
            for r in reqs]


def goodput(reqs: list, seconds: float) -> float:
    met = sum(1 for r in reqs
              if r["done"] is not None and r["done"] - r["due"] <= r["limit"])
    return met / seconds


def spread(values: list) -> float:
    """Interquartile range over the median (``statistics.quantiles``)."""
    q1, q2, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / q2


def emitted(spans, t0: float, t1: float) -> int:
    """Output tokens the engine emitted in [t0, t1): the first token of
    each fresh prefill and one token per live sequence of each decode
    step, by the generation adapter's spans that started in the window."""
    return sum(info["emitted"] for name in ("prefill", "decode")
               for *_, info in spans.within(name, t0, t1))
