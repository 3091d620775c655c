"""The one traffic generator: an open-loop arrival schedule from a mix file.

A mix file gives the workflow weights, the per-class latency limits, the
length profile the scheduler's ``WorkloadProfile`` takes, and the arrival
process: a mean rate and optionally a fixed burst schedule.  Time is cut
into one-second slots.  How many requests fall in each slot, the spacing
of arrivals inside it, and the requests themselves (workflow, lengths,
rounds) are drawn from the mix's own seed, so every run offers the same
work; the run's ``--seed`` only permutes spacings within each slot and the
order of the requests within the lead, the window and the tail.
"""
from __future__ import annotations

import math

import numpy as np

from bench import seeds


def rate_at(t: dict, s: float) -> float:
    """Offered rate (req/s) at window time ``s`` (may be negative: lead)."""
    rate = float(t["rate_per_s"])
    b = t.get("bursts")
    if not b or s < 0:
        return rate
    if s >= b["first_s"] and (s - b["first_s"]) % b["period_s"] < b["length_s"]:
        return rate * b["high"]
    return rate * b["low"]


def schedule(t: dict, seed: int, seconds: float) -> list[tuple[float, str, int]]:
    """(due time in window seconds, workflow, request identity) of every
    request, ordered by due time, from ``-lead_s`` to ``seconds + tail_s``.

    The identity keys a request's lengths and rounds (``profile``), so a
    request keeps its size whichever time it is sent at.  The run's seed
    reorders the requests within each phase (lead, window, tail), so every
    seed offers the window the same requests, in another order."""
    lead, tail = float(t.get("lead_s", 0)), float(t["tail_s"])
    base = np.random.default_rng(np.random.SeedSequence([int(t["seed"])]))
    times: list[float] = []
    for slot in range(-math.ceil(lead), math.ceil(seconds + tail)):
        n = int(base.poisson(rate_at(t, slot)))
        gaps = base.dirichlet(np.ones(n + 1)) if n else np.zeros(1)
        gaps = seeds.rng(seed, seeds.TRAFFIC, slot + (1 << 20)).permutation(gaps)
        times.extend(slot + np.cumsum(gaps[:n]))
    times = [x for x in times if -lead <= x < seconds + tail]
    names = sorted(t["workflows"])
    w = np.asarray([t["workflows"][k] for k in names], np.float64)
    picks = base.choice(len(names), size=len(times), p=w / w.sum())
    order = np.arange(len(times))
    for phase, (a, b) in enumerate(((-lead, 0.0), (0.0, seconds), (seconds, seconds + tail))):
        idx = np.flatnonzero([(a <= x < b) for x in times])
        order[idx] = idx[seeds.rng(seed, seeds.TRAFFIC, phase).permutation(len(idx))]
    return [(float(times[i]), names[int(picks[order[i]])], int(order[i]))
            for i in range(len(times))]


def profile(t: dict, identities: list):
    """The scheduler's ``WorkloadProfile`` for this mix, with its limits as
    per-class latency targets.  Request ``r`` (the ``r``-th sent) draws its
    lengths and rounds as identity ``identities[r]``."""
    from repro.serving.workload import WorkloadProfile

    class _Profile(WorkloadProfile):
        def _rng(self, request_id, node_id, tag):
            ident = identities[request_id] if 0 <= request_id < len(identities) else request_id
            return super()._rng(ident, node_id, tag)

    return _Profile(name=t["name"], **t["profile"],
                    slo_class_us={k: v * 1e6 for k, v in t["limits_s"].items()})
