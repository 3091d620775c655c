"""Share of the window the scheduler thread spent in scheduling code: time
inside ``Server.step`` less the backend calls made from it, over the
window's seconds."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import window_spans  # noqa: E402


def read(ctx):
    t0, t1 = ctx["window"]
    steps = window_spans(ctx, "sched_step")
    if not steps:
        return None
    inner = sum(b - a for n in ("gen", "search", "stage")
                for _, a, b, _ in window_spans(ctx, n))
    busy = sum(b - a for _, a, b, _ in steps)
    return 100.0 * max(busy - inner, 0.0) / (t1 - t0)
