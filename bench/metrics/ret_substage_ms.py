"""Mean wall time of one retrieval sub-stage (``RealBackend.search_charged``)
in the window."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import window_spans  # noqa: E402


def read(ctx):
    s = window_spans(ctx, "search")
    if not s:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in s) / len(s)
