"""Wall milliseconds of the engine's prefills in the window per thousand
prompt tokens they kept."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import window_spans  # noqa: E402


def read(ctx):
    s = window_spans(ctx, "prefill")
    toks = sum(i["tokens"] for _, _, _, i in s)
    if not toks:
        return None
    return 1e3 * sum(b - a for _, a, b, _ in s) / (toks / 1e3)
