"""Share of the traced slice in which no operation ran on the device."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import traced  # noqa: E402


def read(ctx):
    red = traced(ctx)
    if red is None or red["n_devices"] == 0 or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
