"""The device IVF scan's share of its roofline over the traced slice: for
each scan call made in the slice, the larger of its FLOPs over the bf16
peak and its HBM bytes over the HBM bandwidth (``bench/flops.py``:
the valid rows of each group's tile, queries and outputs), summed, over
the device time of the scan programs in the trace."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import traced  # noqa: E402

from bench import flops, peaks, trace  # noqa: E402


def read(ctx):
    red = traced(ctx)
    calls = ctx.get("scan_rows") or []
    if red is None or not calls:
        return None
    pk = peaks.peaks_for(ctx["device_kind"])
    ideal = 0.0
    for G, k, rows, (n_slots, _tile, dim), qb, itemsize in calls:
        fl, by = flops.scan_cost(G, k, rows, qb, dim, n_slots, itemsize)
        ideal += max(fl / pk["bf16_flops"], by / pk["hbm_bytes_per_s"])
    secs = trace.module_seconds(red, "ivf_scan")
    if secs <= 0:
        return None
    return 100.0 * ideal / secs
