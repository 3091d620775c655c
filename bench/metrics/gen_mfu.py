"""Model FLOPs of the prefills and decode steps in the traced slice, for
their real tokens at their real context (counted by the configuration's
model family), over the device time of the prefill and decode programs in
the trace, over the chip's bf16 peak."""
import os
import sys

sys.path.insert(0, os.path.dirname(__file__))
from _common import traced, traced_spans  # noqa: E402

from bench import peaks, trace  # noqa: E402


def read(ctx):
    red = traced(ctx)
    if red is None:
        return None
    m, fam = ctx["model"], ctx["family"]
    fl = sum(fam.prefill_flops(m, i["tokens"]) for *_, i in traced_spans(ctx, "prefill"))
    # a decode step's live sequences each attend to their own context:
    # the per-token cost is affine in the context, so the sum needs only
    # the number of live sequences and their total context
    for *_, i in traced_spans(ctx, "decode"):
        fl += (i["live"] * fam.decode_flops(m, 0)
               + (fam.decode_flops(m, 1) - fam.decode_flops(m, 0)) * i["ctx"])
    secs = trace.module_seconds(red, "prefill") + trace.module_seconds(red, "decode")
    if fl == 0 or secs <= 0:
        return None
    return 100.0 * fl / secs / peaks.peaks_for(ctx["device_kind"])["bf16_flops"]
