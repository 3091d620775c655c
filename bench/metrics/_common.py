"""Shared by the metric readers: spans of the window and trace programs."""
from __future__ import annotations


def window_spans(ctx: dict, name: str) -> list:
    t0, t1 = ctx["window"]
    return ctx["spans"].within(name, t0, t1)


def traced(ctx: dict):
    """The reduced trace, or None when the run took none."""
    return ctx.get("trace")


def traced_spans(ctx: dict, name: str) -> list:
    """Spans of ``name`` inside the traced slice of the window."""
    if ctx.get("trace_window") is None:
        return []
    t0, t1 = ctx["trace_window"]
    return ctx["spans"].within(name, t0, t1)
