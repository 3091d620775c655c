"""The benchmark's own spans: wall intervals around calls into a layer.

Each span ends where the call returns to the host.  With tracing on, each
is also written into the profiler's trace (``TraceAnnotation``), on the
device trace's clock, so that idle gaps of the device can be attributed to
the host span open during them.  Spans are kept in memory and read once
the window has closed.
"""
from __future__ import annotations

import contextlib
import time
from typing import Optional


class Spans:
    def __init__(self, annotate: bool = False):
        self.annotate = annotate
        self.rows: list = []  # (name, t0, t1, info)

    @contextlib.contextmanager
    def span(self, name: str, **info):
        ann = contextlib.nullcontext()
        if self.annotate:
            import jax

            ann = jax.profiler.TraceAnnotation(f"bench.{name}")
        t0 = time.perf_counter()
        try:
            with ann:
                yield info
        finally:
            self.rows.append((name, t0, time.perf_counter(), info))

    def wrap(self, obj, attr: str, name: Optional[str] = None) -> None:
        """Replace ``obj.attr`` with a spanned call of the original."""
        orig = getattr(obj, attr)
        label = name or attr

        def spanned(*a, **kw):
            with self.span(label):
                return orig(*a, **kw)

        setattr(obj, attr, spanned)

    def within(self, name: str, t0: float, t1: float) -> list:
        """Spans of ``name`` that started inside [t0, t1)."""
        return [r for r in self.rows if r[0] == name and t0 <= r[1] < t1]
