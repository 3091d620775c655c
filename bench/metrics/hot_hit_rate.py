"""Share of the window's cluster probes that the device hot cache held,
from the hybrid engine's own counters (``hybrid.stats()``)."""


def read(ctx):
    hits = ctx["cache1"]["hits"] - ctx["cache0"].get("hits", 0)
    miss = ctx["cache1"]["misses"] - ctx["cache0"].get("misses", 0)
    if hits + miss == 0:
        return None
    return 100.0 * hits / (hits + miss)
