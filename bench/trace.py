"""From a profiler trace to device busy time, program and kernel time, and
idle gaps named by the host span open during them.

``extract`` turns the profiler's ``.xplane.pb`` into a flat list of events
(the form the recorded test fixture keeps); ``reduce`` works on that list
alone.  Device events are those of planes named ``/device:...``: the
``XLA Ops`` line gives the operations that ran, the ``XLA Modules`` line
the programs they belong to.  Host spans are the benchmark's own
``bench.*`` annotations.
"""
from __future__ import annotations

import bisect
import glob
import os


def extract(trace_dir: str) -> list:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith("bench."):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": _short(ev.name) if device else ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def _union(intervals: list) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _short(name: str) -> str:
    """An operation's name without the HLO text that follows it."""
    return name.split(" = ", 1)[0][:80]


def reduce(events: list, t0_ns: float, t1_ns: float, *, top: int = 10) -> dict:
    """Busy and idle time of the device over [t0_ns, t1_ns), time per
    program and per operation (named ``program:op``), and the longest idle
    gaps named by the host span open during them."""
    window = t1_ns - t0_ns
    ops = [e for e in events if e["line"] == "XLA Ops"]
    planes = sorted({e["plane"] for e in ops})
    busy_ns, gaps = 0.0, []
    for p in planes:
        iv = _union([[max(e["start_ns"], t0_ns), min(e["start_ns"] + e["dur_ns"], t1_ns)]
                     for e in ops if e["plane"] == p
                     and e["start_ns"] < t1_ns and e["start_ns"] + e["dur_ns"] > t0_ns])
        busy_ns += sum(b - a for a, b in iv)
        edges = [t0_ns] + [x for ab in iv for x in ab] + [t1_ns]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy_ns /= max(len(planes), 1)

    def inside(e):
        return t0_ns <= e["start_ns"] < t1_ns

    mods: dict = {}  # plane -> programs by start; they do not overlap
    by_module: dict = {}
    for e in sorted((e for e in events if e["line"] == "XLA Modules"),
                    key=lambda e: e["start_ns"]):
        mods.setdefault(e["plane"], []).append(e)
        if inside(e):
            by_module[e["name"]] = by_module.get(e["name"], 0.0) + e["dur_ns"]
    starts = {p: [m["start_ns"] for m in ms] for p, ms in mods.items()}

    def program(e):
        i = bisect.bisect_right(starts.get(e["plane"], []), e["start_ns"]) - 1
        if i >= 0:
            m = mods[e["plane"]][i]
            if e["start_ns"] < m["start_ns"] + m["dur_ns"]:
                return m["name"].split("(", 1)[0]
        return "?"

    by_op: dict = {}
    for e in ops:
        if inside(e):
            key = f"{program(e)}:{_short(e['name'])}"
            by_op[key] = by_op.get(key, 0.0) + e["dur_ns"]
    spans = [e for e in events if e["name"].startswith("bench.")]
    named: list = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        mid = (a + b) / 2.0
        open_ = [s for s in spans if s["start_ns"] <= mid < s["start_ns"] + s["dur_ns"]]
        name = max(open_, key=lambda s: s["start_ns"])["name"] if open_ else "no span"
        named.append([name, (b - a) / 1e9])
    return {
        "busy_s": busy_ns / 1e9, "window_s": window / 1e9, "n_devices": len(planes),
        "module_s": {k: v / 1e9 for k, v in by_module.items()},
        "device_ops": [[k, v / 1e9] for k, v in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": named,
    }


def module_seconds(red: dict, fragment: str) -> float:
    """Device seconds of the programs whose name contains ``fragment``."""
    return sum(v for k, v in red["module_s"].items() if fragment in k)
