"""One run of a cell with the program's own wall-clock spans on.

    python3 bench/program_trace.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> [--out <dir>]

Runs the cell as ``bench/run.py`` does, with the server's recorder switched
on after warm-up (``Server.wall_trace``), and prints the same lines and
result line.  Then it prints the per-layer metrics read from the program's
own spans and counters (``program metrics``), the cost of one span on this
host, and with ``--trace 1`` the device's idle time attributed to the
innermost ``repro.*`` span open during it (``device idle by program
span``) and the idle gaps of 10 ms or more with their span.  With
``--trace 0`` the result line's end-to-end metrics measure a run with
spans on and no profiler, against runs of ``bench/run.py`` with tracing off.

The readers below take a ``ctx`` that holds the recorder under
``"program_trace"`` and the window under ``"window"`` (``perf_counter``
seconds, the clock the recorder's ``perf_counter_ns`` counts in).  The
harness passes neither today; ``PERF.md`` (Open questions) names the edit
that would make them per-layer metrics of every ``--trace 1`` run.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


# ------------------------------------------------------------------ readers
def spans_in(ctx: dict, name: str) -> list:
    """The recorder's spans of ``name`` that start inside the window."""
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    return [s for s in ctx["program_trace"].wall_spans
            if s.name == name and t0 <= s.t0 < t1]


def _children(ctx: dict, parents: list, name: str) -> dict:
    """parent sid -> total ns of its child spans named ``name``."""
    sids = {p.sid for p in parents}
    out: dict = {}
    for s in ctx["program_trace"].wall_spans:
        if s.name == name and s.parent in sids:
            out[s.parent] = out.get(s.parent, 0) + s.t1 - s.t0
    return out


def _mean_ms(spans: list):
    if not spans:
        return None
    return sum(s.t1 - s.t0 for s in spans) / len(spans) / 1e6


def _breakdown_rows(ctx: dict) -> list:
    """Wall breakdowns of the requests whose ingress stamp is in the window."""
    from repro.obs.attribution import wall_breakdown

    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    stamps = {s.args["rid"]: s.t0 for s in ctx["program_trace"].wall_spans
              if s.name == "serve.queue"}
    return [row for rid, row in wall_breakdown(ctx["program_trace"]).items()
            if t0 <= stamps[rid] < t1]


def ingress_wait_ms(ctx):
    rows = _breakdown_rows(ctx)
    return sum(r["ingress_us"] for r in rows) / len(rows) / 1e3 if rows else None


def stage_wait_ms(ctx):
    rows = _breakdown_rows(ctx)
    return sum(r["stage_wait_us"] for r in rows) / len(rows) / 1e3 if rows else None


def gen_substage_ms(ctx):
    return _mean_ms(spans_in(ctx, "sched.gen_substage"))


def gen_budget_ms(ctx):
    """Mean budget ``mb_us`` the scheduler sized its generation sub-stages
    by, in ms."""
    s = spans_in(ctx, "sched.gen_substage")
    return sum(x.args["budget_us"] for x in s) / len(s) / 1e3 if s else None


def decode_host_ms(ctx):
    steps = spans_in(ctx, "engine.decode")
    if not steps:
        return None
    pull = _children(ctx, steps, "engine.decode.pull")
    return sum(s.t1 - s.t0 - pull.get(s.sid, 0) for s in steps) / len(steps) / 1e6


def slot_occupancy(ctx):
    steps = spans_in(ctx, "engine.decode")
    if not steps:
        return None
    return 100.0 * sum(s.args["live"] / s.args["slots"] for s in steps) / len(steps)


def ret_host_scan_ms(ctx):
    subs = spans_in(ctx, "ret.substage")
    if not subs:
        return None
    host = _children(ctx, subs, "ret.host_scan")
    return sum(host.values()) / len(subs) / 1e6


def ret_device_row_share(ctx):
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    before = after = None
    for t, name, values in ctx["program_trace"].counters:
        if name != "ret.scanned":
            continue
        if t < t0:
            before = values
        elif t < t1:
            after = values
    if after is None:
        return None
    base = before or {"device_rows": 0, "host_rows": 0}
    dev = after["device_rows"] - base["device_rows"]
    host = after["host_rows"] - base["host_rows"]
    return 100.0 * dev / (dev + host) if dev + host else None


def sched_host_share(ctx):
    """Self time of ``sched.cycle`` (less the scheduler's calls into the
    backend) over the window's seconds."""
    cycles = spans_in(ctx, "sched.cycle")
    if not cycles:
        return None
    inner = 0
    for name in ("sched.gen_substage", "sched.ret_substage", "sched.stage"):
        inner += sum(_children(ctx, cycles, name).values())
    busy = sum(s.t1 - s.t0 for s in cycles)
    t0, t1 = ctx["window"]
    return 100.0 * (busy - inner) / 1e9 / (t1 - t0)


def decode_step_ms(ctx):
    return _mean_ms(spans_in(ctx, "engine.decode"))


def prefill_ms_per_ktok(ctx):
    s = spans_in(ctx, "engine.prefill")
    toks = sum(x.args["tokens"] for x in s)
    return sum(x.t1 - x.t0 for x in s) / 1e6 / (toks / 1e3) if toks else None


def ret_substage_ms(ctx):
    return _mean_ms(spans_in(ctx, "ret.substage"))


READERS = {
    # new per-layer metrics
    "ingress_wait_ms": ingress_wait_ms, "stage_wait_ms": stage_wait_ms,
    "gen_substage_ms": gen_substage_ms, "decode_host_ms": decode_host_ms,
    "slot_occupancy": slot_occupancy, "ret_host_scan_ms": ret_host_scan_ms,
    "ret_device_row_share": ret_device_row_share,
    # in-program sources of metrics the benchmark reads from its own spans
    "sched_host_share": sched_host_share, "decode_step_ms": decode_step_ms,
    "prefill_ms_per_ktok": prefill_ms_per_ktok, "ret_substage_ms": ret_substage_ms,
}


def program_metrics(ctx: dict) -> dict:
    out = {}
    for name, read in READERS.items():
        v = read(ctx)
        if v is not None:
            out[name] = v
    return out


# ----------------------------------------------------------- device trace
def extract(trace_dir: str) -> list:
    """``bench.trace.extract``'s events plus the host's ``repro.*`` spans."""
    import glob
    import os

    from jax.profiler import ProfileData

    from bench import trace

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    events = []
    for plane in ProfileData.from_file(paths[-1]).planes:
        device = plane.name.startswith("/device:")
        for line in plane.lines:
            if device and line.name not in ("XLA Ops", "XLA Modules"):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(("bench.", "repro.")):
                    continue
                events.append({"plane": plane.name, "line": line.name,
                               "name": trace._short(ev.name) if device else ev.name,
                               "start_ns": float(ev.start_ns),
                               "dur_ns": float(ev.duration_ns)})
    return events


def idle_by_span(events: list, t0_ns: float, t1_ns: float) -> tuple:
    """Every idle interval of the devices over [t0_ns, t1_ns), cut where the
    innermost open ``repro.*`` host span changes and charged to it (or to
    ``no span``).  Returns ({name: idle seconds}, [(name, gap seconds)] of
    the gaps of 10 ms or more, named by the span that holds most of
    each)."""
    from bench import trace

    ops = [e for e in events if e["line"] == "XLA Ops"]
    spans = [(e["start_ns"], e["start_ns"] + e["dur_ns"], e["name"][6:])
             for e in events if e["name"].startswith("repro.")]
    planes = sorted({e["plane"] for e in ops})
    by_name: dict = {}
    long_gaps: list = []
    for p in planes:
        busy = trace._union([[max(e["start_ns"], t0_ns),
                              min(e["start_ns"] + e["dur_ns"], t1_ns)]
                             for e in ops if e["plane"] == p
                             and e["start_ns"] < t1_ns
                             and e["start_ns"] + e["dur_ns"] > t0_ns])
        edges = [t0_ns] + [x for ab in busy for x in ab] + [t1_ns]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            inside = [s for s in spans if s[0] < b and s[1] > a]
            cuts = sorted({a, b} | {x for s in inside for x in s[:2] if a < x < b})
            share: dict = {}
            for x, y in zip(cuts, cuts[1:]):
                mid = (x + y) / 2.0
                open_ = [s for s in inside if s[0] <= mid < s[1]]
                name = max(open_, key=lambda s: s[0])[2] if open_ else "no span"
                share[name] = share.get(name, 0.0) + (y - x) / 1e9
            for name, secs in share.items():
                by_name[name] = by_name.get(name, 0.0) + secs / len(planes)
            if b - a >= 1e7:
                long_gaps.append((max(share, key=share.get), (b - a) / 1e9))
    return by_name, sorted(long_gaps, key=lambda g: -g[1])


def span_totals(ctx: dict) -> dict:
    """name -> [spans, total ms, self ms] of the spans that start inside the
    window; self time is a span's duration less its children's."""
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    spans = ctx["program_trace"].wall_spans
    child_ns: dict = {}
    for s in spans:
        if s.parent >= 0:
            child_ns[s.parent] = child_ns.get(s.parent, 0) + s.t1 - s.t0
    out: dict = {}
    for s in spans:
        if t0 <= s.t0 < t1:
            row = out.setdefault(s.name, [0, 0.0, 0.0])
            row[0] += 1
            row[1] += (s.t1 - s.t0) / 1e6
            row[2] += (s.t1 - s.t0 - child_ns.get(s.sid, 0)) / 1e6
    return out


def span_cost_us(n: int = 20000) -> dict:
    """Host microseconds per span site with the channel on (no profiler
    running) and off."""
    from repro.obs.trace import NOSPAN, TraceRecorder

    out = {}
    for on in (True, False):
        tr = TraceRecorder(wall=on)
        t0 = time.perf_counter()
        for i in range(n):
            with (tr.span("cost", rid=i) if tr.wall else NOSPAN):
                pass
        out["on" if on else "off"] = (time.perf_counter() - t0) / n * 1e6
    return out


def main() -> int:
    import argparse

    from bench import harness, manifest, trace

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    spec = manifest.resolve_cell(ROOT, manifest.load_manifest(ROOT), args.workload)
    devs = harness.require_chips(int(spec["cell"]["chips"]))
    print(f"device: {devs[0].device_kind} x{len(devs)}; compile cache: {harness.enable_cache()}")
    trace.extract = extract  # keeps the repro.* spans; reduce reads as before
    recs = []
    res = harness.run(ROOT, spec, args.seed, args.seconds, bool(args.trace), T_START,
                      devices=devs, fault=lambda st: recs.append(st.server.wall_trace()))
    line = harness.report(ROOT, spec, res, bool(args.trace), devs)
    print(json.dumps(line))
    ctx = dict(res["ctx"], program_trace=recs[0])
    prog = program_metrics(ctx)
    s = spans_in(ctx, "sched.gen_substage")
    if s:
        steps = statistics.mean(x.args["n_steps"] for x in s)
        print(f"generation sub-stages: {len(s)}, mean {prog['gen_substage_ms']:.3f} ms against "
              f"a budget of {gen_budget_ms(ctx):.3f} ms (ratio "
              f"{prog['gen_substage_ms'] / gen_budget_ms(ctx):.1f}), {steps:.2f} steps each")
    cost = span_cost_us()
    print(f"span cost on this host: {cost['on']:.3f} us a span with the channel on, "
          f"{cost['off']:.3f} us a site with it off")
    totals = span_totals(ctx)
    print("program spans in the window as name spans/total ms/self ms: " + ", ".join(
        f"{n} {c}/{t:.1f}/{s_:.1f}" for n, (c, t, s_) in
        sorted(totals.items(), key=lambda kv: -kv[1][2])))
    bench_totals: dict = {}
    for n, a, b, _ in ctx["spans"].rows:
        if ctx["window"][0] <= a < ctx["window"][1]:
            row = bench_totals.setdefault(n, [0, 0.0])
            row[0] += 1
            row[1] += (b - a) * 1e3
    print("benchmark spans in the window as name spans/total ms: " + ", ".join(
        f"{n} {c}/{t:.1f}" for n, (c, t) in bench_totals.items()))
    t0, t1 = (int(t * 1e9) for t in ctx["window"])
    keep = {"line": line, "program_metrics": prog, "span_cost_us": cost,
            "span_totals": totals, "bench_span_totals": bench_totals,
            "spans": [[s.name, s.t0, s.t1, s.sid, s.parent, s.args]
                      for s in recs[0].wall_spans if t0 <= s.t0 < t1]}
    red = ctx["trace"]
    if red is not None:
        a, b = red["marks_ns"]
        idle, gaps = idle_by_span(red["events"], a, b)
        total = sum(idle.values())
        print("device idle by program span: " + ", ".join(
            f"{n} {v:.4f} s" for n, v in sorted(idle.items(), key=lambda kv: -kv[1]))
            + f"; {100.0 * idle.get('no span', 0.0) / total if total else 0.0:.1f}% "
            f"of {total:.4f} s idle under no span")
        print("idle gaps of 10 ms or more: " + ", ".join(f"{n} {g:.4f} s" for n, g in gaps))
        keep.update(idle_by_span=idle, long_gaps=gaps)
    print("program metrics: " + json.dumps(prog))
    if args.out:
        out = ROOT / args.out
        out.mkdir(parents=True, exist_ok=True)
        (out / f"program_trace.s{args.seed}.t{args.trace}.json").write_text(
            json.dumps(keep, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
