"""One run of a cell, keeping what is needed to size and debug it.

    python3 bench/rehearse.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> --out chiprun_out/<name>

Runs the cell as ``bench/run.py`` does, prints the same lines, and writes
under ``--out``: the result line, the scan shapes and slab upload sizes the
traffic reached (for the mix file's ``warm`` ranges), span totals by name,
and with ``--trace 1`` the flat event list of the trace (``events.json``),
cut to a quarter second for the trace-reduction test fixture.
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse

    from bench import harness, manifest

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    spec = manifest.resolve_cell(ROOT, manifest.load_manifest(ROOT), args.workload)
    devs = harness.require_chips(int(spec["cell"]["chips"]))
    print(f"device: {devs[0].device_kind} x{len(devs)}; cache {harness.enable_cache()}")
    res = harness.run(ROOT, spec, args.seed, args.seconds, bool(args.trace), T_START,
                      devices=devs)
    line = harness.report(ROOT, spec, res, bool(args.trace), devs)
    print(json.dumps(line))
    ctx = res["ctx"]
    totals: dict = {}
    t0, t1 = ctx["window"]
    for n, a, b, _ in ctx["spans"].rows:
        if t0 <= a < t1:
            tot = totals.setdefault(n, [0, 0.0])
            tot[0] += 1
            tot[1] += b - a
    keep = {"line": line, "scan_shapes": [[g, k, n] for (g, k), n in res["scan_shapes"].items()],
            "upload_sizes": res["upload_sizes"], "span_totals": totals,
            "gen_counts": ctx["gen_counts"],
            "latencies": [(r["workflow"], r["due"] - t0,
                           None if r["done"] is None else r["done"] - r["due"])
                          for r in ctx["requests"]]}
    if ctx["trace"] is not None:
        red = dict(ctx["trace"])
        events = red.pop("events")
        # a quarter second from the middle of the traced slice: small
        # enough to keep as the reduction's test fixture
        a, b = red["marks_ns"]
        mid = (a + b) / 2
        cut = [e for e in events if mid <= e["start_ns"] < mid + 2.5e8
               or (e["name"].startswith("bench.") and e["start_ns"] < mid + 2.5e8
                   and e["start_ns"] + e["dur_ns"] > mid)]
        (out / f"events.s{args.seed}.json").write_text(json.dumps(
            {"marks_ns": [mid, mid + 2.5e8], "events": cut}))
        # every scan program and its operations in the traced slice, with
        # the scan calls the host made in it, for a look at one scan
        mods = [e for e in events if e["line"] == "XLA Modules" and "ivf_scan" in e["name"]]
        ops = [e for e in events if e["line"] == "XLA Ops" and any(
            m["start_ns"] <= e["start_ns"] < m["start_ns"] + m["dur_ns"] for m in mods)]
        (out / f"scans.s{args.seed}.json").write_text(json.dumps(
            {"marks_ns": red["marks_ns"], "trace_window": ctx["trace_window"],
             "modules": mods, "ops": ops, "calls": ctx["scan_rows"],
             "spans": [e for e in events if e["name"] == "bench.search"]}))
        keep["trace"] = {k: v for k, v in red.items()}
    (out / f"rehearse.s{args.seed}.t{args.trace}.json").write_text(
        json.dumps(keep, default=str))
    return 0


if __name__ == "__main__":
    sys.exit(main())
