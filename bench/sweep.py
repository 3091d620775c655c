"""Find a cell's knee once: the highest steady rate at which 90% of the
requests due in the window meet their limits and the backlog does not grow.

    python3 bench/sweep.py --workload <cell> --seed <n> --seconds <s> \
        --rates 1 1.5 2 2.5

Runs the cell at each offered rate as a steady open loop (bursts off),
each rate in a process of its own, and prints, per rate, the share of
requests that met their limit and the client-side p50 and p90, overall and
per workflow.  The cell's mix file then takes 0.8 of the knee as its
``rate_per_s``.
"""
import time

T_START = time.perf_counter()

import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    import argparse

    from bench import harness, manifest, stats

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--child", type=int, default=0)
    args = ap.parse_args()
    if not args.child:
        import subprocess

        for rate in args.rates:
            subprocess.run([sys.executable, __file__, "--workload", args.workload,
                            "--seed", str(args.seed), "--seconds", str(args.seconds),
                            "--rates", str(rate), "--child", "1"], cwd=ROOT, check=False)
        return 0
    spec = manifest.resolve_cell(ROOT, manifest.load_manifest(ROOT), args.workload)
    devs = harness.require_chips(int(spec["cell"]["chips"]))
    harness.enable_cache()
    for rate in args.rates:
        sp = copy.deepcopy(spec)
        sp["traffic"]["rate_per_s"] = rate
        sp["traffic"].pop("bursts", None)
        t0 = T_START
        res = harness.run(ROOT, sp, args.seed, args.seconds, False, t0, devices=devs)
        reqs = res["ctx"]["requests"]
        lat = stats.client_latencies(reqs)
        met = sum(1 for r in reqs if r["done"] is not None
                  and r["done"] - r["due"] <= r["limit"])
        by_wf: dict = {}
        for r, x in zip(reqs, lat):
            by_wf.setdefault(r["workflow"], []).append(x)
        row = {"rate": rate, "requests": len(reqs), "met_share": met / max(len(reqs), 1),
               "p50_ms": 1e3 * stats.percentile(lat, 50) if lat else None,
               "p90_ms": 1e3 * stats.percentile(lat, 90) if lat else None,
               "unanswered": sum(1 for r in reqs if r["done"] is None),
               "p90_ms_by_workflow": {k: 1e3 * stats.percentile(v, 90)
                                      for k, v in by_wf.items()},
               "gen": res["ctx"]["gen_counts"], "correct": all(c["ok"] for c in res["checks"]),
               "setup": res["setup_spans"], "setup_s": res["setup_s"],
               "window_lowered": res["in_window"]["lowered"],
               "wall_s": time.perf_counter() - t0}
        print("sweep " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
