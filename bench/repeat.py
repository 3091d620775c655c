"""Run one cell several times, each run its own process, and summarise.

    python3 bench/repeat.py --workload <cell> --seconds <s> --seeds 11 12 13 \
        [--trace 1] [--out chiprun_out/<name>]

Each run is ``bench/run.py`` in a child process (one process at a time
holds the chip; this parent never touches JAX).  The last line of each
run's output, and the tail of its standard error, are kept under
``--out``; the summary gives each metric's median and its spread (the
interquartile range over the median, ``statistics.quantiles``).
"""
from __future__ import annotations

import argparse
import json
import statistics
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

from bench import stats  # noqa: E402  (no JAX: this parent never holds the chip)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--out", default="chiprun_out/repeat")
    args = ap.parse_args()
    out = ROOT / args.out
    out.mkdir(parents=True, exist_ok=True)
    lines = []
    for seed in args.seeds:
        t0 = time.perf_counter()
        p = subprocess.run(
            [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", args.workload,
             "--seed", str(seed), "--seconds", str(args.seconds), "--trace", str(args.trace)],
            cwd=ROOT, capture_output=True, text=True)
        wall = time.perf_counter() - t0
        tag = f"{args.workload}.s{seed}.t{args.trace}"
        (out / f"{tag}.out").write_text(p.stdout)
        (out / f"{tag}.err").write_text(p.stderr[-20000:])
        last = p.stdout.strip().splitlines()[-1] if p.stdout.strip() else ""
        try:
            line = json.loads(last)
        except json.JSONDecodeError:
            line = None
        print(f"== seed {seed} rc {p.returncode} wall {wall:.1f} s", flush=True)
        print("\n".join(p.stdout.strip().splitlines()[-7:-1]))
        print("\n".join(p.stderr.strip().splitlines()[-5:]))
        print(last, flush=True)
        if line is not None:
            lines.append(line)
    if len(lines) >= 2:
        names = sorted({k for ln in lines for k in ln["metrics"]})
        for n in names:
            v = [ln["metrics"][n]["value"] for ln in lines if n in ln["metrics"]]
            sp = stats.spread(v) if len(v) >= 2 else float("nan")
            print(f"summary {n}: median {statistics.median(v)} spread {sp} values {v}")
        print(f"summary correct: {[ln['correct'] for ln in lines]}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
