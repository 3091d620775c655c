"""Readings for the limits of ``correct``: the program's and the controls'.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in a process of its own, runs the cell with a short window at its own
load and prints the numbers compared on the served path, and on the same
sample the controls' readings: the reference computed one precision below
the configuration's in the program's place (fp8 for the bf16 model,
bfloat16 for the float32 index).  A limit is set between the largest
program reading and the smallest control reading (PERF.md gives both).
The benchmark's own runs never compute the controls.
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
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--child", type=int, default=0)
    args = ap.parse_args()
    if not args.child:
        import subprocess

        for seed in args.seeds:
            subprocess.run([sys.executable, __file__, "--workload", args.workload,
                            "--seconds", str(args.seconds), "--seeds", str(seed),
                            "--child", "1"], cwd=ROOT, check=False)
        return 0
    spec = manifest.resolve_cell(ROOT, manifest.load_manifest(ROOT), args.workload)
    devs = harness.require_chips(int(spec["cell"]["chips"]))
    harness.enable_cache()
    for seed in args.seeds:
        t0 = T_START
        res = harness.run(ROOT, spec, seed, args.seconds, False, t0, devices=devs,
                          with_controls=True)
        row = {"seed": seed, "program": {c["name"]: c["value"] for c in res["checks"]},
               "detail": {c["name"]: c["detail"] for c in res["checks"]},
               "control": res["controls"], "wall_s": time.perf_counter() - t0}
        print("control " + json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
