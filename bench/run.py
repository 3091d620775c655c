"""Run one cell of the benchmark and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  It needs a TPU with as many chips as the
cell asks for, and exits non-zero without a result when it finds none.
The last line of standard output is one JSON object; the numbers compared
for ``correct`` are the last lines of standard error.
"""
import time

T_START = time.perf_counter()

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench import harness

    sys.exit(harness.main(sys.argv[1:], T_START, ROOT))
