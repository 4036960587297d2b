#!/usr/bin/env python3
"""Run one cell of the on-chip benchmark.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout, on a machine holding the chips the cell asks
for.  The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, and `checks` (each number
compared with the reference beside its limit), also printed as the last
lines of standard error.  With no TPU, or fewer chips than the cell asks
for, it prints no result and exits non-zero.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], harness.process_start(), ROOT))
