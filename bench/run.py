#!/usr/bin/env python3
"""Benchmark entry point: runs one cell once on the chips it asks for.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The last line of standard output is
the result as one JSON object; the numbers compared for ``correct`` are
the last lines of standard error.  Without a TPU, or with fewer chips
than the cell asks for, it exits nonzero and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# the checkout root (for the ``bench`` package) and the program under
# test, in place of this script's directory
sys.path[0:1] = [str(ROOT), str(ROOT / "src")]

if __name__ == "__main__":
    from bench.harness import main

    raise SystemExit(main(t_start=T_START))
