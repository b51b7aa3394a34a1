#!/usr/bin/env python3
"""Run one benchmark cell once on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the cell's JSON result; the numbers
compared with the plain reference are the last lines of standard error.
Exits non-zero, with no result, where JAX finds no TPU or fewer chips
than the cell asks for.  See ``bench/harness.py``.
"""
import time

T_START = time.perf_counter()   # set-up is counted from here

import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(t_start=T_START))
