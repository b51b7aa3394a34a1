#!/usr/bin/env python3
"""Read a cell's compared numbers for the program and for its control, on
several seeds in one process, at the cell's own size.

    python3 bench/control.py --workload <cell> --seeds 11,12,13 --seconds 5

For each seed: set up, run a short window, then print one JSON line with
the program's checks and the control's (the driver's ``check(control=
True)``).  Limits are set from these readings: above the largest the
program gives, below the smallest the control gives.  Needs the chips the
cell asks for, like ``bench/run.py``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.find_chips(cell.chips)
    except harness.NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    harness.enable_cache()
    for seed in (int(s) for s in args.seeds.split(",")):
        drv = harness.driver(cell.config).Driver(
            cell.config, cell.traffic, seed=seed, devices=devices,
            log=lambda m: print(m, flush=True))
        drv.window(args.seconds)
        drv.release()
        print(json.dumps({
            "seed": seed,
            "program": {c.name: c.value for c in drv.check()},
            "control": {c.name: c.value for c in drv.check(control=True)},
        }), flush=True)
        del drv
    return 0


if __name__ == "__main__":
    sys.exit(main())
