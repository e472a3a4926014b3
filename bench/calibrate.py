#!/usr/bin/env python3
"""Readings that set a cell's limits: the numbers ``correct`` compares, for
the program and for the control, on many seeds in one process.

  python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... \
      [--faults half_batch no_mix] [--no-program] [--no-control]

For a training cell each seed builds the cell's own trainer (its pool,
state and compiled scan, at the cell's size), drives its first meta-steps
and reads the program's gaps against the float32 reference; the control
is the same reference computed in bfloat16, compared likewise. No
measured window is needed. Prints one JSON line per seed and reading.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import harness  # noqa: E402

sys.path.insert(0, os.path.join(harness.ROOT, "src"))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--no-program", action="store_true")
    ap.add_argument("--no-control", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    devices = harness.require_devices(cell["chips"])
    harness.use_compile_cache()
    job = harness.load_module("jobs", cell["job"])
    for seed in args.seeds:
        for row in job.calibrate(cell, seed, devices, args.faults,
                                 with_program=not args.no_program,
                                 with_control=not args.no_control):
            print(json.dumps(dict(row, seed=seed)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
