#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from: for each seed, in one
process, a run of a cell (set-up, a short window, the check), the numbers compared for the program, and
the same numbers for the control (the reference one precision below the
configuration's, in the program's place). Not run by the benchmark's runs.

    python benchmark/calibrate.py --workload <name> --seeds 11,12,13 --seconds 5 [--control 1]

Prints one ``reading`` line per number and seed; no result line.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark.harness import Run  # noqa: E402
from benchmark.run import execute, open_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control", type=int, default=1)
    args = ap.parse_args(argv)
    cell, devices, peaks, driver = open_cell(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = Run(cell, seed, args.seconds, False, devices, peaks)
        t0 = time.perf_counter()
        result, compared, _, _, _, state = execute(run, t0=t0)
        for c in compared:
            print(f"reading seed={seed} program {c.name} {c.value:.6g}",
                  flush=True)
        if args.control:
            for c in driver.control(run, state, result):
                print(f"reading seed={seed} control {c.name} {c.value:.6g}",
                      flush=True)
        print(f"seed {seed}: e2e {result['e2e']} attempted "
              f"{result['attempted']} failed {result['failed']} "
              f"({time.perf_counter() - t0:.1f} s)", flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
