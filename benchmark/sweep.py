#!/usr/bin/env python3
"""The one-off sweep that finds an open-loop cell's knee: the same mix at a
list of arrival rates, one set-up, one window each. The knee is the highest
rate at which completions keep up (images finished inside the window ~ images
offered) and the backlog at the window's end does not grow with the rate.
The cell's traffic file then states 0.8 x that rate as a number. By hand.

    python benchmark/sweep.py --workload <cell> --rates 60,100,140 --seconds 15 --seed 7
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import stats  # noqa: E402
from benchmark.harness import Run  # noqa: E402
from benchmark.run import open_cell  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=7)
    args = ap.parse_args(argv)
    cell, devices, peaks, driver = open_cell(args.workload)
    run = Run(cell, args.seed, args.seconds, False, devices, peaks)
    state = driver.setup(run)
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            cell.traffic["arrivals"]["rate_per_s"] = rate
            result = driver.window(run, state, args.seconds)
            offered = sum(r.images for r, _ in result["finished"])
            late = sum(1 for r, t in result["finished"]
                       if t.done_time > result["t1"])
            print(f"sweep rate {rate:g}/s: attempted {result['attempted']} "
                  f"failed {result['failed']} images finished "
                  f"{offered} in-window img/s "
                  f"{result['e2e']['serve_img_per_s']:.1f} p95 "
                  f"{stats.percentile(result['latency_s'], 95) * 1e3:.1f} ms; finished after "
                  f"the window closed {late}; counters {result['counters']}",
                  flush=True)
    finally:
        driver.close(run, state)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
