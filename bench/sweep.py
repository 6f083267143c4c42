#!/usr/bin/env python3
"""Knee sweep of an open-loop cell on the chip.

    python bench/sweep.py --workload <open-loop cell> --seed 11 \\
        --seconds 10 --rates 500,1000,1500,2000

One build and warm-up, then one measured window per rate on the same
gateway; prints one JSON line per rate (p50 and p99 from due time, the
share failed, and how long past the window the last answer came).  The
knee is the highest rate that meets the configuration's p99 limit with
no growing backlog; the cell's traffic file then fixes its rate.
"""
import argparse
import json
import os
import sys

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    from harness import cell

    try:
        rows = cell.sweep(args.workload, args.seed,
                          [float(r) for r in args.rates.split(",")],
                          args.seconds)
    except cell.NoChip as e:
        print(f"sweep: {e}", file=sys.stderr)
        return 2
    for row in rows:
        print(json.dumps(row))
    return 0


if __name__ == "__main__":
    sys.exit(main())
