#!/usr/bin/env python3
"""Readings of the comparison that decides ``correct``, over many seeds.

    python bench/control.py --workload toolbench-catalog.agents \\
        --seconds 20 --seeds 11,12,13

For each seed, in one process: one run of the cell as ``bench/run.py``
makes it, then the judge's readings of the program's decisions (the
lower readings of each limit) and of the control's in their place (the
reference at ``Precision.HIGH``, one step below the ``HIGHEST`` the
configuration states: the upper readings).  One JSON line per seed.  The
benchmark's own runs never run the control.
"""
import argparse
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from harness import cell

    for seed in (int(s) for s in args.seeds.split(",")):
        try:
            out = cell.run(args.workload, seed, args.seconds, False,
                           time.monotonic(), control=True)
        except cell.NoChip as e:
            print(f"control: {e}", file=sys.stderr)
            return 2
        drop = ("worst",)
        print(json.dumps({
            "seed": seed, "correct": out["result"]["correct"],
            "metrics": {k: v["value"] for k, v in out["result"]["metrics"].items()},
            "program": {k: v for k, v in out["reading"].items() if k not in drop},
            "control": {k: v for k, v in out["control"].items() if k not in drop},
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
