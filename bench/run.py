#!/usr/bin/env python3
"""The chip benchmark: one run of one cell of ``BENCHMARK.json``.

    python bench/run.py --workload toolbench-catalog.agents --seed 7 \\
        --seconds 20 --trace 0

Builds the cell from the seed, warms up every shape its traffic uses,
measures for ``--seconds``, judges the window's decisions against the
numpy reference under ``bench/ref`` and prints one JSON object as the
last line of standard output: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window), ``device``
and, traced, ``breakdown``; last, under ``checks``, each number the
judge compared with its limit.  The same numbers end standard error.

It refuses to run (exit 2, no result) where JAX finds no TPU or fewer
chips than the cell asks for.  JAX's persistent compilation cache lives
in ``.jax_cache/`` of the checkout unless ``JAX_COMPILATION_CACHE_DIR``
names another.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import cell

    try:
        out = cell.run(args.workload, args.seed, args.seconds,
                       bool(args.trace), T_START)
    except cell.NoChip as e:
        print(f"bench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, (value, limit) in out["checks"].items():
        print(f"check {name}: {value!r} (limit {limit!r})", file=sys.stderr)
    print(json.dumps(out["result"]), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
