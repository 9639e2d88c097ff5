#!/usr/bin/env python3
"""The benchmark's entry point: one run of one cell.

    python3 bench/run.py --workload hacc.dump --seed 7 --seconds 10 \
        --trace 0

Prints progress and the compared numbers on standard error and, as the
last line of standard output, one JSON object: `correct`, `attempted`,
`failed`, `metrics`, `device` (and `breakdown` with `--trace 1`), with
the compared numbers and their limits last, under `checks`. Exits 2,
printing no result, unless JAX finds a TPU with as many chips as the
cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", metavar="DIR",
                    help="with --trace 1, copy the profiler trace here")
    args = ap.parse_args(argv)
    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t_start=T_START,
                             keep_trace=args.keep_trace)
    except harness.NoDevice as e:
        harness.log(f"bench: {e}")
        return 2
    for name, c in result["checks"].items():
        harness.log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
