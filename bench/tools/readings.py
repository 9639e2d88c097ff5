#!/usr/bin/env python3
"""Read a cell's compared numbers over many seeds in one process: the
program as committed, or with `--control` its control (the op's `control()`).
Each seed is one whole run (set-up, window, check); the programs are
compiled once.

    python3 bench/tools/readings.py --workload hacc.dump --seconds 10 \
        --seeds 11 12 13 [--control]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from lib import harness, named  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    spec = harness.load_spec()
    cell = harness.cell_of(spec, args.workload)
    op = harness.load_json(os.path.join(
        harness.BENCH_DIR, "traffic", cell["traffic"] + ".json"))["op"]
    hooks = named.module("ops", op).control() if args.control else None
    for seed in args.seeds:
        r = harness.run(args.workload, seed, args.seconds, False,
                        hooks=hooks)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control, "correct": r["correct"],
                          "attempted": r["attempted"],
                          "metrics": r["metrics"], "checks": r["checks"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
