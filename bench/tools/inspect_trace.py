#!/usr/bin/env python3
"""Print what a profiler trace holds: planes, lines, event counts, the
time span of each line and its costliest event names. For looking at a
trace by hand before writing a reader against it.

    python3 bench/tools/inspect_trace.py TRACE_DIR [--top 25]
"""
from __future__ import annotations

import argparse
import collections
import glob
import os


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace_dir")
    ap.add_argument("--top", type=int, default=25)
    args = ap.parse_args(argv)
    from jax.profiler import ProfileData
    for path in glob.glob(os.path.join(args.trace_dir, "**", "*.xplane.pb"),
                          recursive=True):
        print(f"== {path} ({os.path.getsize(path)} B)")
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            lines = list(plane.lines)
            print(f"plane {plane.name!r}: {len(lines)} lines")
            for line in lines:
                ev = list(line.events)
                if not ev:
                    continue
                t0 = min(e.start_ns for e in ev)
                t1 = max(e.start_ns + e.duration_ns for e in ev)
                print(f"  line {line.name!r}: {len(ev)} events, "
                      f"{t0 * 1e-9:.6f}..{t1 * 1e-9:.6f} s")
                if plane.name.startswith("/device") or any(
                        e.name.startswith("bench.") for e in ev):
                    tot = collections.Counter()
                    cnt = collections.Counter()
                    for e in ev:
                        tot[e.name] += e.duration_ns
                        cnt[e.name] += 1
                    for name, ns in tot.most_common(args.top):
                        print(f"    {ns * 1e-9:12.6f} s  {cnt[name]:6d}x  "
                              f"{name[:150]}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
