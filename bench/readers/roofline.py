"""Roofline share, in %, of one dispatch op.

Work: raw plus stream bytes of the chunks the window's ops moved, over
peak HBM bandwidth (lib/peaks.py). Device time: the programs whose name,
as the trace's `XLA Modules` line gives it, matches `programs` (a
regular expression from the metric's file, found by hand in a chip
trace with tools/inspect_trace.py).
"""
import re

from lib import work


def read(ctx, programs: str):
    if ctx.trace is None:
        return None
    device_s = sum(s for s, _ in ctx.trace.program_time(
        re.compile(programs).search).values())
    return work.roofline_pct(work.io_bytes(ctx.ops), device_s,
                             ctx.device_kind)
