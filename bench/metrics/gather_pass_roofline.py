"""Dispatch ops: roofline share, in %, of the rank-sharded bank pass.

Work: raw plus stream bytes of the window's ops (every rank's brick)
over peak HBM bandwidth (lib/peaks.py). Device time: the program
`jit_ceaz_gather_pass(...)` of the trace's `XLA Modules` line, the
N-D bank pass `_bank_pass_fn` run on each chip on its own brick, summed
over the chips (each chip does its rank's share of the work)."""
import re

from lib import work

PROGRAM = re.compile(r"^jit_ceaz_gather_pass\(")


def read(ctx):
    if ctx.trace is None:
        return None
    per_chip = sum(s for s, _ in ctx.trace.program_time(
        PROGRAM.search).values())
    return work.roofline_pct(work.io_bytes(ctx.ops),
                             per_chip * len(ctx.trace.modules),
                             ctx.device_kind)
