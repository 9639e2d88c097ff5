"""Collectives: device time in collective operations (the trace's `XLA
Ops` whose names match `xtrace.COLLECTIVE`), summed over the chips, in %
of chips x window. Read for the gather cells."""
from lib.xtrace import COLLECTIVE


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.op_time(COLLECTIVE.search) / ctx.trace.window_s
