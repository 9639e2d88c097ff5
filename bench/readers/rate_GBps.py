"""Raw bytes the window's ops moved per second of the window, in GB/s."""
from lib import work


def read(ctx):
    return work.rate_GBps(ctx.ops, ctx.window_s)
