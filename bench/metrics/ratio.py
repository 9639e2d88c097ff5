"""Raw bytes over stream-file bytes, over all dumps of the window."""
from lib import work


def read(ctx):
    return work.ratio(ctx.ops)
