"""Facade and fused runtime: the real values the device passes carried,
in % of the values they were sized for (padding to whole chunks on the
encode side, to capacity buckets on the decode side), from the program's
counters `ceaz_pass_live_values_total` / `ceaz_pass_values_total` of the
cell's side. Read for every `pass_fill.<cell family>` metric."""
from lib import counters


def read(ctx):
    side = counters.side(ctx)
    sized = counters.total("ceaz_pass_values_total", side=side)
    if side is None or not sized:
        return None
    live = counters.total("ceaz_pass_live_values_total", side=side)
    return 100.0 * live / sized
