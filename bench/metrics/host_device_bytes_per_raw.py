"""Facade and fused runtime: bytes that crossed between host and device
per raw byte, on the cell's side: (`ceaz_h2d_bytes_total` +
`ceaz_d2h_bytes_total`) over `ceaz_raw_bytes_total` (encode) or
`ceaz_decoded_bytes_total` (decode), from the program's counters. Read
for every `host_device_bytes_per_raw.<cell family>` metric."""
from lib import counters

RAW = {"encode": "ceaz_raw_bytes_total",
       "decode": "ceaz_decoded_bytes_total"}


def read(ctx):
    side = counters.side(ctx)
    if side is None:
        return None
    moved = (counters.total("ceaz_h2d_bytes_total", side=side)
             + counters.total("ceaz_d2h_bytes_total", side=side))
    raw = counters.total(RAW[side])
    if not moved or not raw:
        return None
    return moved / raw
