"""Collectives: padding in the gathered payload buffers, in % of the
bytes gathered, from the program's counters
`ceaz_gather_pad_bytes_total` / (`ceaz_gather_payload_bytes_total` +
`ceaz_gather_pad_bytes_total`). A program without them reads nothing."""
from lib import counters


def read(ctx):
    pad = counters.total("ceaz_gather_pad_bytes_total")
    moved = pad + counters.total("ceaz_gather_payload_bytes_total")
    if not moved:
        return None
    return 100.0 * pad / moved
