"""Work counts and the arithmetic of the end-to-end metrics.

A roofline share counts an op's work from its inputs and outputs alone,
the raw bytes of the chunks it processed plus their stream bytes, never
from the intermediates of an implementation. The least time the chip
could take is that work over the peak HBM bandwidth; the share is that
time over the device time of the programs the op launched.
"""
from __future__ import annotations

import math

from . import peaks

F32_BYTES = 4


def field_bytes(shape) -> int:
    return F32_BYTES * math.prod(shape)


def chunks_per_field(shape, chunk_bytes: int) -> int:
    return -(-field_bytes(shape) // chunk_bytes)


def io_bytes(ops) -> int:
    """Raw plus stream bytes of every chunk the ops moved."""
    return sum(o.raw_bytes + o.stream_bytes for o in ops)


def roofline_pct(work_bytes: float, device_s: float, device_kind: str):
    """100 x (work / peak HBM bandwidth) / device seconds; None where the
    op ran no program in the window."""
    if device_s <= 0 or work_bytes <= 0:
        return None
    least_s = work_bytes / peaks.peaks(device_kind)["hbm_bytes_per_s"]
    return 100.0 * least_s / device_s


def rate_GBps(ops, window_s: float):
    if not ops or window_s <= 0:
        return None
    return sum(o.raw_bytes for o in ops) / window_s / 1e9


def ratio(ops):
    stream = sum(o.stream_bytes for o in ops)
    if not stream:
        return None
    return sum(o.raw_bytes for o in ops) / stream
