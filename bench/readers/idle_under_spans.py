"""Device: share of the traced window, in %, in which the chip was idle
while a program leaf span of one kind was open.

Leaf spans and their kinds come from the program's own table
(`repro.obs.trace.LEAF_KINDS`); a program without it reads nothing. The
spans are mapped from the host perf_counter clock onto the profiler's
through the offset of the `bench.window` annotation, and met with the
idle gaps of the first chip. An idle instant under leaves of several
kinds counts once, for the first of `ATTRIBUTED`; `kind` None reads the
idle time under no such leaf. So the readings of `transfer`, `host` and
None add up to `idle_share` on one chip.
"""
from lib.xtrace import merge

ATTRIBUTED = ("transfer", "host")       # in order of precedence


def _intersect(a, b):
    """Intersection of two merged interval lists."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def _subtract(a, b):
    """`a` less `b`, both merged interval lists."""
    out = []
    for lo, hi in a:
        for b0, b1 in b:
            if b1 <= lo or b0 >= hi:
                continue
            if b0 > lo:
                out.append((lo, b0))
            lo = max(lo, b1)
            if lo >= hi:
                break
        if hi > lo:
            out.append((lo, hi))
    return out


def idle_by_kind(gaps, spans, kinds, offset):
    """{kind or None: idle seconds} over `ATTRIBUTED` and None, for idle
    `gaps` on the profiler clock and (name, t0, t1) `spans` on the host
    clock, `offset` apart; `kinds` maps a leaf span's name to its kind."""
    left = merge(gaps)
    out = {}
    for kind in ATTRIBUTED:
        cover = merge([(a + offset, b + offset) for n, a, b in spans
                       if kinds.get(n) == kind])
        hit = _intersect(left, cover)
        out[kind] = sum(b - a for a, b in hit)
        left = _subtract(left, cover)
    out[None] = sum(b - a for a, b in left)
    return out


def read(ctx, kind):
    if ctx.trace is None or not ctx.spans:
        return None
    try:
        from repro.obs.trace import LEAF_KINDS
    except ImportError:
        return None
    if not any(n in LEAF_KINDS for n, _, _ in ctx.spans):
        return None
    idle = idle_by_kind(ctx.trace.gaps(), ctx.spans, LEAF_KINDS,
                        ctx.trace.host_offset)
    return 100.0 * idle[kind] / ctx.trace.window_s
