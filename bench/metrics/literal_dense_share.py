"""Facade and fused runtime: the encode's literal checks that ran densely
over every value on the host, because the device pass kept fewer
literal candidates than its guard band found, in % of all its literal
checks: 100 `ceaz_literal_dense_checks_total` /
`ceaz_literal_checks_total` of the cell's side, from the program's
counters. Read for every `literal_dense_share.<cell family>` metric. A
program without the counters reads nothing."""
from lib import counters


def read(ctx):
    side = counters.side(ctx)
    checks = counters.total("ceaz_literal_checks_total", side=side)
    if side is None or not checks:
        return None
    dense = counters.total("ceaz_literal_dense_checks_total", side=side)
    return 100.0 * dense / checks
