"""Device: share of the traced window, in %, in which no operation ran on
the chip, averaged over the chips (1 - busy / window). Read for every
`idle_share.<cell family>` metric."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
