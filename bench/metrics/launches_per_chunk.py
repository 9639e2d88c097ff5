"""Facade and fused runtime: device program executions in the traced
window per chunk the window's ops moved (from the trace's `XLA Modules`
line). Read for every `launches_per_chunk.<cell family>` metric."""


def read(ctx):
    chunks = sum(o.chunks for o in ctx.ops)
    if ctx.trace is None or not chunks:
        return None
    return ctx.trace.executions() / chunks
