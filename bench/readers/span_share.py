"""Share of the window, in %, covered by the program's `obs` spans of
the name `span` (their union), from the traced run."""


def read(ctx, span: str):
    if not ctx.spans:
        return None
    return 100.0 * ctx.span_union_s(span) / ctx.window_s
