def read(ctx):
    """Real rows routed per front-end flush in the window."""
    w = ctx.window
    rows = sum(1 for s in ctx.samples
               if s.result is not None and not s.result.shed
               and not s.result.expired)
    return rows / w.frontend_flushes if w.frontend_flushes else None
