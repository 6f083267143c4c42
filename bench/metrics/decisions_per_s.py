def read(ctx):
    """Decisions completed in the window over its length (1/s)."""
    w = ctx.window
    done = sum(1 for s in ctx.samples
               if s.result is not None and not s.result.shed
               and not s.result.expired and s.t_done is not None
               and w.t0 <= s.t_done <= w.t1)
    return done / ctx.seconds if done else None
