def read(ctx):
    """Process start to the first timed request (s): catalog, index and
    trace build, device placement and warm-up."""
    return ctx.setup_s
