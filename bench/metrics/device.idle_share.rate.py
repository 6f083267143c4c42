from harness import readers


def read(ctx):
    """Share of the traced window (%) in which no op ran on the device."""
    return readers.idle_share(ctx)
