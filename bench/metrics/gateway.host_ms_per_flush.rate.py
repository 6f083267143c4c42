from harness import readers


def read(ctx):
    """Host encode + merge ms per flush, from the gateway's raw phase
    histograms (not the rescaled spans)."""
    return readers.ms_per_flush(ctx, ("encode", "merge"))
