from harness import readers


def read(ctx):
    """Engine dispatch ms per flush (every chunk's route call up to its
    host readback), from the gateway's raw phase histogram."""
    return readers.ms_per_flush(ctx, ("dispatch",))
