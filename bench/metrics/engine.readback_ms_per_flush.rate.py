from harness import flush_records


def read(ctx):
    """Engine readback ms per flush, summed over the flush's engine calls,
    from the flush records of the window's answers."""
    return flush_records.phase_ms(ctx, "engine.readback")
