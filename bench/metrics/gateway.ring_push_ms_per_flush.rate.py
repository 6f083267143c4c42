from harness import flush_records


def read(ctx):
    """Telemetry-ring push ms per flush (column build and push, summed
    over the flush's completions), from the flush records of the window's
    answers."""
    return flush_records.phase_ms(ctx, "gateway.ring_push")
