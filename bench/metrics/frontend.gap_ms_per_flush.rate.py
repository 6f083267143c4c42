from harness import flush_records


def read(ctx):
    """Front-end ms from the end of one flush to the start of the next,
    where requests were waiting when the earlier one ended, from the flush
    records of the window's answers."""
    return flush_records.gap_ms(ctx)
