from harness import flush_records

PHASE = "gateway.health_mask"


def read(ctx):
    """SONAR-FT health-row ms per flush (the probe draws and the
    [rows, n_replicas] rows of every chunk, summed over the flush), from
    the flush records of the window's answers; nothing where no record
    holds the span."""
    recs = flush_records.in_window(ctx)
    if not any(PHASE in r.phases for r in recs):
        return None
    return flush_records.phase_ms(ctx, PHASE)
