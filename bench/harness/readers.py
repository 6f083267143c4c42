"""Shared arithmetic of the metric readers under ``bench/metrics``.

Each reader is ``read(ctx) -> float | None``; None means the run has
nothing for it to read, and the metric is left out of the line.
"""
from __future__ import annotations



def phase_delta(ctx, names) -> tuple:
    """(flushes, total ms of the named phases) in the window, from the
    gateway's raw per-flush phase histograms (count and sum are exact)."""
    p0, p1 = ctx.phases
    flushes = p1["encode"][0] - p0["encode"][0]
    total = sum(p1[n][1] - p0[n][1] for n in names)
    return flushes, total


def ms_per_flush(ctx, names):
    flushes, total = phase_delta(ctx, names)
    return total / flushes if flushes > 0 else None


def idle_share(ctx):
    if ctx.trace is None or ctx.trace["idle_share"] is None:
        return None
    return 100.0 * ctx.trace["idle_share"]
