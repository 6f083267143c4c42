"""Server timing from the program's flush records.

A routed answer (`ServeResult`) carries ``flush``, the record of the flush
that routed it: that flush's phase times (ms, summed over its engine calls
and completions) and ``gap_ms``, the front end's time from the end of the
previous flush to its start.  A program whose answers carry no record
reads as nothing: the metric is left out of the line.
"""
from __future__ import annotations


def in_window(ctx) -> list:
    """The distinct flush records of the routed answers that returned
    inside the window, in the order first met."""
    w = ctx.window
    seen, out = set(), []
    for s in ctx.samples:
        rec = getattr(s.result, "flush", None)
        if rec is None or s.t_done is None or not w.t0 <= s.t_done <= w.t1:
            continue
        if id(rec) not in seen:
            seen.add(id(rec))
            out.append(rec)
    return out


def phase_ms(ctx, name: str):
    """Mean ms per flush of the phase ``name`` (a profiler span name)."""
    recs = in_window(ctx)
    if not recs:
        return None
    return sum(r.phases.get(name, 0.0) for r in recs) / len(recs)


def gap_ms(ctx):
    """Mean front-end gap before a flush, over the flushes that had one."""
    gaps = [r.gap_ms for r in in_window(ctx) if r.gap_ms is not None]
    return sum(gaps) / len(gaps) if gaps else None
