"""Capture a profiler trace of the window and reduce it to numbers.

`capture` records the window with ``jax.profiler`` (host TraceMe spans,
no Python tracer).  `load` reads the ``.xplane.pb`` into plain lists of
events, the form the committed test fixture keeps.  `reduce` works on
those lists alone:

* the window is the host span ``bench.window`` that the harness opens at
  the window's start and closes at its end;
* a device's busy time is the union of its op intervals inside the window,
  averaged over the devices; idle share is 1 - busy / window;
* the device ops that took most time, and the longest idle gaps, each
  named by the innermost host span around the gap's middle.
"""
from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
# lines of a device plane that hold the XLA ops it ran (one per op)
OP_LINES = ("XLA Ops",)
# host lines of Python threads: what the program's own code was doing
PYTHON_LINES = ("python",)
# planes of the chips themselves ("/device:TPU:0"), not the profiler's
# custom planes ("/device:CUSTOM:Megascale Trace")
DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")


def op_name(hlo: str) -> str:
    """``%fused_score_select_pallas.1 = (s32[8,1]...) custom-call(...)`` ->
    ``fused_score_select_pallas.1``: the op's name without its HLO text."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def capture(log_dir: str):
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    return jax.profiler.trace(log_dir, profiler_options=opts)


def load(log_dir: str) -> dict:
    """Events of the newest ``.xplane.pb`` under ``log_dir``:
    ``{"device": {plane: [[name, start_ns, dur_ns], ...]},
       "host": [[thread, name, start_ns, dur_ns], ...]}``."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(paths[-1])
    out = {"device": {}, "host": []}
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = []
            for line in plane.lines:
                if line.name in OP_LINES:
                    evs += [[op_name(e.name), float(e.start_ns),
                             float(e.duration_ns)] for e in line.events]
            out["device"][plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[line.name, e.name, float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events]
    return out


def _union(intervals: list) -> float:
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def window_of(events: dict) -> tuple:
    spans = [(s, s + d) for _, name, s, d in events["host"]
             if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")
    return max(spans, key=lambda x: x[1] - x[0])


def _clip(evs: list, w0: float, w1: float) -> list:
    out = []
    for name, s, d in evs:
        a, b = max(s, w0), min(s + d, w1)
        if b > a:
            out.append((name, a, b))
    return out


def reduce(events: dict, top: int = 10) -> dict:
    """busy_s, window_s, idle_share, per-op totals and the breakdown."""
    w0, w1 = window_of(events)
    window_ns = w1 - w0
    busy, totals, gaps = [], {}, []
    planes = sorted(events["device"])
    for plane in planes:
        ops = _clip(events["device"][plane], w0, w1)
        busy.append(_union([(a, b) for _, a, b in ops]))
        for name, a, b in ops:
            totals[name] = totals.get(name, 0.0) + (b - a)
        edge = w0
        for _, a, b in sorted(ops, key=lambda x: x[1]):
            if a > edge:
                gaps.append((edge, a))
            edge = max(edge, b)
        if w1 > edge:
            gaps.append((edge, w1))
    n_dev = max(len(planes), 1)
    busy_ns = sum(busy) / n_dev
    by_what: dict = {}
    for (a, b), what in zip(gaps, _innermost(events["host"],
                                             [0.5 * (a + b) for a, b in gaps])):
        by_what[what] = by_what.get(what, 0.0) + (b - a) / n_dev
    device_ops = sorted(totals.items(), key=lambda x: -x[1])
    return dict(
        window_s=window_ns * 1e-9,
        busy_s=busy_ns * 1e-9,
        idle_share=1.0 - busy_ns / window_ns if window_ns > 0 else None,
        op_totals_s={k: v * 1e-9 / n_dev for k, v in totals.items()},
        op_counts=_counts(events, w0, w1),
        breakdown=dict(
            device_ops=[[k, v * 1e-9 / n_dev] for k, v in device_ops[:top]],
            idle_gaps=[[k, v * 1e-9] for k, v in
                       sorted(by_what.items(), key=lambda x: -x[1])[:top]],
        ),
    )


def _innermost(host: list, points: list) -> list:
    """For each time in ``points``, the name of the shortest span of a
    Python thread around it ("no host span" where none is; where the trace
    names no Python thread, of any thread): one sweep over the spans
    sorted by start, with a heap of the open ones keyed by duration."""
    import heapq

    py = [h for h in host if h[0] in PYTHON_LINES]
    spans = sorted((s, s + d, d, name) for _, name, s, d in (py or host)
                   if name != WINDOW_SPAN)
    order = sorted(range(len(points)), key=lambda i: points[i])
    out = ["no host span"] * len(points)
    heap: list = []
    k = 0
    for i in order:
        m = points[i]
        while k < len(spans) and spans[k][0] <= m:
            s, e, d, name = spans[k]
            heapq.heappush(heap, (d, e, name))
            k += 1
        while heap and heap[0][1] < m:
            heapq.heappop(heap)
        if heap:
            out[i] = heap[0][2]
    return out


def _counts(events: dict, w0: float, w1: float) -> dict:
    out: dict = {}
    for evs in events["device"].values():
        for name, s, d in evs:
            if w0 <= s + 0.5 * d <= w1:
                out[name] = out.get(name, 0) + 1
    return out


def kernel_time(reduced: dict, prefix: str) -> tuple:
    """(seconds, calls) of the device ops whose name starts with
    ``prefix`` (a Pallas kernel's op carries its kernel function's name,
    with a numeric suffix when it recurs)."""
    secs = sum(v for k, v in reduced["op_totals_s"].items()
               if k.startswith(prefix))
    calls = sum(v for k, v in reduced["op_counts"].items()
                if k.startswith(prefix))
    return secs, calls


def trim(events: dict, max_events: int = 2000) -> dict:
    """A small copy of ``events`` for a test fixture: the window span and
    the first ``max_events`` events of each device and of the host."""
    w0, w1 = window_of(events)
    dev = {p: sorted(e, key=lambda x: x[1])[:max_events]
           for p, e in events["device"].items()}
    last = max((e[1] + e[2] for evs in dev.values() for e in evs), default=w1)
    host = [h for h in events["host"] if h[1] != WINDOW_SPAN and h[2] <= last]
    host = sorted(host, key=lambda x: x[2])[:max_events]
    host.append(["main", WINDOW_SPAN, w0, min(w1, last) - w0])
    return {"device": dev, "host": host}

