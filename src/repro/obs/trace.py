"""Span-based request tracing with Chrome trace-event export.

One `SpanTracer` records the full lifecycle of every request through the
serving stack — admission, queue wait, encode, device dispatch, merge,
failover hops, completion — as *complete* ("X") trace events on a single
timeline, plus instant ("i") events for discrete occurrences (sheds,
expiries, chaos fault injections).  `to_chrome_trace()` emits the Trace
Event Format JSON that Perfetto / chrome://tracing load directly.

Design rules (the observability layer must cost ~nothing when off):

  * A disabled tracer's `span()` returns a cached no-op context manager
    and every `add_*` call is a single attribute check — no allocation,
    no clock read.  `NULL_TRACER` is the shared disabled singleton.
  * The event buffer is bounded (`max_events`); past the cap new events
    are dropped and counted (`n_dropped`), never silently lost — the
    export records the drop count in metadata.
  * Timestamps are **milliseconds** on the *caller's* clock: the
    virtual-time pump passes its virtual clock, the asyncio front-end
    its wall clock, the discrete-event simulator its sim clock.  Export
    converts to the microseconds Chrome expects.

`annotate(name, histogram)` is the one phase primitive of the served
path.  It times its block on `time.perf_counter`, adds the milliseconds to
a registry histogram (exact count and sum) and to the `FlushRecord` of the
flush being routed, and, while `enable_jax_annotations` is on, opens a
`jax.profiler.TraceAnnotation` of the same name, so a profile shows each
program phase on the device's clock.  Off, it costs two clock reads and
one ``observe``.
"""
from __future__ import annotations

import contextlib
import contextvars
import json
import time
from typing import Callable, Optional, Sequence

__all__ = [
    "NULL_TRACER",
    "FlushRecord",
    "SpanTracer",
    "annotate",
    "emit_chaos_events",
    "emit_flush_spans",
    "emit_request_spans",
    "enable_jax_annotations",
    "jax_annotations_enabled",
    "recording",
]


def _wall_ms() -> float:
    return 1000.0 * time.perf_counter()


class _NoopSpan:
    """Reusable no-op context manager for disabled tracers."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP_SPAN = _NoopSpan()


class _LiveSpan:
    """Context manager that records one X event on exit."""

    __slots__ = ("tracer", "name", "cat", "tid", "args", "t0")

    def __init__(self, tracer, name, cat, tid, args):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.tid = tid
        self.args = args
        self.t0 = 0.0

    def __enter__(self):
        self.t0 = self.tracer.clock_ms()
        return self

    def __exit__(self, *exc):
        self.tracer.add_span(
            self.name, self.t0, self.tracer.clock_ms(),
            cat=self.cat, tid=self.tid, args=self.args,
        )
        return False


class SpanTracer:
    """Bounded in-memory trace-event recorder (ms timestamps).

    Parameters
    ----------
    enabled : bool
        A disabled tracer records nothing and costs one attribute check
        per call site.
    clock_ms : callable, optional
        ``() -> float`` returning the current time in **ms**.  Default is
        a wall clock (`time.perf_counter`); drivers with their own
        timeline (virtual-time pump, discrete-event simulator) pass
        theirs so every span lands on one consistent axis.
    pid : str
        Process name grouping the events in the Perfetto UI.
    max_events : int
        Event-buffer bound; events past it are dropped and counted.
    """

    def __init__(
        self,
        enabled: bool = True,
        clock_ms: Optional[Callable[[], float]] = None,
        pid: str = "netmcp",
        max_events: int = 200_000,
    ):
        self.enabled = enabled
        self.clock_ms = clock_ms if clock_ms is not None else _wall_ms
        self.pid = pid
        self.max_events = int(max_events)
        self.events: list = []
        self.n_dropped = 0

    # -- recording -----------------------------------------------------------
    def _push(self, ev: dict) -> None:
        if len(self.events) >= self.max_events:
            self.n_dropped += 1
            return
        self.events.append(ev)

    def span(self, name: str, cat: str = "serving", tid=0,
             args: Optional[dict] = None):
        """Context manager timing a block on this tracer's clock."""
        if not self.enabled:
            return _NOOP_SPAN
        return _LiveSpan(self, name, cat, tid, args)

    def add_span(self, name: str, t0_ms: float, t1_ms: float, *,
                 cat: str = "serving", tid=0, pid: Optional[str] = None,
                 args: Optional[dict] = None) -> None:
        """Record one complete span with explicit [t0, t1] timestamps."""
        if not self.enabled:
            return
        ev = {
            "name": name, "cat": cat, "ph": "X",
            "ts": 1000.0 * t0_ms, "dur": 1000.0 * max(t1_ms - t0_ms, 0.0),
            "pid": pid or self.pid, "tid": tid,
        }
        if args:
            ev["args"] = args
        self._push(ev)

    def instant(self, name: str, t_ms: Optional[float] = None, *,
                cat: str = "event", tid=0, pid: Optional[str] = None,
                args: Optional[dict] = None) -> None:
        """Record an instant event (sheds, expiries, fault injections)."""
        if not self.enabled:
            return
        ev = {
            "name": name, "cat": cat, "ph": "i", "s": "t",
            "ts": 1000.0 * (self.clock_ms() if t_ms is None else t_ms),
            "pid": pid or self.pid, "tid": tid,
        }
        if args:
            ev["args"] = args
        self._push(ev)

    # -- export --------------------------------------------------------------
    def to_chrome_trace(self) -> dict:
        """Trace Event Format payload (Perfetto / chrome://tracing)."""
        meta = [{
            "name": "process_name", "ph": "M", "pid": self.pid, "tid": 0,
            "args": {"name": self.pid},
        }]
        payload = {
            "traceEvents": meta + list(self.events),
            "displayTimeUnit": "ms",
            "otherData": {
                "n_events": len(self.events),
                "n_dropped": self.n_dropped,
            },
        }
        return payload

    def write(self, path: str) -> None:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)

    def clear(self) -> None:
        self.events = []
        self.n_dropped = 0


NULL_TRACER = SpanTracer(enabled=False)


# ---------------------------------------------------------------------------
# Phases: histogram + flush record + jax.profiler annotation
# ---------------------------------------------------------------------------

_JAX_ANNOTATIONS = False


def enable_jax_annotations(on: bool = True) -> None:
    """Toggle the `jax.profiler.TraceAnnotation` that every `annotate`
    phase opens: the front end's ``frontend.flush``, ``frontend.submit``
    and ``frontend.resolve``; the gateway's ``gateway.encode``,
    ``gateway.dispatch``, ``gateway.merge`` and ``gateway.ring_push``; the
    engines' ``engine.upload``, ``engine.enqueue`` and ``engine.readback``;
    `ServeEngine`'s ``netmcp.prefill`` and ``netmcp.decode_step``.  On, an
    `xprof` profile captured around serving names each host gap after the
    program phase that was open."""
    global _JAX_ANNOTATIONS
    _JAX_ANNOTATIONS = bool(on)


def jax_annotations_enabled() -> bool:
    return _JAX_ANNOTATIONS


class FlushRecord:
    """Server timing of one flush, shared by the answers it routed.

    ``index`` is the flush's number (the parent id of its requests'
    spans); ``t_start_ms`` / ``t_end_ms`` its interval on the serving
    clock (the pump's virtual one, the asyncio front end's wall one);
    ``gap_ms`` the time from the end of the previous flush to its start
    where the batcher held requests when that flush ended (else None).
    ``spans`` holds each phase the flush ran as (name, start ms from the
    flush's start, ms); ``phases`` sums them by name (engine phases over
    the flush's chunks, the ring push over its completions).  ``gauges``
    holds the values the flush noted (`note`), such as the ejected
    replicas its decisions saw.  Make the record when the flush starts:
    offsets count from then.
    """

    __slots__ = ("index", "t_start_ms", "t_end_ms", "gap_ms", "spans",
                 "gauges", "_t0")

    def __init__(self, index: int, t_start_ms: float,
                 gap_ms: Optional[float] = None):
        self.index = index
        self.t_start_ms = t_start_ms
        self.t_end_ms = t_start_ms
        self.gap_ms = gap_ms
        self.spans: list = []
        self.gauges: dict = {}
        self._t0 = time.perf_counter()

    def add(self, name: str, t0_s: float, t1_s: float) -> None:
        self.spans.append((name, 1000.0 * (t0_s - self._t0),
                           1000.0 * (t1_s - t0_s)))

    @property
    def phases(self) -> dict:
        """Phase name -> ms, summed over the flush."""
        out: dict = {}
        for name, _, ms in self.spans:
            out[name] = out.get(name, 0.0) + ms
        return out


_FLUSH: contextvars.ContextVar = contextvars.ContextVar("netmcp_flush",
                                                        default=None)


@contextlib.contextmanager
def recording(rec: FlushRecord):
    """Make ``rec`` the flush that phases in this context add to, inside a
    ``frontend.flush`` profiler span."""
    with annotate("frontend.flush"):
        token = _FLUSH.set(rec)
        try:
            yield rec
        finally:
            _FLUSH.reset(token)


def note(**values) -> None:
    """Record named values (gauge readings) in the open flush's record;
    nothing where no flush is being recorded."""
    rec = _FLUSH.get()
    if rec is not None:
        rec.gauges.update(values)


class _Phase:
    __slots__ = ("name", "hist", "ms", "_t0", "_span")

    def __init__(self, name: str, histogram=None):
        self.name = name
        self.hist = histogram
        self.ms = 0.0
        self._span = None

    def __enter__(self):
        if _JAX_ANNOTATIONS:
            import jax

            self._span = jax.profiler.TraceAnnotation(self.name)
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.ms = 1000.0 * (t1 - self._t0)
        if self.hist is not None:
            self.hist.observe(self.ms)
        rec = _FLUSH.get()
        if rec is not None:
            rec.add(self.name, self._t0, t1)
        if self._span is not None:
            self._span.__exit__(*exc)
        return False


def annotate(name: str, histogram=None) -> _Phase:
    """Time a block as the phase ``name``: into ``histogram`` (ms) when
    given, into the current flush's record when one is open (see
    `recording`), and into the profiler when annotations are on.  The
    returned object holds the block's milliseconds in ``ms`` after it
    exits."""
    return _Phase(name, histogram)


# ---------------------------------------------------------------------------
# Structured emission helpers shared by the serving drivers
# ---------------------------------------------------------------------------

def emit_flush_spans(
    tracer: SpanTracer,
    rec: FlushRecord,
    rids: Sequence[int],
    *,
    tid=0,
) -> None:
    """Emit one flush's span tree: a parent ``flush`` span over the
    record's [t_start_ms, t_end_ms] and every measured phase at its
    measured offset from the flush's start, with its measured duration
    (engine phases nest inside ``gateway.dispatch``, ring pushes inside
    ``gateway.merge``).  The flush's self time is what its phases leave
    uncovered.  Where the pump's virtual service time (``service_ms``) is
    shorter than the measured work, phases are cut at the flush's end."""
    if not tracer.enabled:
        return
    t0, t1 = rec.t_start_ms, rec.t_end_ms
    tracer.add_span("flush", t0, t1, cat="serving", tid=tid,
                    args={"rids": list(rids), "batch": len(rids),
                          "flush": rec.index})
    for name, offset_ms, ms in rec.spans:
        a = min(t0 + offset_ms, t1)
        tracer.add_span(name, a, min(a + ms, t1), cat="serving", tid=tid,
                        args={"flush": rec.index})


def emit_request_spans(
    tracer: SpanTracer,
    rid: int,
    t_arrival_ms: float,
    t_routed_ms: float,
    t_done_ms: float,
    *,
    replica_idx: int = -1,
    flush_idx: Optional[int] = None,
) -> None:
    """Per-request lifecycle spans on the ``requests`` track: ``serve``
    (arrival -> completion) wrapping ``queue_wait`` (arrival -> flush
    start).  The remainder of ``serve`` is exactly the flush interval the
    request rode, whose phase spans `emit_flush_spans` records."""
    if not tracer.enabled:
        return
    args = {"rid": rid, "replica": replica_idx}
    if flush_idx is not None:
        args["flush"] = flush_idx
    tracer.add_span("serve", t_arrival_ms, t_done_ms, cat="request",
                    pid="requests", tid=rid, args=args)
    tracer.add_span("queue_wait", t_arrival_ms, t_routed_ms, cat="request",
                    pid="requests", tid=rid, args={"rid": rid})


def _mask_intervals(row) -> list:
    """[(start_step, end_step)] maximal runs of True in a bool vector."""
    out = []
    start = None
    for t, v in enumerate(row):
        if v and start is None:
            start = t
        elif not v and start is not None:
            out.append((start, t))
            start = None
    if start is not None:
        out.append((start, len(row)))
    return out


def emit_chaos_events(tracer: SpanTracer, schedule, dt_s: float) -> None:
    """Render a `repro.chaos.ChaosSchedule` onto the trace timeline.

    Every fault injection becomes visible structure: per-server ``down``
    spans (with an ``inject:down`` instant at onset), ``degraded`` spans
    where the latency inflation exceeds 1, and ``telemetry-stale`` spans
    for monitoring blackouts — all on a dedicated ``chaos`` process with
    one track per server, aligned with the serving/request spans.
    """
    if not tracer.enabled or schedule is None:
        return
    step_ms = 1000.0 * dt_s

    def spans(mask_row, name, server):
        for s, e in _mask_intervals(mask_row):
            tracer.add_span(
                name, s * step_ms, e * step_ms, cat="chaos",
                pid="chaos", tid=server, args={"server": server},
            )
            if name == "down":
                tracer.instant(
                    "inject:down", s * step_ms, cat="chaos",
                    pid="chaos", tid=server, args={"server": server},
                )

    for i in range(schedule.n_servers):
        spans(schedule.down[i], "down", i)
        spans(schedule.degrade[i] > 1.0, "degraded", i)
        spans(schedule.stale[i], "telemetry-stale", i)
