"""Load loops: open loop and closed loop.

Every time here is the host's monotonic clock in ms.  An open-loop request
is timed from when it was due, so a late generator or a stalled event loop
shows in the latency it caused; the harness also reports how late it fired
each request.  A closed-loop client sends its next request as soon as the
last one returns.
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Optional

import numpy as np

GRACE_MS = 60_000.0     # how long past the window an answer is waited for
WINDOW_SPAN = "bench.window"


def now_ms() -> float:
    return 1000.0 * time.monotonic()


@dataclasses.dataclass
class Sample:
    """One request: when it was due (open loop) or sent (closed loop),
    when the harness fired it, when its answer returned, and the answer."""

    t_due: float
    t_fire: float
    t_done: Optional[float] = None
    result: object = None


@dataclasses.dataclass
class Window:
    t0: float
    t1: float
    samples: list
    first_flush: int            # index of the window's first recorded flush
    n_flushes: int              # flushes recorded up to the window's end
    frontend_flushes: int       # front-end flushes in the window
    phases0: dict
    phases1: dict
    clock_offset: float         # front-end clock minus the harness's
    compiles: int = 0
    drained_ms: float = 0.0     # when the last answer of the window came


class CompileCounter:
    """Counts programs JAX compiled or loaded: ``n`` while ``active`` (the
    window), ``total`` since it was made with their seconds, and ``hits``,
    those the persistent cache served."""

    def __init__(self):
        import jax

        self.active = False
        self.n = 0
        self.total = 0
        self.hits = 0
        self.seconds = 0.0

        def on(event, duration, **kw):
            if event.endswith("backend_compile_duration"):
                self.total += 1
                self.seconds += float(duration)
                if self.active:
                    self.n += 1
            elif event.endswith("cache_retrieval_time_sec"):
                self.hits += 1

        jax.monitoring.register_event_duration_secs_listener(on)


async def _warm(srv, texts: list, rounds: int, batch: int) -> None:
    """Fixed warm-up: ``rounds`` full flushes, each ``batch`` concurrent
    requests, then one flush of a single request.  An answer that never
    comes is left behind after the grace period (the window's judge
    counts what the window misses)."""
    k = 0
    for size in [batch] * rounds + [1]:
        tasks = [asyncio.ensure_future(srv.submit(texts[(k + i) % len(texts)]))
                 for i in range(size)]
        k += size
        done, pending = await asyncio.wait(tasks, timeout=GRACE_MS / 1000.0)
        for t in done:
            t.result()
        for t in pending:
            t.cancel()


def run_gateway(sut, spec: dict, texts: list, due_s: Optional[np.ndarray],
                seconds: float, warm_texts: list, counter: CompileCounter,
                trace_window=None) -> Window:
    """Drive the served gateway: warm up, then an open loop over ``due_s``
    (seconds from the window's start) or a closed loop of
    ``spec["clients"]`` clients, for ``seconds``."""
    batch = int(sut.cfg["batching"]["max_batch"])

    async def serve() -> Window:
        srv = sut.frontend()
        await srv.start()
        t_warm = now_ms()
        await _warm(srv, warm_texts, int(spec.get("warm_rounds", 8)), batch)
        sut.setup_phases["warm_s"] = (now_ms() - t_warm) / 1000.0
        rec = sut.recorder
        offset = srv.now_ms() - now_ms()
        first = len(rec.flushes)
        fl0 = srv.n_flushes
        ph0 = sut.phases()
        samples: list = []
        tracer = trace_window() if trace_window is not None else None
        span = None
        if tracer is not None:
            import jax

            tracer.__enter__()
            span = jax.profiler.TraceAnnotation(WINDOW_SPAN)
            span.__enter__()
        counter.active = True
        t0 = now_ms() + 1.0
        t1 = t0 + 1000.0 * seconds
        if due_s is not None:
            await _open_loop(srv, texts, t0 + 1000.0 * due_s, samples)
        else:
            await _closed_loop(srv, texts, int(spec["clients"]), t1, samples)
        counter.active = False
        end = now_ms()
        if tracer is not None:
            span.__exit__(None, None, None)
            tracer.__exit__(None, None, None)
        ph1 = sut.phases()
        fl1 = srv.n_flushes
        n_fl = len(rec.flushes)
        await srv.close(drain=True)
        w = Window(t0=t0, t1=t1, samples=samples, first_flush=first,
                   n_flushes=n_fl, frontend_flushes=fl1 - fl0, phases0=ph0,
                   phases1=ph1, clock_offset=offset, compiles=counter.n,
                   drained_ms=end)
        return w

    return asyncio.run(serve())


async def _open_loop(srv, texts: list, due: np.ndarray,
                     samples: list) -> None:
    tasks = []

    async def one(s: Sample, text: str) -> None:
        s.result = await srv.submit(text)
        s.t_done = now_ms()

    i, n = 0, len(due)
    while i < n:
        t = now_ms()
        if due[i] > t:
            await asyncio.sleep((due[i] - t) / 1000.0)
            continue
        while i < n and due[i] <= t:
            s = Sample(t_due=float(due[i]), t_fire=t)
            samples.append(s)
            tasks.append(asyncio.ensure_future(one(s, texts[i])))
            i += 1
    if tasks:
        deadline = max(float(due[-1]), now_ms()) + GRACE_MS
        await asyncio.wait(tasks, timeout=(deadline - now_ms()) / 1000.0)
        for task in tasks:
            if task.done():
                task.result()
            else:
                task.cancel()


async def _closed_loop(srv, texts: list, clients: int, t1: float,
                       samples: list) -> None:
    """Each client sends its next request when the last returns, until
    ``t1``.  An answer that has not come ``GRACE_MS`` after the window
    closed never comes: its sample stays unanswered and its client
    stops."""
    cursor = [0]
    deadline = t1 + GRACE_MS

    async def agent() -> None:
        while now_ms() < t1:
            k = cursor[0]
            cursor[0] += 1
            t = now_ms()
            s = Sample(t_due=t, t_fire=t)
            samples.append(s)
            try:
                s.result = await asyncio.wait_for(
                    srv.submit(texts[k % len(texts)]),
                    timeout=(deadline - t) / 1000.0)
            except asyncio.TimeoutError:
                return
            s.t_done = now_ms()

    await asyncio.gather(*[agent() for _ in range(clients)])
