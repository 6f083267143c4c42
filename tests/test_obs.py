"""Observability invariants (docs/observability.md).

Covers: Chrome-trace export validity, span nesting/monotonicity, phase
spans at their measured offsets (phases + flush self time == flush, and
queue_wait + flush == serve, per request), deterministic span replay from `MicroBatchPump.flush_log`,
metrics<->accounting conservation (property-tested against
`MicroBatcher.check_accounting`), jit-safe `DeviceRouteStats` (padding
exclusion + deferred drain), the unified `SonarGateway.report()` source
of truth, the audit tap's bit-exact score recomposition across all
algorithms (riding the parity-suite fixtures), simulator/chaos trace
emission, histogram quantile bounds, and the dashboard renderers.
"""
import asyncio
import io
import json
import types

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.chaos import build_schedule, standard_fault_mix
from repro.core import dataset, routing
from repro.core import latency as latlib
from repro.core.latency import OFFLINE_MS
from repro.core.platform import NetMCPPlatform
from repro.core.routing import RoutingConfig
from repro.obs import (
    AuditTap,
    DeviceRouteStats,
    Histogram,
    LiveDashboard,
    MetricsRegistry,
    Observability,
    render_dashboard,
)
from repro.obs.trace import SpanTracer, emit_chaos_events
from repro.serving.frontend import AsyncServingGateway
from repro.serving.gateway import SonarGateway, replica_pool
from repro.serving.microbatch import BatchingPolicy, MicroBatcher, MicroBatchPump
from repro.traffic import FleetTrafficSim, QueueConfig, poisson_arrivals, replica_fleet
from repro.traffic.source import LiveRequest, request_schedule

from repro.core import adaptive  # noqa: F401  registers sonar_adapt, so the
                                 # audit sweep below covers it deterministically

POOL = dataset.build_server_pool(seed=0)
ALGOS = sorted(routing.ALGORITHMS)
assert "sonar_adapt" in ALGOS
TEXTS = [
    "what is the latest news about the stock market today",
    "search the web for current weather information",
    "find recent articles about machine learning research",
    "look up live election results online",
]


def _make_gateway(n_replicas, algo, seed=0, obs=None):
    replicas = replica_pool([("yi-6b", "dense")] * n_replicas)
    profiles = [latlib.ideal_profile() for _ in range(n_replicas)]
    return SonarGateway(
        replicas, profiles=profiles, algo=algo, seed=seed,
        use_kernels=True, device_telemetry=True, obs=obs,
    )


@pytest.fixture(scope="module")
def pump_run():
    """One fully-instrumented pump replay shared by the trace tests."""
    obs = Observability(trace=True, jit_stats=True)
    gw = _make_gateway(3, "sonar_lb", obs=obs)
    schedule = request_schedule(
        "flash_crowd", jax.random.PRNGKey(0), 400.0, 0.25, TEXTS,
        deadline_ms=30.0, spike_factor=3.0,
    )
    pump = MicroBatchPump(gw, BatchingPolicy(
        max_batch=4, max_wait_ms=2.0, slack_ms=0.0, queue_limit=8,
        pad_batches=True,
    ))
    rep = pump.replay(schedule)
    assert rep.n_routed > 0 and rep.n_flushes > 0
    return obs, gw, pump, rep


# ---------------------------------------------------------------------------
# Chrome trace export
# ---------------------------------------------------------------------------

def _assert_valid_chrome_trace(payload):
    events = payload["traceEvents"]
    assert isinstance(events, list) and events
    assert payload["displayTimeUnit"] == "ms"
    for ev in events:
        assert isinstance(ev["name"], str) and ev["name"]
        assert ev["ph"] in {"X", "i", "C", "M"}
        if ev["ph"] == "M":
            continue
        assert isinstance(ev["ts"], (int, float))
        if ev["ph"] == "X":
            assert ev["dur"] >= 0.0
        if ev["ph"] == "i":
            assert ev["s"] == "t"
    assert any(ev["ph"] == "X" for ev in events)
    assert any(ev["ph"] == "M" for ev in events)


def test_chrome_trace_json_valid(pump_run, tmp_path):
    obs, _, _, _ = pump_run
    path = tmp_path / "trace.json"
    obs.tracer.write(str(path))
    payload = json.loads(path.read_text())
    _assert_valid_chrome_trace(payload)
    assert payload["otherData"]["n_events"] == len(obs.tracer.events)
    assert payload["otherData"]["n_dropped"] == 0


def test_tracer_disabled_and_bounded_buffer():
    off = SpanTracer(enabled=False)
    off.add_span("x", 0.0, 1.0)
    off.instant("y")
    with off.span("w"):
        pass
    assert off.events == []

    small = SpanTracer(enabled=True, clock_ms=lambda: 0.0, max_events=3)
    for i in range(5):
        small.instant(f"e{i}", 0.0)
    assert len(small.events) == 3 and small.n_dropped == 2
    assert small.to_chrome_trace()["otherData"]["n_dropped"] == 2
    small.clear()
    assert small.events == [] and small.n_dropped == 0


def test_chaos_events_render_mask_intervals():
    sched = types.SimpleNamespace(
        n_servers=2,
        down=np.array([[False, True, True, False], [False] * 4]),
        degrade=np.array([[1.0, 1.0, 2.5, 1.0], [1.0] * 4]),
        stale=np.array([[False] * 4, [True, True, False, False]]),
    )
    tr = SpanTracer(enabled=True, clock_ms=lambda: 0.0)
    emit_chaos_events(tr, sched, dt_s=0.5)
    by_name = {}
    for ev in tr.events:
        by_name.setdefault(ev["name"], []).append(ev)
    # server 0 down over steps [1, 3) at 500 ms/step -> [500, 1500] ms
    (down,) = by_name["down"]
    assert down["pid"] == "chaos" and down["tid"] == 0
    assert down["ts"] == 500.0 * 1000 and down["dur"] == 1000.0 * 1000
    (inj,) = by_name["inject:down"]
    assert inj["ph"] == "i" and inj["ts"] == down["ts"]
    (deg,) = by_name["degraded"]
    assert deg["ts"] == 1000.0 * 1000 and deg["dur"] == 500.0 * 1000
    (stale,) = by_name["telemetry-stale"]
    assert stale["tid"] == 1 and stale["ts"] == 0.0 and stale["dur"] == 1000.0 * 1000
    # a None schedule or disabled tracer is a no-op
    emit_chaos_events(tr, None, dt_s=0.5)
    n = len(tr.events)
    emit_chaos_events(SpanTracer(enabled=False), sched, dt_s=0.5)
    assert len(tr.events) == n


# ---------------------------------------------------------------------------
# Span nesting / tiling / e2e-latency identity
# ---------------------------------------------------------------------------

def _spans(events, name, **match):
    out = []
    for ev in events:
        if ev["name"] != name or ev["ph"] != "X":
            continue
        if all(ev.get("args", {}).get(k) == v for k, v in match.items()):
            out.append(ev)
    return out


TOP_PHASES = ("gateway.encode", "gateway.dispatch", "gateway.merge")
NESTED = {"engine.upload": "gateway.dispatch",
          "engine.enqueue": "gateway.dispatch",
          "engine.readback": "gateway.dispatch",
          "gateway.ring_push": "gateway.merge"}


def _inside(ev, parents):
    return any(p["ts"] - 1e-6 <= ev["ts"]
               and ev["ts"] + ev["dur"] <= p["ts"] + p["dur"] + 1e-6
               for p in parents)


def test_span_nesting_and_phase_tiling(pump_run):
    """Phases sit at their measured offsets in the flush: the flush's own
    phases are in order, inside it and non-overlapping, and their sum plus
    the flush's self time (what they leave uncovered) is its duration;
    engine phases nest in a dispatch, ring pushes in the merge."""
    obs, _, pump, rep = pump_run
    events = obs.tracer.events
    for fidx in range(rep.n_flushes):
        (flush,) = _spans(events, "flush", flush=fidx)
        rec = pump.flush_records[fidx]
        t0, t1 = flush["ts"], flush["ts"] + flush["dur"]
        assert np.isclose(flush["dur"] / 1000.0, rec.t_end_ms - rec.t_start_ms)
        top = sorted((e for ph in TOP_PHASES for e in _spans(events, ph, flush=fidx)),
                     key=lambda e: e["ts"])
        names = [e["name"] for e in top]
        assert names[0] == "gateway.encode" and names[-1] == "gateway.merge"
        assert set(names[1:-1]) == {"gateway.dispatch"}
        self_us, cur = 0.0, t0
        for ev in top:
            assert ev["ts"] >= cur - 1e-6 and ev["dur"] >= 0.0
            self_us += ev["ts"] - cur
            cur = ev["ts"] + ev["dur"]
        assert cur <= t1 + 1e-6
        self_us += t1 - cur
        assert np.isclose(sum(e["dur"] for e in top) + self_us, flush["dur"],
                          rtol=1e-9, atol=1e-3)
        # measured, not rescaled: each span is its phase's measured time
        for ph in TOP_PHASES:
            assert np.isclose(sum(e["dur"] for e in _spans(events, ph, flush=fidx)) / 1000.0,
                              rec.phases[ph], rtol=1e-9, atol=1e-6)
        for child, parent in NESTED.items():
            kids = _spans(events, child, flush=fidx)
            assert kids
            parents = _spans(events, parent, flush=fidx)
            assert all(_inside(k, parents) for k in kids)


def test_request_spans_sum_to_e2e_latency(pump_run):
    """Acceptance identity: per request, queue_wait + the flush it rode
    == serve, and both reproduce the measured end-to-end serve latency."""
    obs, _, pump, rep = pump_run
    events = obs.tracer.events
    routed = [r for r in rep.results if not (r.shed or r.expired)]
    assert routed
    for res in routed:
        (serve,) = [
            e for e in _spans(events, "serve")
            if e["tid"] == res.rid and e["pid"] == "requests"
        ]
        (wait,) = [
            e for e in _spans(events, "queue_wait") if e["tid"] == res.rid
        ]
        fidx = serve["args"]["flush"]
        assert res.flush is pump.flush_records[fidx]
        (flush,) = _spans(events, "flush", flush=fidx)
        total_ms = (wait["dur"] + flush["dur"]) / 1000.0
        assert np.isclose(total_ms, res.serve_ms, rtol=1e-9, atol=1e-6)
        assert np.isclose(serve["dur"] / 1000.0, res.serve_ms,
                          rtol=1e-9, atol=1e-6)
        # nesting: queue_wait starts with serve, ends at the flush start
        assert wait["ts"] == serve["ts"]
        assert np.isclose(wait["ts"] + wait["dur"], flush["ts"],
                          rtol=1e-9, atol=1e-3)
        assert wait["ts"] + wait["dur"] <= serve["ts"] + serve["dur"] + 1e-3
    # shed / expired requests are instants, not spans
    names = [e["name"] for e in events if e["ph"] == "i"]
    assert names.count("shed") == rep.n_shed
    assert names.count("expired") == rep.n_expired


def test_replay_spans_reproduces_live_trace(pump_run):
    obs, _, pump, _ = pump_run
    span_names = {"flush", "serve", "queue_wait", *TOP_PHASES, *NESTED}
    live = [e for e in obs.tracer.events if e["name"] in span_names]
    replayed = pump.replay_spans().events
    assert live == replayed
    # replay of a replay is byte-identical
    assert json.dumps(replayed) == json.dumps(pump.replay_spans().events)


def test_async_frontend_emits_the_same_span_taxonomy():
    async def drive():
        obs = Observability(trace=True)
        gw = _make_gateway(2, "sonar", obs=obs)
        srv = AsyncServingGateway(gw, BatchingPolicy(
            max_batch=2, max_wait_ms=1.0, queue_limit=8,
        ))
        await srv.start()
        res = await asyncio.gather(*[srv.submit(t) for t in TEXTS])
        await srv.close()
        return obs, res

    obs, res = asyncio.run(drive())
    assert all(not (r.shed or r.expired) for r in res)
    serve = _spans(obs.tracer.events, "serve")
    assert len(serve) == len(TEXTS)
    for r in res:
        (sp,) = [e for e in serve if e["tid"] == r.rid]
        (wait,) = [
            e for e in _spans(obs.tracer.events, "queue_wait")
            if e["tid"] == r.rid
        ]
        assert np.isclose(sp["dur"] / 1000.0, r.serve_ms,
                          rtol=1e-9, atol=1e-3)
        assert wait["dur"] <= sp["dur"] + 1e-3
    assert obs.registry.value("serving_offered_total") == len(TEXTS)


# ---------------------------------------------------------------------------
# Flush records: the server timing of each flush, on every answer
# ---------------------------------------------------------------------------

ENGINE_PHASES = ("engine.upload", "engine.enqueue", "engine.readback")


def _chunked_gateway(obs):
    """sonar_lb over three replicas with two-row chunks: every flush of
    four makes two engine calls."""
    replicas = replica_pool([("yi-6b", "dense")] * 3)
    return SonarGateway(
        replicas, profiles=[latlib.ideal_profile()] * 3, algo="sonar_lb",
        use_kernels=True, device_telemetry=True, lb_chunk=2, obs=obs,
    )


def _serve_burst(gw, frontend, n, straggler=False):
    """``n`` requests offered at once through ``frontend``, and with
    ``straggler`` one more after the gateway went idle; the results."""
    policy = BatchingPolicy(max_batch=4, max_wait_ms=1.0, queue_limit=64,
                            pad_batches=True)
    texts = [TEXTS[i % len(TEXTS)] for i in range(n)]
    if frontend == "pump":
        pump = MicroBatchPump(gw, policy)
        sched = [LiveRequest(rid=i, text=t, t_ms=0.0)
                 for i, t in enumerate(texts)]
        if straggler:
            sched.append(LiveRequest(rid=n, text=TEXTS[0], t_ms=60_000.0))
        return pump.replay(sched).results

    async def drive():
        srv = AsyncServingGateway(gw, policy)
        await srv.start()
        res = await asyncio.gather(*[srv.submit(t) for t in texts])
        if straggler:
            await asyncio.sleep(0.02)
            res.append(await srv.submit(TEXTS[0]))
        await srv.close()
        return res

    return asyncio.run(drive())


@pytest.mark.parametrize("frontend", ["pump", "async"])
def test_flush_records_account_every_phase(frontend):
    obs = Observability()
    gw = _chunked_gateway(obs)
    eng = gw.engine()
    calls = [0]
    route = eng.route

    def counted(*a, **kw):
        calls[0] += 1
        return route(*a, **kw)

    eng.route = counted
    results = _serve_burst(gw, frontend, 14, straggler=True)
    routed = [r for r in results if not (r.shed or r.expired)]
    assert len(routed) == 15 and all(r.flush is not None for r in routed)
    reg = obs.registry
    for ph in ("upload", "enqueue", "readback"):
        assert reg.get(f"engine_phase_{ph}_ms").count == calls[0]
    assert reg.get("gateway_phase_ring_push_ms").count == len(routed)
    recs = sorted({id(r.flush): r.flush for r in routed}.values(),
                  key=lambda r: r.index)
    assert [r.index for r in recs] == list(range(len(recs))) and len(recs) >= 4
    assert reg.get("gateway_phase_dispatch_ms").count == len(recs)
    for res in routed:
        assert (res.t_routed_ms, res.t_done_ms) == (res.flush.t_start_ms,
                                                    res.flush.t_end_ms)
    for rec in recs:
        p = rec.phases
        assert all(p[ph] > 0.0 for ph in ENGINE_PHASES)
        assert sum(p[ph] for ph in ENGINE_PHASES) <= p["gateway.dispatch"]
        assert 0.0 < p["gateway.ring_push"] <= p["gateway.merge"]
    # requests waited at the end of every burst flush but the last; the
    # straggler came to an idle gateway
    assert recs[0].gap_ms is None and recs[-1].gap_ms is None
    assert all(r.gap_ms is not None and r.gap_ms >= 0.0 for r in recs[1:-1])
    gaps = reg.get("serving_flush_gap_ms")
    assert gaps.count == len(recs) - 2
    assert np.isclose(gaps.total, sum(r.gap_ms for r in recs[1:-1]))


@pytest.fixture
def jax_annotations():
    from repro.obs import trace as obs_trace

    obs_trace.enable_jax_annotations(True)
    try:
        yield
    finally:
        obs_trace.enable_jax_annotations(False)


def test_phase_spans_reach_the_profiler_timeline(jax_annotations, tmp_path):
    """With annotations on, the phases are host spans of the profiler's
    own trace (the clock of the device ops), each inside its flush's
    ``frontend.flush`` span on the thread that routed the flush."""
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench"))
    from harness import tracefile

    gw = _chunked_gateway(Observability())
    _serve_burst(gw, "async", 4)            # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        results = _serve_burst(gw, "async", 12)
    n_flushes = len({id(r.flush) for r in results})
    host = tracefile.load(str(tmp_path))["host"]
    flushes = [(t, s, s + d) for t, name, s, d in host
               if name == "frontend.flush"]
    assert len(flushes) == n_flushes
    for name in ("gateway.encode", "engine.readback", "gateway.ring_push"):
        spans = [(t, s, s + d) for t, n, s, d in host if n == name]
        assert spans, name
        for t, a, b in spans:
            assert any(t == ft and fa <= a and b <= fb
                       for ft, fa, fb in flushes), name
    encodes = [s for t, n, s, d in host if n == "gateway.encode"]
    assert len(encodes) == n_flushes


# ---------------------------------------------------------------------------
# Metrics: conservation, registry semantics, histogram bounds
# ---------------------------------------------------------------------------

@settings(max_examples=20, deadline=None)
@given(seed=st.integers(0, 2**31 - 1))
def test_registry_conservation_matches_check_accounting(seed):
    """The registry counters satisfy the exact conservation law
    `MicroBatcher.check_accounting` enforces, over random offer/take
    interleavings with deadlines and queue overflow."""
    rng = np.random.default_rng(seed)
    reg = MetricsRegistry()
    b = MicroBatcher(
        BatchingPolicy(max_batch=4, max_wait_ms=5.0, queue_limit=6),
        registry=reg,
    )
    now, rid = 0.0, 0
    for _ in range(int(rng.integers(10, 60))):
        now += float(rng.exponential(2.0))
        if rng.random() < 0.7:
            deadline = (
                now + float(rng.uniform(0.0, 10.0))
                if rng.random() < 0.5 else None
            )
            b.offer(LiveRequest(rid=rid, text="q", t_ms=now,
                                deadline_ms=deadline), now)
            rid += 1
        else:
            b.take(now)
            b.take_expired()
    b.check_accounting()
    assert reg.value("serving_offered_total") == b.n_offered
    assert reg.value("serving_routed_total") == b.n_taken
    assert reg.value("serving_shed_total") == b.n_shed
    assert reg.value("serving_expired_total") == b.n_expired
    assert reg.value("serving_queue_depth") == b.n_pending
    assert reg.value("serving_offered_total") == (
        reg.value("serving_routed_total") + reg.value("serving_shed_total")
        + reg.value("serving_expired_total")
        + reg.value("serving_queue_depth")
    )


def test_pump_registry_matches_report(pump_run):
    obs, _, _, rep = pump_run
    reg = obs.registry
    assert reg.value("serving_offered_total") == rep.n_offered
    assert reg.value("serving_routed_total") == rep.n_routed
    assert reg.value("serving_shed_total") == rep.n_shed
    assert reg.value("serving_expired_total") == rep.n_expired
    assert reg.value("serving_flushes_total") == rep.n_flushes
    assert reg.get("serving_latency_ms").count == rep.n_routed


def test_gateway_report_reads_the_shared_registry(pump_run):
    obs, gw, _, rep = pump_run
    report = gw.report()
    assert report["n"] == rep.n_routed
    assert report["shed"] == rep.n_shed
    assert report["expired"] == rep.n_expired
    assert report["n"] == obs.registry.get("gateway_latency_ms").count
    assert report["in_flight"] == 0.0


def test_registry_bind_semantics(tmp_path):
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "req")
    c2 = reg.counter("x_total")
    assert c1 is c2
    with pytest.raises(TypeError):
        reg.gauge("x_total")
    reg.gauge("depth").set(3)
    reg.histogram("lat_ms").observe(12.5)
    snap = reg.snapshot()
    assert snap["x_total"]["type"] == "counter"
    assert snap["depth"]["type"] == "gauge"
    assert snap["lat_ms"]["type"] == "histogram"
    for key in ("count", "mean", "p50", "p99", "p999"):
        assert key in snap["lat_ms"]
    path = tmp_path / "metrics.json"
    reg.to_json(str(path), extra={"summary": {"ok": True}})
    payload = json.loads(path.read_text())
    assert payload["metrics"].keys() == snap.keys()
    assert payload["summary"] == {"ok": True}


def test_histogram_quantiles_within_bucket_bound():
    rng = np.random.default_rng(0)
    vals = rng.lognormal(mean=3.0, sigma=1.0, size=5000)
    h = Histogram("lat", "ms")
    h.observe_many(vals)
    ratio = 10.0 ** (1.0 / h.per_decade)      # one-bucket relative width
    assert h.count == vals.size
    assert np.isclose(h.mean, vals.mean())
    assert h.vmin == vals.min() and h.vmax == vals.max()
    for q in (0.50, 0.99, 0.999):
        exact = float(np.percentile(vals, 100.0 * q))
        got = h.quantile(q)
        assert exact / ratio <= got <= exact * ratio
        assert h.vmin <= got <= h.vmax
    empty = Histogram("none")
    assert empty.quantile(0.99) == 0.0
    assert empty.snapshot()["min"] == 0.0 and empty.snapshot()["max"] == 0.0
    # out-of-range observations land in the edge buckets, never lost
    h2 = Histogram("edge", lo=1.0, hi=10.0, per_decade=4)
    h2.observe_many([0.01, 0.5, 50.0, 1e9])
    assert h2.count == 4 and sum(h2.counts) == 4


# ---------------------------------------------------------------------------
# DeviceRouteStats: padding exclusion + deferred drain
# ---------------------------------------------------------------------------

def test_device_route_stats_excludes_padding_and_defers():
    import jax.numpy as jnp

    drs = DeviceRouteStats(4)
    idx = jnp.asarray([2, 2, 1, 3], jnp.int32)
    c = jnp.asarray([0.5, 0.7, 0.9, 99.0], jnp.float32)
    n = jnp.asarray([0.2, 0.4, 0.6, 99.0], jnp.float32)
    s = jnp.asarray([0.6, 0.8, 1.0, 99.0], jnp.float32)
    drs.accumulate(idx, c, n, s, n_real=3)      # last row is padding
    assert len(drs._pending) == 1               # O(1) append, no dispatch
    out = drs.fold(reset=False)
    assert len(drs._pending) == 0
    np.testing.assert_array_equal(out["picks"], [0.0, 1.0, 2.0, 0.0])
    assert out["n_routed"] == 3.0
    assert np.isclose(out["mean_expertise"], (0.5 + 0.7 + 0.9) / 3)
    assert np.isclose(out["mean_network"], (0.2 + 0.4 + 0.6) / 3)
    assert np.isclose(out["mean_fused"], (0.6 + 0.8 + 1.0) / 3)
    # reset=True zeroes the device buffer
    drs.fold(reset=True)
    assert drs.fold(reset=False)["n_routed"] == 0.0
    # n_real=None counts every row
    drs.accumulate(idx, c, n, s)
    assert drs.fold()["n_routed"] == 4.0


def test_device_route_stats_max_pending_backstop():
    import jax.numpy as jnp

    drs = DeviceRouteStats(2)
    drs.MAX_PENDING = 2                         # shrink the inline bound
    one = jnp.asarray([1], jnp.int32)
    f = jnp.asarray([1.0], jnp.float32)
    drs.accumulate(one, f, f, f)
    assert len(drs._pending) == 1
    drs.accumulate(one, f, f, f)                # hits the backstop: drains
    assert len(drs._pending) == 0
    assert drs.fold()["picks"][1] == 2.0


def test_pump_route_stats_count_real_rows_only(pump_run):
    """Device-side pick counts equal the host-side routed count even
    though every flush was padded (the n_real mask excludes pad rows)."""
    obs, _, _, rep = pump_run
    stats = obs.route_stats.fold(reset=False)
    assert stats["n_routed"] == rep.n_routed
    assert stats["picks"].sum() == rep.n_routed


def test_observability_bundle_toggles():
    off = Observability()
    assert not off.tracer.enabled and off.route_stats is None
    assert off.ensure_route_stats(8) is None
    off.drain_route_stats()                     # no-op without stats
    assert off.fold_route_stats() is None
    on = Observability(jit_stats=True, audit=True)
    drs = on.ensure_route_stats(8)
    assert drs is not None and drs.n_servers == 8
    assert on.ensure_route_stats(8) is drs      # cached per fleet size
    assert on.ensure_route_stats(16) is not drs
    assert on.audit_tap is not None


# ---------------------------------------------------------------------------
# Audit tap: bit-exact score recomposition (all algorithms)
# ---------------------------------------------------------------------------

def _audit_fixture(seed, mask_kind, n_servers=5):
    rng = np.random.default_rng(seed)
    pick = rng.choice(len(POOL), size=n_servers, replace=False)
    servers = [POOL[i] for i in pick]
    hist = rng.uniform(5.0, 400.0, (n_servers, 24)).astype(np.float32)
    hist[rng.random(n_servers) < 0.3, -1] = OFFLINE_MS + 50.0
    load = (rng.random(n_servers) * 2.0).astype(np.float32)
    age = (rng.random(n_servers) * 600.0).astype(np.float32)
    if mask_kind == "none":
        mask = None
    elif mask_kind == "all":
        mask = np.ones(n_servers, bool)
    else:
        mask = rng.random(n_servers) < 0.4
    rtt = (rng.random(n_servers) * 500.0).astype(np.float32)
    return servers, hist, load, age, mask, rtt


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    algo=st.sampled_from(ALGOS),
    mask_kind=st.sampled_from(["none", "some", "all"]),
)
def test_audit_recomposition_is_bit_exact(seed, algo, mask_kind):
    """`ScoreAudit.recompose()` rebuilds the exact score vector the argmax
    saw, and `winning_score()` equals `Decision.fused` with no tolerance —
    for every algorithm, on the parity-suite style fixtures."""
    servers, hist, load, age, mask, rtt = _audit_fixture(seed, mask_kind)
    router = routing.make_router(
        algo, servers, RoutingConfig(top_s=4, top_k=5)
    )
    tap = AuditTap()
    for q in TEXTS[:2]:
        d = router.select(
            q, hist, load, telemetry_age_s=age, failed_mask=mask,
            client_rtt_ms=rtt, audit=tap,
        )
        a = tap.last
        assert a is not None and a.algo == router.name
        assert (a.server_idx, a.tool_idx) == (d.server_idx, d.tool_idx)
        assert a.winning_score() == d.fused
        np.testing.assert_array_equal(a.recompose(), a.fused)
        terms = a.terms()
        assert set(terms) == {"expertise", "network", "load", "rtt"}
        total = sum(terms.values())
        if np.isfinite(d.fused):
            assert np.isclose(total, d.fused, rtol=1e-5, atol=1e-6)
        assert router.name in a.explain()
    assert len(tap.records) == 2


def test_audit_records_every_failover_hop():
    servers = replica_fleet(4)
    router = routing.make_router(
        "sonar_ft", servers, RoutingConfig(top_s=4, top_k=4)
    )
    rng = np.random.default_rng(0)
    hist = rng.uniform(5.0, 200.0, (4, 16)).astype(np.float32)
    tap = AuditTap()
    d, hops = router.select_failover(
        TEXTS[0], hist, np.zeros(4, np.float32),
        alive=np.zeros(4, bool), budget=2, audit=tap,
    )
    assert hops == 2 and len(tap.records) == 3
    # consecutive hops mask out the previous pick
    picked = [r.server_idx for r in tap.records]
    assert len(set(picked)) == 3
    for r in tap.records:
        assert r.winning_score() == r.fused[r.best]


def test_audit_tap_is_bounded():
    tap = AuditTap(max_records=2)
    servers = replica_fleet(3)
    router = routing.make_router(
        "sonar", servers, RoutingConfig(top_s=3, top_k=3)
    )
    hist = np.full((3, 8), 50.0, np.float32)
    for _ in range(4):
        router.select(TEXTS[0], hist, audit=tap)
    assert len(tap.records) == 2 and tap.n_dropped == 2
    tap.clear()
    assert tap.records == [] and tap.n_dropped == 0


def test_gateway_threads_audit_tap():
    obs = Observability(audit=True)
    gw = SonarGateway(
        replica_pool([("yi-6b", "dense")] * 3), algo="sonar", obs=obs
    )
    gw.route(TEXTS[0])
    a = obs.audit_tap.last
    assert a is not None
    np.testing.assert_array_equal(a.recompose(), a.fused)


# ---------------------------------------------------------------------------
# Simulator + chaos trace integration
# ---------------------------------------------------------------------------

def test_simulator_metrics_and_chaos_trace():
    n, horizon = 4, 120.0
    sched = build_schedule(
        standard_fault_mix(0.8, n, horizon), n, int(horizon), 1.0, seed=0
    )
    plat = NetMCPPlatform(
        replica_fleet(n),
        profiles=[latlib.ideal_profile() for _ in range(n)],
        scenario="ideal", seed=0, horizon_s=horizon, dt_s=1.0, chaos=sched,
    )
    obs = Observability(trace=True)
    sim = FleetTrafficSim(
        plat,
        routing.make_router("sonar_ft", plat.servers,
                            RoutingConfig(top_s=n, top_k=n)),
        QueueConfig(capacity=4, queue_limit=16, base_service_ms=200.0),
        retry_budget=2, seed=1, obs=obs,
    )
    arr = poisson_arrivals(jax.random.PRNGKey(0), 2.0, horizon)
    rep = sim.run(arr, TEXTS)
    reg = obs.registry
    assert reg.value("sim_offered_total") == rep.n_offered
    assert reg.value("sim_completed_total") == rep.n_completed
    assert reg.value("sim_failed_total") == rep.n_failed
    assert reg.value("sim_drops_total") == rep.n_drop_events
    assert reg.value("sim_hedges_total") == rep.n_hedges
    names = [e["name"] for e in obs.tracer.events if e["ph"] == "i"]
    assert reg.value("sim_crashes_total") == names.count("crash")
    assert reg.value("sim_drops_total") == names.count("drop")
    events = obs.tracer.events
    assert len(_spans(events, "serve")) == rep.n_completed
    # the fault schedule is rendered onto the chaos track
    assert sched.down.any()
    assert _spans(events, "down")
    assert any(
        e["name"] == "inject:down" and e["pid"] == "chaos" for e in events
    )
    _assert_valid_chrome_trace(obs.tracer.to_chrome_trace())


# ---------------------------------------------------------------------------
# Dashboard
# ---------------------------------------------------------------------------

def test_render_dashboard_panel(pump_run):
    obs, _, _, rep = pump_run
    stats = obs.route_stats.fold(reset=False)
    panel = render_dashboard(
        obs.registry.snapshot(), stats, title="obs test"
    )
    assert "obs test" in panel
    assert "offered / routed" in panel
    assert f"{rep.n_offered:.0f} / {rep.n_routed:.0f}" in panel
    assert "serve p50 / p99 / p999" in panel
    assert "replica" in panel                    # pick distribution rows
    assert "mean C / N / S" in panel
    # every line fits the fixed box width
    widths = {len(line) for line in panel.splitlines()}
    assert len(widths) == 1


def test_live_dashboard_repaints_in_place(pump_run):
    obs, _, _, _ = pump_run
    out = io.StringIO()
    dash = LiveDashboard(
        obs.registry, route_stats_fn=None, min_interval_s=60.0,
        stream=out, title="live",
    )
    assert dash.update(force=True)
    assert not dash.update()                     # throttled
    assert dash.update(force=True)
    text = out.getvalue()
    assert "live" in text
    assert "\x1b[" in text                       # ANSI in-place repaint
