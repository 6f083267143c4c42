"""The gateway over a template-tiled fleet with template telemetry.

A `SonarGateway` handed a `TiledFleetIndex` and a telemetry template map
holds no `Server` objects and no per-replica trace matrix; these tests pin
that it routes exactly as the gateway over the expanded fleet does, that
its picks are scalar `Router.select`'s, that the template-mapped ring holds
per-replica traces, and that the health rows it builds are timed and
counted per flush.
"""
import jax
import ml_dtypes
import numpy as np
import pytest

from repro.core import dataset
from repro.core import latency as latlib
from repro.core.mesh_routing import ShardedRoutingEngine
from repro.core.routing import RoutingConfig
from repro.obs import trace as obs_trace
from repro.serving.gateway import SonarGateway
from repro.traffic import fleet

POOL = dataset.build_server_pool(seed=0)
N = 3000
PALETTE = [
    latlib.outage_profile(probability=0.6, duration_min_s=20.0,
                          duration_max_s=60.0),
    latlib.ideal_profile(),
    latlib.high_jitter_profile(),
    latlib.outage_profile(probability=0.4, duration_min_s=20.0,
                          duration_max_s=60.0),
]
TMAP = fleet.telemetry_template_map(N, len(PALETTE))
CFG = RoutingConfig(top_s=8, top_k=8)
QUERIES = [
    "search the web for the latest news",
    "what is the weather forecast tomorrow",
    "find real-time information online",
    "refactor this function in the repository",
    "order a product from the amazon catalog",
    "run a sql query against the database",
    "get a stock quote",
    "look up current facts on the internet",
] * 8


DOWN = set(range(40))                  # every call fails: candidates of
                                       # each template (ties go to low ids)


def _down_executor(gw_of):
    """A call's latency: the replica's trace sample, and on a replica in
    ``DOWN`` at least the offline latency (its network telemetry stays
    its template's, so only the failure streaks find it)."""
    def call(idx, text):
        lat = gw_of().trace_at(idx)
        return max(lat, latlib.OFFLINE_MS) if idx in DOWN else lat
    return call


def _tiled(algo, **kw):
    index = fleet.mega_fleet_index(N, POOL, weights_dtype="bfloat16")
    return SonarGateway(index, profiles=PALETTE, template_map=TMAP, cfg=CFG,
                        seed=7, use_kernels=True, algo=algo, shards=1,
                        telemetry_dtype="bfloat16", eject_after=1, **kw)


def _dense(algo, **kw):
    servers = [POOL[i % len(POOL)] for i in range(N)]
    return SonarGateway(servers, profiles=PALETTE, template_map=TMAP,
                        cfg=CFG, seed=7, use_kernels=True, algo=algo,
                        device_telemetry=True, telemetry_dtype="bfloat16",
                        eject_after=1, **kw)


def _record_scalar_picks(gw, calls):
    """Wrap the gateway's engine: each call's picks beside scalar
    `Router.select` over the same ring, load and health row."""
    eng = gw.engine()
    orig = eng.route

    def route(batch, lat, load, *a, failed_mask=None, **kw):
        dec = orig(batch, lat, load, *a, failed_mask=failed_mask, **kw)
        calls.append((np.asarray(lat, np.float32), np.array(load),
                      None if failed_mask is None else failed_mask.copy(),
                      dec))
        return dec

    eng.route = route


def _pair(algo, down):
    if not down:
        return _tiled(algo), _dense(algo)
    box: dict = {}
    tiled = box["t"] = _tiled(algo, executor=_down_executor(lambda: box["t"]))
    dense = box["d"] = _dense(algo, executor=_down_executor(lambda: box["d"]))
    return tiled, dense


@pytest.mark.parametrize("algo,down", [("sonar_ft", False), ("sonar_lb", False),
                                       ("sonar_ft", True)])
def test_tiled_gateway_routes_as_dense_gateway_and_scalar_router(algo, down):
    """Also with replicas down behind healthy telemetry: the batch path
    calls the executor, and the health rows then eject stage-1
    candidates for good."""
    tiled, dense = _pair(algo, down)
    assert isinstance(tiled.engine(), ShardedRoutingEngine)
    assert tiled.replicas == [] and tiled.n_replicas == N
    calls: list = []
    _record_scalar_picks(tiled, calls)
    ejected_seen = 0
    for _ in range(12):
        a = tiled.route_batch(QUERIES)
        b = dense.route_batch(QUERIES)
        assert [r.replica_idx for r in a] == [r.replica_idx for r in b]
        assert [r.ok for r in a] == [r.ok for r in b]
        np.testing.assert_array_equal(tiled.ejected, dense.ejected)
        ejected_seen = max(ejected_seen, int(tiled.ejected.sum()))
    np.testing.assert_array_equal(np.asarray(tiled.telemetry),
                                  np.asarray(dense.telemetry))
    if algo == "sonar_ft":
        assert ejected_seen > 0            # the health rows were exercised
    if down:
        failed = np.flatnonzero(tiled.ejected)
        assert set(failed) & DOWN and not any(
            r.ok for r in tiled.stats if r.replica_idx in DOWN)
    # every engine call's picks are scalar Router.select's
    chunk = tiled.lb_chunk
    texts = [QUERIES[lo:lo + chunk] for lo in range(0, len(QUERIES), chunk)]
    masked = 0
    for k, (lat, load, mask, dec) in enumerate(calls):
        for j, q in enumerate(texts[k % len(texts)]):
            row = None if mask is None else mask[j]
            masked += row is not None and bool(row.any())
            d = tiled.router.select(q, lat, load, failed_mask=row)
            assert (d.server_idx, d.tool_idx) == (int(dec.server_idx[j]),
                                                   int(dec.tool_idx[j]))
            assert d.fused == pytest.approx(float(dec.fused[j]), abs=1e-6)
    assert masked > 0 or algo != "sonar_ft"


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_template_traces_equal_per_replica_traces(dtype):
    """Traces synthesized once per template and read through the map equal
    each replica's own trace built from its mapped profile (with its
    template's key), on ring init and after pushes."""
    n, history = 40, 16
    tmap = (np.arange(n) * 7) % len(PALETTE)
    servers = [POOL[i % len(POOL)] for i in range(n)]
    gw = SonarGateway(servers, profiles=PALETTE, template_map=tmap, seed=3,
                      history=history, algo="sonar_ft", telemetry_dtype=dtype)
    with pytest.raises(ValueError, match="device ring"):
        SonarGateway(servers, profiles=PALETTE, template_map=tmap,
                     device_telemetry=False)
    steps = latlib.trace_horizon_steps()
    keys = jax.random.split(jax.random.PRNGKey(3), len(PALETTE))[tmap]
    packed = latlib.pack_profiles([PALETTE[m] for m in tmap])
    per_replica = np.asarray(jax.jit(jax.vmap(
        lambda k, p: latlib.generate_trace(k, p, steps)))(keys, packed))
    assert gw.trace_rows.shape == (len(PALETTE), steps)
    np.testing.assert_array_equal(gw.traces, per_replica)
    ring = (lambda x: x) if dtype == "float32" else (
        lambda x: np.asarray(x, ml_dtypes.bfloat16).astype(np.float32))
    np.testing.assert_array_equal(gw.telemetry, ring(per_replica[:, :history]))
    for t, idx in enumerate([3, 3, 17, 0, 39]):
        gw._observe(idx, 1234.5)
        want = per_replica[:, history + t].copy()
        want[idx] = 1234.5
        np.testing.assert_array_equal(gw.telemetry[:, -1], ring(want))
    np.testing.assert_array_equal(gw.telemetry[:, :-5],
                                  ring(per_replica[:, 5:history]))


@pytest.mark.parametrize("algo,health", [("sonar_ft", True),
                                         ("sonar_lb", False)])
def test_health_rows_are_timed_and_counted_per_flush(algo, health):
    box: dict = {}
    gw = box["gw"] = _tiled(algo, executor=_down_executor(lambda: box["gw"]))
    gw.route_batch(QUERIES)                       # the down picks fail
    ejected = float(gw.ejected.sum())
    assert gw.report()["ejected"] == ejected and ejected > 0
    hist = gw.obs.registry.get("gateway_phase_health_ms")
    before = hist.count
    rec = obs_trace.FlushRecord(0, 0.0)
    with obs_trace.recording(rec):
        gw.route_batch(QUERIES)
    if health:
        assert "gateway.health_mask" in rec.phases
        assert hist.count - before == len(QUERIES) // gw.lb_chunk
        assert rec.gauges["gateway_ejected"] == ejected
        assert rec.gauges["gateway_health_row_bytes_per_flush"] == len(QUERIES) * N
    else:
        assert "gateway.health_mask" not in rec.phases and hist.count == 0
        assert rec.gauges["gateway_health_row_bytes_per_flush"] == 0.0


def test_tiled_fleet_needs_the_sharded_engine_and_template_telemetry():
    index = fleet.mega_fleet_index(60, POOL)
    with pytest.raises(ValueError, match="shards"):
        SonarGateway(index, algo="sonar_ft", use_kernels=True,
                     profiles=PALETTE, template_map=np.zeros(60, int))
    with pytest.raises(ValueError, match="template_map"):
        SonarGateway(index, algo="sonar_ft")
