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
from repro.serving.gateway import SonarGateway, replica_pool
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


class _CountingRng:
    """Counts the uniforms a gateway's probe generator hands out."""

    def __init__(self, rng):
        self.rng, self.drawn = rng, 0

    def random(self, shape):
        out = self.rng.random(shape)
        self.drawn += out.size
        return out


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
    rng = gw._probe_rng = _CountingRng(gw._probe_rng)
    rec = obs_trace.FlushRecord(0, 0.0)
    with obs_trace.recording(rec):
        gw.route_batch(QUERIES)
    draws = rec.gauges["gateway_health_draws_per_flush"]
    assert draws == rng.drawn
    assert gw.obs.registry.get("gateway_health_draws_per_flush").value == draws
    if health:
        assert "gateway.health_mask" in rec.phases
        assert hist.count - before == len(QUERIES) // gw.lb_chunk
        assert rec.gauges["gateway_ejected"] == ejected
        assert rec.gauges["gateway_health_row_bytes_per_flush"] == len(QUERIES) * N
        assert draws == len(QUERIES) * ejected       # rows x ejected, not N
    else:
        assert "gateway.health_mask" not in rec.phases and hist.count == 0
        assert rec.gauges["gateway_health_row_bytes_per_flush"] == 0.0
        assert draws == 0.0


def _health_gateway(n, ejected, probe_prob=0.1):
    gw = SonarGateway(replica_pool([("yi-6b", "dense")] * n),
                      algo="sonar_ft", seed=11, probe_prob=probe_prob)
    gw.ejected[ejected] = True
    gw._probe_rng = _CountingRng(gw._probe_rng)
    return gw


def _health_rows(gw, rows, batched):
    """``rows`` requests' health rows, as one batched call or as that many
    scalar calls (a ``None`` row masks nothing)."""
    if batched:
        mask = gw._health_mask(rows)
        assert mask is None or mask.shape == (rows, gw.n_replicas)
        return np.zeros((rows, gw.n_replicas), bool) if mask is None else mask
    out = np.zeros((rows, gw.n_replicas), bool)
    for r in range(rows):
        row = gw._health_mask()
        assert row is None or row.shape == (gw.n_replicas,)
        if row is not None:
            out[r] = row
    return out


HEALTH_N = 32


@pytest.mark.parametrize("batched", [False, True], ids=["scalar", "batched"])
@pytest.mark.parametrize("n_ejected", [1, 7, HEALTH_N])
@pytest.mark.parametrize("rows", [1, 8, 64])
def test_health_rows_draw_only_for_ejected_replicas(rows, n_ejected, batched):
    """A health row draws one probe uniform per (request, ejected replica)
    and never masks a replica that is not ejected."""
    ejected = np.linspace(0, HEALTH_N - 1, n_ejected).astype(int)
    gw = _health_gateway(HEALTH_N, ejected)
    mask = _health_rows(gw, rows, batched)
    assert gw._probe_rng.drawn == rows * n_ejected
    assert not mask[:, ~gw.ejected].any()
    assert not mask.all(axis=1).any()


@pytest.mark.parametrize("batched", [False, True], ids=["scalar", "batched"])
@pytest.mark.parametrize("n_ejected", [1, 7])
def test_health_rows_readmit_each_ejected_replica_at_probe_prob(
        n_ejected, batched):
    """Over 10^4 requests each ejected replica is a candidate in a share
    ``probe_prob`` of them, within 5 binomial standard deviations."""
    rows, p = 10_000, 0.1
    gw = _health_gateway(HEALTH_N, np.arange(n_ejected) * 4, probe_prob=p)
    mask = _health_rows(gw, rows, batched)
    admitted = rows - mask[:, gw.ejected].sum(axis=0)
    z = np.abs(admitted - rows * p) / np.sqrt(rows * p * (1 - p))
    assert z.max() < 5.0, z


@pytest.mark.parametrize("batched", [False, True], ids=["scalar", "batched"])
@pytest.mark.parametrize("probe_prob", [0.0, 0.3])
def test_health_rows_never_mask_the_whole_fleet(probe_prob, batched):
    """With every replica ejected no request sees the whole fleet masked;
    with no probes at all that is no mask, for the scalar form too."""
    n, rows = 3, 256
    gw = _health_gateway(n, np.arange(n), probe_prob=probe_prob)
    mask = _health_rows(gw, rows, batched)
    assert not mask.all(axis=1).any()
    if probe_prob == 0.0:
        assert gw._health_mask() is None and gw._health_mask(rows) is None
    else:
        assert mask.any()


@pytest.mark.parametrize("n_requests", [None, 8])
def test_health_rows_without_ejection_are_none_and_draw_nothing(n_requests):
    gw = _health_gateway(HEALTH_N, [])
    assert gw._health_mask(n_requests) is None
    assert gw._probe_rng.drawn == 0


def test_tiled_fleet_needs_the_sharded_engine_and_template_telemetry():
    index = fleet.mega_fleet_index(60, POOL)
    with pytest.raises(ValueError, match="shards"):
        SonarGateway(index, algo="sonar_ft", use_kernels=True,
                     profiles=PALETTE, template_map=np.zeros(60, int))
    with pytest.raises(ValueError, match="template_map"):
        SonarGateway(index, algo="sonar_ft")
