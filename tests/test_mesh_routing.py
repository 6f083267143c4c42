"""Mesh-sharded routing engine: parity with the single-device engine and
shard-merge edge cases.

The acceptance invariant is **argmax identity**: for any fleet, telemetry,
load vector, staleness ages and fault mask, the sharded engine picks the
exact same (server_idx, tool_idx) as `BatchRoutingEngine` for every one of
the seven algorithms — and in fact the fused scores are bit-identical (the
merge reproduces the single-device candidate order, see
core.mesh_routing's module docstring).

Shard-merge edge cases pinned here:
  * fleet size not divisible by the shard count (pad servers/tools),
  * a shard whose servers are all dead/masked (its candidates lose to
    every live shard's),
  * top_k larger than a shard's tool slice (the shard contributes its
    whole slice; the merged top-k is still the global top-k).

With >= 2 jax devices (CI runs one step with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``) the same checks
run through the real ``shard_map`` mesh path; on one device the engine
emulates the shard structure with identical math, so the invariants are
exercised either way.
"""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

import jax

from repro.core import dataset, routing
from repro.core.batch_routing import BatchRoutingEngine
from repro.core.latency import OFFLINE_MS
from repro.core.mesh_routing import (
    ShardedRoutingEngine,
    TiledFleetIndex,
    make_shard_plan,
)
from repro.core.routing import RoutingConfig
from repro.traffic import replica_fleet

ALGOS = sorted(routing.ALGORITHMS)
POOL = dataset.build_server_pool(seed=0)
QUERY_TEXTS = [
    "search the web for the latest news",
    "refactor this function in the repository",
    "what is the weather forecast tomorrow",
]


def _materialize(seed, n_servers, identical, all_offline, mask_kind):
    """Fleet + telemetry + load + age + failed-mask from one seed (the
    same construction as tests/test_parity_prop.py)."""
    rng = np.random.default_rng(seed)
    if identical:
        servers = replica_fleet(n_servers)
    else:
        pick = rng.choice(len(POOL), size=n_servers, replace=False)
        servers = [POOL[i] for i in pick]
    T = 24
    hist = rng.uniform(5.0, 400.0, size=(n_servers, T)).astype(np.float32)
    if all_offline:
        hist[:, -1] = OFFLINE_MS + 100.0
    else:
        down = rng.random(n_servers) < 0.3
        hist[down, -1] = OFFLINE_MS + 50.0
    load = (rng.random(n_servers) * 2.0).astype(np.float32)
    age = (rng.random(n_servers) * 600.0).astype(np.float32)
    if mask_kind == "none":
        mask = None
    elif mask_kind == "all":
        mask = np.ones(n_servers, bool)
    else:
        mask = rng.random(n_servers) < 0.4
    return servers, hist, load, age, mask


def _assert_same(d0, d1, ctx: str):
    np.testing.assert_array_equal(
        d0.server_idx, d1.server_idx, err_msg=f"{ctx}: server_idx"
    )
    np.testing.assert_array_equal(
        d0.tool_idx, d1.tool_idx, err_msg=f"{ctx}: tool_idx"
    )
    np.testing.assert_array_equal(
        d0.fused, d1.fused, err_msg=f"{ctx}: fused scores"
    )


@settings(max_examples=10, deadline=None)
@given(
    seed=st.integers(0, 2**31 - 1),
    algo=st.sampled_from(ALGOS),
    n_servers=st.integers(2, 8),
    n_shards=st.integers(1, 5),
    identical=st.booleans(),
    all_offline=st.booleans(),
    mask_kind=st.sampled_from(["none", "some", "all"]),
)
def test_sharded_matches_batch_engine(
    seed, algo, n_servers, n_shards, identical, all_offline, mask_kind
):
    """Property: sharded == single-device for all seven algorithms, any
    (fleet, shard count) split — including indivisible ones — with load
    vectors, staleness ages and fault masks in play."""
    servers, hist, load, age, mask = _materialize(
        seed, n_servers, identical, all_offline, mask_kind
    )
    cfg = RoutingConfig(top_s=min(4, n_servers), top_k=5)
    base = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=False)
    d0 = base.route_texts(QUERY_TEXTS, hist, load, age, mask)
    sh = ShardedRoutingEngine(
        servers, cfg, algo=algo, n_shards=n_shards,
        use_kernels=False, index=base.index,
    )
    d1 = sh.route_texts(QUERY_TEXTS, hist, load, age, mask)
    _assert_same(
        d0, d1,
        f"{algo} seed={seed} n={n_servers} J={n_shards} "
        f"identical={identical} offline={all_offline} mask={mask_kind}",
    )


def test_indivisible_fleet_all_shard_counts():
    """7 servers across J=1..7 shards: every split (most leave a ragged
    tail shard) reproduces the single-device decision."""
    servers, hist, load, age, mask = _materialize(11, 7, True, False, "some")
    cfg = RoutingConfig(top_s=3, top_k=4)
    for algo in ("sonar", "sonar_lb", "sonar_ft"):
        base = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=False)
        d0 = base.route_texts(QUERY_TEXTS, hist, load, age, mask)
        for n_shards in range(1, 8):
            sh = ShardedRoutingEngine(
                servers, cfg, algo=algo, n_shards=n_shards,
                use_kernels=False, index=base.index,
            )
            d1 = sh.route_texts(QUERY_TEXTS, hist, load, age, mask)
            _assert_same(d0, d1, f"{algo} J={n_shards}")


def test_whole_shard_dead():
    """Mask out every server of shard 0 (and separately of the last
    shard): the winner must come from a live shard, identically to the
    single-device masked argmax."""
    n, n_shards = 8, 4
    servers = replica_fleet(n)
    rng = np.random.default_rng(3)
    hist = rng.uniform(5.0, 400.0, size=(n, 24)).astype(np.float32)
    cfg = RoutingConfig(top_s=4, top_k=5)
    base = BatchRoutingEngine(servers, cfg, algo="sonar_ft", use_kernels=False)
    sh = ShardedRoutingEngine(
        servers, cfg, algo="sonar_ft", n_shards=n_shards,
        use_kernels=False, index=base.index,
    )
    s_pad = -(-n // n_shards)
    for dead_shard in (0, n_shards - 1):
        mask = np.zeros(n, bool)
        mask[dead_shard * s_pad : (dead_shard + 1) * s_pad] = True
        d0 = base.route_texts(QUERY_TEXTS, hist, failed_mask=mask)
        d1 = sh.route_texts(QUERY_TEXTS, hist, failed_mask=mask)
        _assert_same(d0, d1, f"dead shard {dead_shard}")
        assert not np.isin(d1.server_idx, np.flatnonzero(mask)).any(), (
            "picked a server on the dead shard"
        )


def test_k_larger_than_shard_slice():
    """top_k (and top_s) exceed every shard's slice: shards contribute
    their whole slices and the merge still recovers the global top-k."""
    n = 6
    servers = replica_fleet(n)
    rng = np.random.default_rng(7)
    hist = rng.uniform(5.0, 400.0, size=(n, 24)).astype(np.float32)
    load = (rng.random(n) * 1.5).astype(np.float32)
    cfg = RoutingConfig(top_s=6, top_k=12)   # > s_pad=1 and > t_pad per shard
    for algo in ("sonar", "sonar_lb"):
        base = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=False)
        d0 = base.route_texts(QUERY_TEXTS, hist, load)
        sh = ShardedRoutingEngine(
            servers, cfg, algo=algo, n_shards=6,
            use_kernels=False, index=base.index,
        )
        d1 = sh.route_texts(QUERY_TEXTS, hist, load)
        _assert_same(d0, d1, f"{algo} k>slice")


def test_shard_plan_shapes():
    """Plan invariants on a ragged split: contiguous server slices, tools
    grouped with their host shard, pads marked invalid."""
    idx = routing.ToolIndex(POOL)          # 15 servers, multi-tool
    plan = make_shard_plan(idx.tool_server, len(POOL), 4)
    assert plan.n_shards == 4 and plan.s_pad == 4
    # every real server appears exactly once
    real = plan.server_gid[plan.server_valid]
    assert sorted(real.tolist()) == list(range(15))
    # every real tool appears exactly once, on the shard of its host
    real_tools = plan.tool_gid[plan.tool_valid]
    assert sorted(real_tools.tolist()) == list(range(idx.n_tools))
    hosts = plan.tool_host_global[plan.tool_valid]
    shard_of_tool = np.repeat(np.arange(4), plan.t_pad).reshape(
        4, plan.t_pad
    )[plan.tool_valid]
    assert np.array_equal(hosts // plan.s_pad, shard_of_tool)
    # shard counts clamp to the fleet size
    assert make_shard_plan(idx.tool_server, 15, 99).n_shards == 15


def test_tiled_index_matches_densified():
    """TiledFleetIndex routes identically to the densified expansion of
    itself (template-compact telemetry included)."""
    n_servers = 60
    tmap = np.arange(n_servers) % len(POOL)
    idx = TiledFleetIndex(POOL, tmap)
    dense = idx.densify()
    rng = np.random.default_rng(5)
    m_t = 6
    tel_map = (np.arange(n_servers) * 5) % m_t
    compact = rng.uniform(5.0, 400.0, size=(m_t, 24)).astype(np.float32)
    load = (rng.random(n_servers) * 2.0).astype(np.float32)
    mask = rng.random(n_servers) < 0.2
    cfg = RoutingConfig(top_s=5, top_k=8)
    for algo in ("sonar", "sonar_lb", "sonar_ft"):
        base = BatchRoutingEngine([], cfg, algo=algo, use_kernels=False,
                                  index=dense)
        d0 = base.route_texts(QUERY_TEXTS, compact[tel_map], load,
                              failed_mask=mask)
        sh = ShardedRoutingEngine(cfg=cfg, algo=algo, n_shards=5,
                                  use_kernels=False, index=idx)
        d1 = sh.route_texts(QUERY_TEXTS, server_load=load, failed_mask=mask,
                            telemetry_templates=(compact, tel_map))
        _assert_same(d0, d1, f"tiled {algo}")


def test_kernel_path_parity():
    """The Pallas fused-selection kernel (interpret mode on CPU) closes
    the merged candidate set identically to the jnp oracle."""
    servers, hist, load, age, mask = _materialize(23, 6, True, False, "some")
    cfg = RoutingConfig(top_s=4, top_k=5)
    base = BatchRoutingEngine(servers, cfg, algo="sonar_ft",
                              use_kernels=False)
    d0 = base.route_texts(QUERY_TEXTS, hist, load, age, mask)
    sh = ShardedRoutingEngine(
        servers, cfg, algo="sonar_ft", n_shards=3,
        use_kernels=True, interpret=True, index=base.index,
    )
    d1 = sh.route_texts(QUERY_TEXTS, hist, load, age, mask)
    np.testing.assert_array_equal(d0.server_idx, d1.server_idx)
    np.testing.assert_array_equal(d0.tool_idx, d1.tool_idx)


@pytest.mark.parametrize("n_shards,shared_row", [(1, False), (3, False),
                                                 (4, True)])
def test_tiled_stage1_stream_matches_jnp_engine(n_shards, shared_row):
    """Tiled SONAR-FT over 3,000 replicas with a down rack of 40: the
    kernel path, whose stage 1 streams the template scores
    (`ops.stage1_select`, interpret mode), decides exactly as the jnp path
    (`lax.top_k`), and ``engine_stage1_streamed_per_call`` reads n_q x
    replicas there and 0 on the jnp path and on a dense index."""
    n = 3000
    idx = TiledFleetIndex(POOL, np.arange(n) % len(POOL),
                          weights_dtype="bfloat16")
    rng = np.random.default_rng(n_shards)
    tel_map = (np.arange(n) * 2654435761) % 16
    compact = rng.uniform(5.0, 400.0, size=(16, 64)).astype(np.float32)
    load = (rng.random(n) * 0.9).astype(np.float32)
    texts = QUERY_TEXTS + ["find a flight to paris", "zzz qqq"]
    mask = np.zeros((1 if shared_row else len(texts), n), bool)
    mask[:, :40] = True
    mask[-1, 3] = False                        # a probe re-admits one
    if shared_row:
        mask = mask[0]
    cfg = RoutingConfig(top_s=8, top_k=8)
    decs, streamed = [], []
    for use_kernels in (False, True):
        eng = ShardedRoutingEngine(cfg=cfg, algo="sonar_ft",
                                   n_shards=n_shards, use_kernels=use_kernels,
                                   interpret=True, index=idx)
        decs.append(eng.route_texts(texts, server_load=load,
                                    failed_mask=mask,
                                    telemetry_templates=(compact, tel_map)))
        streamed.append(
            eng.registry.get("engine_stage1_streamed_per_call").value)
    _assert_same(decs[0], decs[1], "tiled stage 1 kernel vs jnp")
    np.testing.assert_array_equal(decs[0].expertise, decs[1].expertise)
    assert streamed == [0.0, float(len(texts) * n)]
    servers, hist, load, age, fmask = _materialize(3, 6, True, False, "some")
    dense = ShardedRoutingEngine(servers, cfg, algo="sonar_ft", n_shards=2,
                                 use_kernels=True, interpret=True)
    dense.route_texts(QUERY_TEXTS, hist, load, age, fmask)
    assert dense.registry.get("engine_stage1_streamed_per_call").value == 0.0


def test_per_query_telemetry_parity():
    """Per-query telemetry slabs/loads/ages/masks shard along axis 1."""
    servers, _, _, _, _ = _materialize(2, 6, True, False, "none")
    rng = np.random.default_rng(9)
    n, n_q = 6, len(QUERY_TEXTS)
    hist = rng.uniform(5.0, 400.0, size=(n_q, n, 24)).astype(np.float32)
    load = (rng.random((n_q, n)) * 2.0).astype(np.float32)
    age = (rng.random((n_q, n)) * 600.0).astype(np.float32)
    mask = rng.random((n_q, n)) < 0.3
    cfg = RoutingConfig(top_s=4, top_k=5)
    base = BatchRoutingEngine(servers, cfg, algo="sonar_ft",
                              use_kernels=False)
    d0 = base.route_texts(QUERY_TEXTS, hist, load, age, mask)
    sh = ShardedRoutingEngine(
        servers, cfg, algo="sonar_ft", n_shards=4,
        use_kernels=False, index=base.index,
    )
    d1 = sh.route_texts(QUERY_TEXTS, hist, load, age, mask)
    _assert_same(d0, d1, "per-query telemetry")


@pytest.mark.slow
@pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (XLA_FLAGS=--xla_force_host_platform_"
    "device_count=N); the emulated path covers the math on 1 device",
)
def test_shard_map_mesh_path():
    """With a real multi-device mesh, the shard_map path must reproduce
    the single-device engine bit-for-bit too."""
    from repro.launch.mesh import make_fleet_mesh

    n_dev = min(len(jax.devices()), 4)
    mesh = make_fleet_mesh(n_dev)
    servers, hist, load, age, mask = _materialize(31, 9, True, False, "some")
    cfg = RoutingConfig(top_s=4, top_k=5)
    for algo in ALGOS:
        base = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=False)
        d0 = base.route_texts(QUERY_TEXTS, hist, load, age, mask)
        sh = ShardedRoutingEngine(
            servers, cfg, algo=algo, n_shards=n_dev, mesh=mesh,
            use_kernels=False, index=base.index,
        )
        assert sh.mesh is not None
        d1 = sh.route_texts(QUERY_TEXTS, hist, load, age, mask)
        _assert_same(d0, d1, f"shard_map {algo}")
    # SONAR-GEO with an *active* RTT vector through the real mesh:
    # decisions argmax-identical; the fused score agrees to ~1 ulp (the
    # 4-term fusion may be FMA-contracted differently across programs —
    # see kernels/ref.py)
    rtt = np.linspace(0.0, 400.0, 9).astype(np.float32)
    base = BatchRoutingEngine(servers, cfg, algo="sonar_geo",
                              use_kernels=False)
    d0 = base.route_texts(QUERY_TEXTS, hist, load, client_rtt_ms=rtt)
    sh = ShardedRoutingEngine(
        servers, cfg, algo="sonar_geo", n_shards=n_dev, mesh=mesh,
        use_kernels=False, index=base.index,
    )
    d1 = sh.route_texts(QUERY_TEXTS, hist, load, client_rtt_ms=rtt)
    np.testing.assert_array_equal(d0.server_idx, d1.server_idx)
    np.testing.assert_array_equal(d0.tool_idx, d1.tool_idx)
    np.testing.assert_allclose(d0.fused, d1.fused, rtol=1e-6, atol=1e-7)


def test_tiled_platform_windows_and_overlay():
    """Tiled NetMCPPlatform: windows densify from template rows; a
    feed-forward observation copy-on-writes only the touched server; the
    compact fast path refuses once overlays exist."""
    from repro.traffic import mega_platform

    n = 50
    plat = mega_platform(n, n_tel_templates=8, seed=1, horizon_s=300.0)
    assert plat.n_servers == n
    assert plat.traces.shape[0] == 8            # compact, not [n, T]
    win = plat.latency_window(100, window=16)
    assert win.shape == (n, 16)
    compact, tmap = plat.compact_window(100, window=16)
    np.testing.assert_array_equal(win, compact[tmap])
    # ground truth matches the template row
    assert plat.latency_at(7, 100) == float(plat.traces[tmap[7], 100])
    # feed-forward: only server 7 diverges from its template sibling
    sibling = int(np.flatnonzero(tmap == tmap[7])[1])
    plat.record_observation(7, 100, 777.0)
    win2 = plat.latency_window(100, window=16)
    assert win2[7, -1] == 777.0
    assert win2[sibling, -1] == win[sibling, -1]
    with pytest.raises(AssertionError):
        plat.compact_window(100, window=16)
    # vectorized slabs agree with the scalar window
    slabs = plat.latency_windows(np.array([100, 40]), window=16)
    np.testing.assert_array_equal(slabs[0], win2)


def test_traffic_sim_on_tiled_platform():
    """The discrete-event simulator runs against a tiled platform (queues
    sized by n_servers, per-tick window cache) and conserves requests."""
    from repro.core.routing import make_router
    from repro.traffic import FleetTrafficSim, mega_platform, poisson_arrivals
    from repro.traffic.fleet import replica_fleet
    from repro.traffic.queueing import QueueConfig

    n = 40
    plat = mega_platform(n, n_tel_templates=8, seed=2, horizon_s=120.0)
    router = make_router("sonar_lb", replica_fleet(n))
    sim = FleetTrafficSim(
        plat, router, QueueConfig(capacity=2, base_service_ms=80.0), seed=0
    )
    arr = poisson_arrivals(jax.random.PRNGKey(3), rate=20.0, horizon_s=20.0)
    rep = sim.run(np.asarray(arr), ["search the web for news"])
    assert rep.n_offered == len(arr)
    assert rep.n_completed + rep.n_failed == rep.n_offered
    assert rep.n_completed > 0


def test_gateway_sharded_route_batch():
    """A sharded gateway serves batches through the mesh engine with the
    device-resident telemetry ring, and reports sane outcomes."""
    from repro.serving.gateway import SonarGateway, replica_pool

    pool = replica_pool([("qwen2-7b", "dense")] * 6)
    gw = SonarGateway(pool, use_kernels=True, algo="sonar_lb", shards=3)
    out = gw.route_batch(["summarize this document please"] * 12)
    assert len(out) == 12
    assert all(0 <= r.replica_idx < 6 for r in out)
    rep = gw.report()
    assert rep["n"] == 12
    # telemetry advanced once per completion, in place on device
    assert gw.telemetry.shape == (6, 64)


def test_gateway_sharded_matches_unsharded():
    """Same seed, same traffic: the sharded gateway picks the same
    replicas as the unsharded kernel gateway (argmax identity end to
    end, telemetry ring included)."""
    from repro.serving.gateway import SonarGateway, replica_pool

    archs = [("qwen2-7b", "dense"), ("yi-6b", "dense"),
             ("whisper-tiny", "audio"), ("internvl2-1b", "vlm"),
             ("minitron-4b", "dense")]
    reqs = ["summarize this document", "transcribe the audio recording",
            "describe the image contents", "write a haiku about queues"] * 3
    gw0 = SonarGateway(replica_pool(archs), use_kernels=True, algo="sonar")
    gw1 = SonarGateway(replica_pool(archs), use_kernels=True, algo="sonar",
                       shards=2)
    r0 = [r.replica_idx for r in gw0.route_batch(reqs)]
    r1 = [r.replica_idx for r in gw1.route_batch(reqs)]
    assert r0 == r1
