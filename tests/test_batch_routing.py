"""Batched routing engine: scalar parity, fused-selection kernel equivalence,
the vectorized episode driver, and the extended scenario registry."""
import numpy as np
import pytest

from repro.core import agent, dataset, latency as L, metrics, platform, routing
from repro.core.batch_routing import make_engine
from repro.core.routing import RoutingConfig
from repro.kernels import ops, ref

SERVERS = dataset.build_server_pool(seed=0)
QUERY_TEXTS = [q.text for q in dataset.build_query_dataset(n=64, seed=1)]
ALL_SCENARIOS = list(platform.SCENARIOS)
# sonar_lb with no server_load supplied must collapse to sonar exactly —
# including it here asserts the load term is a pure extension
ALGOS = ["rag", "rerank_rag", "prag", "sonar", "sonar_lb"]


# ---------------------------------------------------------------------------
# Fused selection kernel vs pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_q,n_t,k,per_q,rerank", [
    (5, 30, 10, False, False),
    (64, 300, 12, True, False),
    (8, 40, 6, False, True),
    (3, 7, 10, True, False),     # k > n_tools
    (130, 200, 5, True, False),  # query padding
])
def test_fused_select_kernel_matches_oracle(n_q, n_t, k, per_q, rerank):
    import jax.numpy as jnp

    rng = np.random.default_rng(n_q * 100 + n_t)
    sel = rng.standard_normal((n_q, n_t)).astype(np.float32) * 3
    sel = np.where(rng.random((n_q, n_t)) < 0.4, sel, -np.inf)
    val = (
        rng.standard_normal((n_q, n_t)).astype(np.float32) if rerank else sel
    )
    qos = (rng.random((n_q, n_t) if per_q else (n_t,)).astype(np.float32)) * 2 - 1
    got = ops.fused_select(
        jnp.asarray(sel), jnp.asarray(val), jnp.asarray(qos),
        k=k, alpha=0.5, beta=0.5,
    )
    want = ref.fused_select_ref(
        jnp.asarray(sel), jnp.asarray(val), jnp.asarray(qos),
        k=k, alpha=0.5, beta=0.5,
    )
    assert (np.asarray(got[0]) == np.asarray(want[0])).all()
    for g, w in zip(got[1:], want[1:]):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# Batched engine == scalar Router.select (argmax-identical)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scenario", ALL_SCENARIOS)
@pytest.mark.parametrize("algo", ALGOS)
def test_batched_matches_scalar(scenario, algo):
    plat = platform.NetMCPPlatform(SERVERS, scenario=scenario, seed=1)
    hist = plat.latency_window(3000)
    router = routing.make_router(algo, SERVERS)
    engine = make_engine(algo, SERVERS)
    dec = engine.route_texts(QUERY_TEXTS, hist)
    for i, q in enumerate(QUERY_TEXTS):
        d = router.select(q, hist)
        assert (d.server_idx, d.tool_idx) == (
            int(dec.server_idx[i]), int(dec.tool_idx[i])
        ), f"{scenario}/{algo} query {i}"


def test_batched_kernel_path_matches_scalar():
    """The Pallas path (interpret mode on CPU) is selection-identical too."""
    plat = platform.NetMCPPlatform(SERVERS, scenario="hybrid", seed=1)
    hist = plat.latency_window(3000)
    router = routing.make_router("sonar", SERVERS)
    engine = make_engine("sonar", SERVERS, use_kernels=True)
    dec = engine.route_texts(QUERY_TEXTS, hist)
    for i, q in enumerate(QUERY_TEXTS):
        d = router.select(q, hist)
        assert (d.server_idx, d.tool_idx) == (
            int(dec.server_idx[i]), int(dec.tool_idx[i])
        )


# ---------------------------------------------------------------------------
# Kernel path: corpora stored at the kernels' shapes
# ---------------------------------------------------------------------------

def _natural_corpora(engine):
    """Give the engine's route program the corpora at their natural
    shapes, so the kernel wrappers pad them inside every call."""
    import jax.numpy as jnp

    engine._w_server = jnp.asarray(engine.index.server_corpus.weights)
    engine._w_tool = jnp.asarray(engine.index.tool_corpus.weights)


def _corpus_pads(text: str, engine) -> list:
    """(operand, result) shapes of the lowered program's pads whose operand
    has a corpus's rows and terms, each real or kernel-aligned."""
    import re

    ws = engine.index.server_corpus.weights.shape
    wt = engine.index.tool_corpus.weights.shape
    sizes = [
        tuple(zip(ws, ops.bm25_corpus_shape(*ws))),
        tuple(zip(wt, ops.score_fuse_corpus_shape(*wt))),
    ]
    pads = re.findall(
        r"stablehlo\.pad .*: \(tensor<(\d+)x(\d+)x\w+>, [^)]*\) -> "
        r"tensor<(\d+)x(\d+)x(\w+)>", text)
    return [((int(a), int(b)), (int(c), int(d), dt)) for a, b, c, d, dt in pads
            if any(int(a) in rows and int(b) in cols for rows, cols in sizes)]


@pytest.mark.parametrize("algo", ["sonar", "sonar_lb", "sonar_ft", "rerank_rag"])
def test_aligned_corpora_match_natural_shapes(algo):
    """The kernel path stores its corpora zero-padded to the kernels'
    shapes; its decisions equal the same engine's with the corpora at
    their natural shapes (padded inside each call instead).  No axis of
    this pool's corpora is tile-aligned."""
    plat = platform.NetMCPPlatform(SERVERS, scenario="hybrid", seed=1)
    hist = plat.latency_window(3000)
    engine = make_engine(algo, SERVERS, use_kernels=True, interpret=True)
    ws = engine.index.server_corpus.weights.shape
    wt = engine.index.tool_corpus.weights.shape
    assert engine._w_server.shape == ops.bm25_corpus_shape(*ws) != ws
    assert engine._w_tool.shape == ops.score_fuse_corpus_shape(*wt) != wt
    batch = engine.encode(QUERY_TEXTS[:16])
    n = len(SERVERS)
    load = np.linspace(0.0, 1.5, n).astype(np.float32)

    if algo == "sonar_ft":
        first = engine.route(batch, hist, load)
        alive = np.ones(n, bool)
        alive[first.server_idx[:4]] = False
        dead = np.zeros(n, bool)
        dead[int(first.server_idx[5])] = True

        def decide():
            dec, fo = engine.route_failover(batch, hist, load, alive=alive,
                                            failed_mask=dead)
            assert fo.sum() > 0
            return dec
    else:
        def decide():
            return engine.route(batch, hist, load if algo == "sonar_lb" else None)

    aligned = decide()
    _natural_corpora(engine)
    natural = decide()
    for f in ("server_idx", "tool_idx", "expertise", "network", "fused"):
        np.testing.assert_array_equal(getattr(aligned, f), getattr(natural, f),
                                      err_msg=f"{algo}: {f}")


def test_route_program_pads_no_corpus():
    """No pad of the lowered kernel-path route program takes a corpus, and
    the engine's gauge says so; the same program given the natural shapes
    pads each corpus to the aligned shape, the bytes the gauge's formula
    gives for those shapes."""
    from repro.obs.metrics import MetricsRegistry

    plat = platform.NetMCPPlatform(SERVERS, scenario="hybrid", seed=1)
    hist = plat.latency_window(3000)
    reg = MetricsRegistry()
    engine = make_engine("sonar_lb", SERVERS, use_kernels=True, interpret=True,
                         registry=reg)
    args = (engine.encode(QUERY_TEXTS[:8]), hist, np.zeros(len(SERVERS)))
    assert _corpus_pads(engine.lower(*args).as_text(), engine) == []
    gauge = reg.get("engine_corpus_pad_bytes_per_call")
    assert gauge is not None and gauge.value == 0.0

    ws = engine.index.server_corpus.weights.shape
    wt = engine.index.tool_corpus.weights.shape
    _natural_corpora(engine)
    pads = _corpus_pads(engine.lower(*args).as_text(), engine)
    assert len(pads) == 4                    # rows and terms of each corpus
    aligned = {ops.bm25_corpus_shape(*ws), ops.score_fuse_corpus_shape(*wt)}
    final = [(r, c, dt) for _, (r, c, dt) in pads if (r, c) in aligned]
    assert len(final) == 2 and {dt for *_, dt in final} == {"f32"}
    assert ops.corpus_pad_bytes(ws, wt) == sum(4 * r * c for r, c, _ in final)
    assert ops.corpus_pad_bytes(ops.bm25_corpus_shape(*ws),
                                ops.score_fuse_corpus_shape(*wt)) == 0


def test_batched_respects_config_and_exposes_scores():
    cfg = RoutingConfig(top_s=3, top_k=6, alpha=0.7, beta=0.3)
    plat = platform.NetMCPPlatform(SERVERS, scenario="fluctuating", seed=2)
    hist = plat.latency_window(2000)
    router = routing.make_router("sonar", SERVERS, cfg)
    engine = make_engine("sonar", SERVERS, cfg)
    dec = engine.route_texts(QUERY_TEXTS[:16], hist)
    for i, q in enumerate(QUERY_TEXTS[:16]):
        d = router.select(q, hist)
        assert d.server_idx == int(dec.server_idx[i])
        np.testing.assert_allclose(d.expertise, dec.expertise[i], rtol=1e-4)
        np.testing.assert_allclose(d.fused, dec.fused[i], rtol=1e-4, atol=1e-5)
    assert dec.select_latency_ms == pytest.approx(
        routing.LLM_CALL_MS + 2 * routing.BM25_STAGE_MS
    )


def test_per_query_telemetry_routes_per_time():
    """3-D telemetry: each query is scored against its own latency window."""
    plat = platform.NetMCPPlatform(SERVERS, scenario="hybrid", seed=1)
    t_vec = np.asarray([100, 2000, 4000, 6000])
    windows = plat.latency_windows(t_vec)
    assert windows.shape == (4, len(SERVERS), plat.history_window)
    for i, t in enumerate(t_vec):
        np.testing.assert_array_equal(windows[i], plat.latency_window(int(t)))
    engine = make_engine("sonar", SERVERS)
    router = routing.make_router("sonar", SERVERS)
    q = QUERY_TEXTS[0]
    dec = engine.route_texts([q] * len(t_vec), windows)
    for i, t in enumerate(t_vec):
        d = router.select(q, plat.latency_window(int(t)))
        assert d.server_idx == int(dec.server_idx[i])


# ---------------------------------------------------------------------------
# Vectorized episode driver
# ---------------------------------------------------------------------------

def test_batch_agent_matches_scalar_agent():
    queries = dataset.build_query_dataset(n=60, seed=0)
    for scenario in ("hybrid", "fluctuating"):
        p1 = platform.NetMCPPlatform(SERVERS, scenario=scenario, seed=1)
        r = routing.make_router("sonar", SERVERS)
        recs1 = agent.Agent(p1, r).run_benchmark(queries, ticks_per_query=60)
        p2 = platform.NetMCPPlatform(SERVERS, scenario=scenario, seed=1)
        recs2 = agent.BatchAgent(p2, make_engine("sonar", SERVERS)).run_benchmark(
            queries, ticks_per_query=60
        )
        for a, b in zip(recs1, recs2):
            assert a.final_server_idx == b.final_server_idx
            assert a.n_calls == b.n_calls
            assert a.success == b.success
            assert a.n_failures == b.n_failures
            assert a.completion_ms == pytest.approx(b.completion_ms, rel=1e-4)
        m1 = metrics.evaluate(recs1, SERVERS)
        m2 = metrics.evaluate(recs2, SERVERS)
        assert m1.ssr == m2.ssr and m1.fr == m2.fr


def test_batch_agent_table2_headline():
    """The batched driver reproduces the Table II headline (SONAR 0% FR)."""
    queries = dataset.build_query_dataset(n=60, seed=0)
    plat = platform.NetMCPPlatform(SERVERS, scenario="hybrid", seed=1)
    recs = agent.BatchAgent(plat, make_engine("sonar", SERVERS)).run_benchmark(
        queries, ticks_per_query=60
    )
    rep = metrics.evaluate(recs, SERVERS)
    assert rep.fr == 0.0 and rep.al_ms < 50.0


# ---------------------------------------------------------------------------
# Scenario registry (all five canonical states + composed)
# ---------------------------------------------------------------------------

def test_scenario_registry_covers_paper_states():
    assert set(platform.SCENARIOS) >= {
        "ideal", "hybrid", "fluctuating",
        "high_latency", "high_jitter", "diurnal_congestion",
    }


def test_high_latency_scenario_profile_classes():
    profs = platform.SCENARIOS["high_latency"](SERVERS)
    ws = [p for s, p in zip(SERVERS, profs) if s.domain == dataset.WEBSEARCH]
    hl = L.high_latency_profile()
    elevated = [p for p in ws if p.base_latency_ms == hl.base_latency_ms]
    assert len(elevated) == len(ws) - 1          # one ideal escape hatch
    assert sum(p.base_latency_ms <= 50.0 for p in ws) == 1
    for s, p in zip(SERVERS, profs):
        if s.domain != dataset.WEBSEARCH:
            assert p.base_latency_ms < hl.base_latency_ms


def test_high_jitter_scenario_profile_classes():
    profs = platform.SCENARIOS["high_jitter"](SERVERS)
    for s, p in zip(SERVERS, profs):
        if s.domain == dataset.WEBSEARCH:
            assert p.std_dev_ms >= 70.0          # high-jitter canonical state
            assert p.base_latency_ms == 100.0
        else:
            assert p.std_dev_ms <= 10.0


def test_diurnal_congestion_composes_states():
    profs = platform.SCENARIOS["diurnal_congestion"](SERVERS)
    ws = [p for s, p in zip(SERVERS, profs) if s.domain == dataset.WEBSEARCH]
    assert all(p.amplitude_ms > 0 for p in ws)               # diurnal rhythm
    assert all(p.period_s == 24 * 3600.0 for p in ws)
    assert sum(p.outage_probability > 0 for p in ws) == 1    # congested top
    phases = sorted(p.phase_shift for p in ws)
    assert len(set(phases)) == len(ws)                       # staggered


def test_new_scenarios_route_end_to_end():
    """SONAR beats PRAG on latency in both new single-state scenarios."""
    queries = dataset.build_query_dataset(n=40, seed=0)
    for scenario in ("high_latency", "high_jitter"):
        reports = {}
        for algo in ("prag", "sonar"):
            plat = platform.NetMCPPlatform(SERVERS, scenario=scenario, seed=3)
            recs = agent.BatchAgent(plat, make_engine(algo, SERVERS)).run_benchmark(
                queries, ticks_per_query=60
            )
            reports[algo] = metrics.evaluate(recs, SERVERS)
        assert reports["sonar"].al_ms < reports["prag"].al_ms, scenario
        assert abs(reports["sonar"].ssr - reports["prag"].ssr) < 15.0
