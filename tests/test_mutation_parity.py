"""Cross-path mutation testing of the parity harness itself.

The three-path parity suite (`tests/test_parity_prop.py`) asserts that
the scalar router, the jit batched engine and the Pallas kernel make the
same decision.  That property only has teeth if a *defect in one path*
actually changes a decision — a parity suite that still passes when a
fusion term is dropped proves nothing.

This module seeds exactly those defects.  Each mutation monkeypatches one
algorithm term **in the scalar path only** (``repro.core.routing`` binds
`load_penalty` / `staleness_discount` / `rtt_penalty` into its own module
namespace, so patching there leaves the batched pipeline and the kernel
untouched), then asserts the three-path parity check *detects* the
divergence:

  - ``drop_load``   — SONAR-LB's convex utilization penalty returns 0
  - ``skip_stale``  — SONAR-FT's staleness discount returns 1 (full trust)
  - ``zero_rtt``    — SONAR-GEO's propagation-RTT penalty returns 0

The fixtures are constructed so the mutated term is *decisive*: identical
replicas tie on semantics, telemetry ties (or favors the to-be-penalized
server), and only the term under test separates the winner — so an
undetected mutation means the parity suite genuinely lost its teeth, not
that the inputs were too easy.

A baseline case asserts parity holds unmutated (the harness cannot be
trivially "detecting" everything), and a kernel-side sanity mutation
(perturbing the oracle's fusion weight) shows detection is symmetric.
"""
import numpy as np
import pytest

from repro.core import routing
from repro.core.batch_routing import BatchRoutingEngine
from repro.core.routing import RoutingConfig
from repro.traffic import replica_fleet

QUERY = "search the web for the latest news"
N = 4
CFG = RoutingConfig(top_s=N, top_k=N)


def _fixture(kind: str):
    """Identical-replica fleet + telemetry crafted so one term decides.

    Returns (servers, hist, load, age, rtt): semantics tie (identical
    replicas), so the fusion term under test is the only separator
    between server 0 and the rest.
    """
    servers = replica_fleet(N)
    if kind == "load":
        # flat healthy telemetry everywhere; server 0 is saturated —
        # only the load term steers the argmax away from index 0
        hist = np.full((N, 24), 100.0, np.float32)
        load = np.array([2.0, 0.0, 0.0, 0.0], np.float32)
        age = None
        rtt = None
    elif kind == "stale":
        # server 0 *looks* pristine but its telemetry is ancient; the
        # others are honest and mediocre.  With the discount, 0's QoS
        # decays toward neutral and an honest server wins; without it,
        # the stale-perfect history wins.
        hist = np.full((N, 24), 100.0, np.float32)
        hist[0] = 30.0
        load = np.zeros(N, np.float32)
        age = np.array([900.0, 0.0, 0.0, 0.0], np.float32)
        rtt = None
    elif kind == "rtt":
        # flat telemetry; server 0 sits an ocean away — only the RTT
        # penalty steers the argmax off index 0
        hist = np.full((N, 24), 100.0, np.float32)
        load = np.zeros(N, np.float32)
        age = None
        rtt = np.array([300.0, 0.0, 0.0, 0.0], np.float32)
    else:
        raise KeyError(kind)
    return servers, hist, load, age, rtt


def _parity_agrees(algo, servers, hist, load, age, rtt) -> bool:
    """One three-path parity probe: scalar vs jnp-batched vs Pallas
    kernel.  True iff all three picked the same (server, tool)."""
    router = routing.make_router(algo, servers, CFG)
    scalar = router.select(
        QUERY, hist, load, telemetry_age_s=age, client_rtt_ms=rtt
    )
    picks = [(scalar.server_idx, scalar.tool_idx)]
    for use_kernels in (False, True):
        kw = {"interpret": True} if use_kernels else {}
        eng = BatchRoutingEngine(
            servers, CFG, algo=algo, use_kernels=use_kernels,
            index=router.index, **kw,
        )
        dec = eng.route_texts(
            [QUERY], hist, load, telemetry_age_s=age, client_rtt_ms=rtt
        )
        picks.append((int(dec.server_idx[0]), int(dec.tool_idx[0])))
    return picks[0] == picks[1] == picks[2]


MUTATIONS = {
    # name -> (algo, fixture kind, scalar-path attribute, mutated stand-in)
    "drop_load": (
        "sonar_lb", "load", "load_penalty",
        lambda rho, knee=0.75, sharp=4.0: np.zeros_like(
            np.asarray(rho, np.float32)
        ),
    ),
    "skip_stale": (
        "sonar_ft", "stale", "staleness_discount",
        lambda age, half=180.0: np.ones_like(np.asarray(age, np.float32)),
    ),
    "zero_rtt": (
        "sonar_geo", "rtt", "rtt_penalty",
        lambda rtt, scale=150.0: np.zeros_like(np.asarray(rtt, np.float32)),
    ),
}


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_baseline_parity_holds(name):
    """Unmutated, every fixture passes the three-path probe — the probe
    is not a tautological failure detector."""
    algo, kind, _, _ = MUTATIONS[name]
    assert _parity_agrees(algo, *_fixture(kind)), (
        f"{algo} disagrees across paths before any mutation — the "
        "mutation harness requires a green baseline"
    )


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_term_decides_the_fixture(name):
    """Each fixture's term is decisive: the intact scalar router must NOT
    pick server 0 (the penalized one) — otherwise a dropped term could
    never flip the argmax and the mutation test would be vacuous."""
    algo, kind, _, _ = MUTATIONS[name]
    servers, hist, load, age, rtt = _fixture(kind)
    router = routing.make_router(algo, servers, CFG)
    d = router.select(
        QUERY, hist, load, telemetry_age_s=age, client_rtt_ms=rtt
    )
    assert d.server_idx != 0, (
        f"{algo}: fixture term is not decisive (picked the penalized "
        "server anyway)"
    )


@pytest.mark.parametrize("name", sorted(MUTATIONS))
def test_parity_suite_detects_scalar_mutation(name, monkeypatch):
    """THE teeth test: dropping one term from the scalar path must break
    three-path parity — i.e. the parity property distinguishes a real
    implementation defect."""
    algo, kind, attr, mutant = MUTATIONS[name]
    servers, hist, load, age, rtt = _fixture(kind)
    monkeypatch.setattr(routing, attr, mutant)
    assert not _parity_agrees(algo, servers, hist, load, age, rtt), (
        f"mutation '{name}' ({attr} neutralized in the scalar path) was "
        "NOT detected by the three-path parity probe — the parity suite "
        "has no teeth for this term"
    )


# ---------------------------------------------------------------------------
# Adaptation-trajectory mutations (SONAR-ADAPT)
# ---------------------------------------------------------------------------
#
# The zero-lr identity suite pins that SONAR-ADAPT *without* learning is
# byte-identical to the hand-tuned routers; these mutations pin the other
# direction — that the adaptation-trajectory assertion ("with lr != 0 and
# informative feedback, the weight vector leaves its init") genuinely
# depends on the update math and the reward signal.  Killing either one
# (identity `_adapt_step`, dead `shape_reward`) must freeze the
# trajectory; a trajectory check that still "moves" would be asserting
# nothing about the learner.

def _scalar_weights_moved(n_steps: int = 24) -> bool:
    """Drive the scalar SONAR-ADAPT feedback loop with informative
    outcomes (alternating SLO hits and deep misses on a load-skewed
    fleet) and report whether the weight vector left its init."""
    from repro.core import adaptive

    servers, hist, load, _, _ = _fixture("load")
    router = adaptive.SonarAdaptRouter(
        servers, CFG, adapt=adaptive.AdaptConfig(slo_ms=200.0)
    )
    init = np.asarray(router.state.weights).copy()
    for i in range(n_steps):
        router.select(QUERY, hist, load)
        router.observe_outcome(60.0 if i % 2 else 1200.0, ok=bool(i % 3))
    return bool(np.any(np.asarray(router.state.weights) != init))


def _adapt_engine(sharded: bool):
    """A SONAR-ADAPT engine on the load fixture: the batched engine, or
    the sharded one over 3 shards."""
    from repro.core import adaptive
    from repro.core.mesh_routing import ShardedRoutingEngine

    servers, _, _, _, _ = _fixture("load")
    acfg = adaptive.AdaptConfig(slo_ms=200.0)
    if sharded:
        return ShardedRoutingEngine(servers, CFG, algo="sonar_adapt",
                                    n_shards=3, use_kernels=False,
                                    adapt=acfg)
    return BatchRoutingEngine(servers, CFG, algo="sonar_adapt", adapt=acfg)


def _engine_weights_moved(sharded: bool, n_rounds: int = 12) -> bool:
    """Same trajectory probe through an engine: feedback queued by
    `observe_feedback` is folded into the weights (the standalone
    `adaptive.adapt_update`) on the next route call."""
    _, hist, load, _, _ = _fixture("load")
    eng = _adapt_engine(sharded)
    init = np.asarray(eng.adapt_state.weights).copy()
    feats = np.asarray([0.6, 0.4, -0.3, 0.0], np.float32)
    for i in range(n_rounds):
        eng.observe_feedback(
            60.0 if i % 2 else 1200.0, ok=bool(i % 3), feats=feats
        )
        eng.route_texts([QUERY], hist, load)
    return bool(np.any(np.asarray(eng.adapt_state.weights) != init))


def _weights_moved(probe: str) -> bool:
    if probe == "scalar":
        return _scalar_weights_moved()
    return _engine_weights_moved(sharded=probe == "sharded")


PROBES = ["scalar", "engine", "sharded"]


@pytest.mark.parametrize("probe", PROBES)
def test_adaptation_trajectory_moves_unmutated(probe):
    """Green baseline: with the real update and reward, the trajectory
    assertion holds on the scalar path and on both engines."""
    assert _weights_moved(probe), (
        f"{probe}: SONAR-ADAPT weights never left their init under "
        "informative feedback — the trajectory probe is vacuous"
    )


@pytest.mark.parametrize("probe", PROBES)
def test_mutation_identity_update_freezes_trajectory(probe, monkeypatch):
    """Killing the EG step (identity `_adapt_step`) must freeze the
    weight trajectory on every path.  `_adapt_step` is looked up on the
    module at trace time, so the patch + a compilation-cache drop reaches
    the standalone jit update that the scalar router and both engines
    run."""
    import jax

    from repro.core import adaptive

    monkeypatch.setattr(
        adaptive, "_adapt_step",
        lambda state, rewards, feats, valid, acfg: state,
    )
    jax.clear_caches()
    try:
        assert not _weights_moved(probe), (
            f"{probe}: weights moved with the update step mutated to the "
            "identity — the trajectory assertion does not depend on "
            "`_adapt_step`"
        )
    finally:
        jax.clear_caches()


@pytest.mark.parametrize("probe", PROBES)
def test_mutation_dead_reward_freezes_trajectory(probe, monkeypatch):
    """Killing the reward signal (shape_reward == 0 for every outcome)
    must also freeze the trajectory: with a zero reward stream and a zero
    baseline the advantage vanishes, so a moving weight vector would mean
    the learner is not actually driven by the simulator-emitted reward.
    (Host-side patch — reward shaping happens before the jit boundary.)"""
    from repro.core import adaptive

    monkeypatch.setattr(
        adaptive, "shape_reward", lambda latency_ms, ok, slo_ms=800.0: 0.0
    )
    assert not _weights_moved(probe), (
        f"{probe}: weights moved with a dead reward signal — the "
        "trajectory assertion does not depend on `shape_reward`"
    )


def test_engines_share_one_adapt_trajectory():
    """The batched engine and the sharded engine (3 shards) fed the same
    `observe_feedback` stream hold bitwise-equal weights after every one
    of 12 routes — one update scheme.  The stream's per-route counts
    include empty rounds, a full `FEEDBACK_BUCKET` and overflows past
    it."""
    from repro.core import adaptive

    _, hist, load, _, _ = _fixture("load")
    engines = [_adapt_engine(sharded=False), _adapt_engine(sharded=True)]
    rng = np.random.default_rng(7)
    B = adaptive.FEEDBACK_BUCKET
    counts = [1, 5, B + 6, 0, 3, B, B + 1, 2, 1, 9, 2 * B + 2, 4]
    init = np.asarray(engines[0].adapt_state.weights).copy()
    for r, count in enumerate(counts):
        lat = rng.uniform(20.0, 1500.0, count)
        ok = rng.random(count) > 0.2
        feats = rng.uniform(-1.0, 1.0, (count, 4)).astype(np.float32)
        for eng in engines:
            for i in range(count):
                eng.observe_feedback(float(lat[i]), ok=bool(ok[i]),
                                     feats=feats[i])
            eng.route_texts([QUERY], hist, load)
        np.testing.assert_array_equal(
            np.asarray(engines[0].adapt_state.weights),
            np.asarray(engines[1].adapt_state.weights),
            err_msg=f"route {r}: the engines' weights diverged",
        )
    assert np.any(np.asarray(engines[0].adapt_state.weights) != init)


def test_parity_suite_detects_oracle_mutation(monkeypatch):
    """Symmetry: perturbing the *batched* side (the jnp oracle's fusion)
    is detected too — the probe is not blind in either direction."""
    from repro.kernels import ref as kref

    servers, hist, load, age, rtt = _fixture("load")
    orig = kref.fused_select_ref

    def mutant(*args, **kw):
        kw["gamma"] = 0.0          # drop the load term in the oracle only
        return orig(*args, **kw)

    import jax

    import repro.core.batch_routing as br

    monkeypatch.setattr(br.kref, "fused_select_ref", mutant)
    # earlier tests already compiled the pipeline for these shapes; the
    # compiled computation embeds the unmutated oracle, so drop every
    # compilation cache to force a retrace through the mutant
    jax.clear_caches()
    try:
        router = routing.make_router("sonar_lb", servers, CFG)
        eng = BatchRoutingEngine(
            servers, CFG, algo="sonar_lb", use_kernels=False,
            index=router.index,
        )
        d = router.select(QUERY, hist, load)
        dec = eng.route_texts([QUERY], hist, load)
        assert (d.server_idx, d.tool_idx) != (
            int(dec.server_idx[0]), int(dec.tool_idx[0])
        ), "oracle-side mutation was not detected"
    finally:
        jax.clear_caches()
