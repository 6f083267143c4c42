"""The paper-pool cell on the CPU at a tiny size: its configuration is
the paper's pool, its reference agrees with the program's scalar router
over the tiled fleet, a sound run is correct, the control is not, and
each fault the cell can have makes it not correct."""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
sys.path.insert(0, BENCH)

from harness import cell, loads, manifest  # noqa: E402
from harness import traffic as tr  # noqa: E402

CELL = "tiny-pool.flush64"
SEED = 12
SECONDS = 1.0


def _config() -> dict:
    with open(os.path.join(BENCH, "configs", "paper-pool-1m.json")) as f:
        return json.load(f)


def _system():
    return manifest.system(BENCH, "pool_gateway")


def _run(root, **kw):
    return cell.run(CELL, SEED, SECONDS, False, time.monotonic(),
                    require_chip=False, root=root, log=lambda *a: None, **kw)


def test_configuration_is_the_papers_pool_uncut():
    from repro.core import dataset
    from repro.traffic import fleet

    cfg = _config()
    pool = dataset.build_server_pool(seed=0)
    assert [(s["name"], s["domain"], s["description"],
             [(t["name"], t["description"]) for t in s["tools"]])
            for s in cfg["pool"]] == [
        (s.name, s.domain, s.description,
         [(t.name, t.description) for t in s.tools]) for s in pool]
    assert cfg["n_replicas"] == 10 ** 6 and cfg["reduced"] == []
    small = dict(cfg, n_replicas=5000)
    np.testing.assert_array_equal(
        _system().telemetry_map(small),
        fleet.telemetry_template_map(5000, cfg["telemetry"]["templates"]))
    assert len(_system().catalog(cfg)["tool_words"]) == 30


@pytest.mark.parametrize("dead_kind", ["none", "scattered", "candidates"])
def test_tiled_reference_agrees_with_router_select(dead_kind):
    """Over 3,000 replicas: the same pick and fused score as the program's
    scalar `Router.select` over its bf16 tiled index, with a ring of
    bf16-rounded template telemetry, load and failed replicas."""
    from ref import sonar, tiled_ft

    from repro.core.dataset import Server, Tool
    from repro.core.routing import ALGORITHMS, RoutingConfig
    from repro.traffic import fleet

    cfg = _config()
    n, r = 3000, cfg["routing"]
    templates = [Server(s["name"], s["domain"], s["description"],
                        [Tool(t["name"], t["description"]) for t in s["tools"]])
                 for s in cfg["pool"]]
    index = fleet.mega_fleet_index(n, templates, weights_dtype="bfloat16")
    router = ALGORITHMS["sonar_ft"]([], RoutingConfig(
        top_s=r["top_s"], top_k=r["top_k"], alpha=r["alpha"], beta=r["beta"],
        gamma=r["gamma"], load_knee=r["load_knee"],
        load_sharp=r["load_sharp"]), index=index)
    ref = tiled_ft.Reference(cfg["pool"], n, r, sonar.load_intents(BENCH),
                             "bfloat16")
    rng = np.random.default_rng(4)
    tmap = fleet.telemetry_template_map(n, 16)
    texts = tr.catalog_texts(_system().catalog(cfg),
                             {"zipf_theta": 0.99, "words_min": 4,
                              "words_max": 16, "general_share": 0.25}, rng, 48)
    picks = set()
    for q in texts:
        hist_t = sonar.to_bf16(rng.gamma(2.0, 60.0, (16, 64)))
        hist_t[rng.random(16) < 0.2, -1] = 1000.0          # some offline
        load = (rng.random(n) * 1.5).astype(np.float32)
        dead = np.zeros(n, bool)
        if dead_kind == "scattered":
            dead[rng.random(n) < 0.1] = True
        elif dead_kind == "candidates":
            dead[ref.candidates(q, np.empty(0, np.int64))[0][:5]] = True
        d = router.select(q, hist_t[tmap], load,
                          failed_mask=dead if dead.any() else None)
        n_tpl = sonar.network_score(hist_t, r["qos"])
        cand_t, s = ref.fused(q, lambda h: n_tpl[tmap[h]],
                              dict(enumerate(load.astype(np.float64))),
                              np.flatnonzero(dead))
        best = int(np.argmax(s))
        assert int(cand_t[best]) == d.tool_idx
        assert s[best] == pytest.approx(d.fused, abs=1e-6)
        picks.add(d.server_idx)
    assert len(picks) > 1


def test_sound_run_is_correct_and_the_control_is_not(tiny):
    """A sound run sees ejections and reads inside every limit; the
    reference with its score arithmetic in bfloat16 in the program's place
    leaves at least one, and neither the health nor the count."""
    out = _run(tiny, control=True)
    res, ctrl = out["result"], out["control"]
    limits = manifest.resolve(CELL, tiny).config["checks"]
    assert res["correct"] is True, out["checks"]
    assert out["reading"]["ejected_seen"] > 0 and out["compiles"] == 0
    assert out["reading"]["probe_share"] is not None
    assert out["reading"]["probe_z"] <= limits["probe_z"]
    assert res["failed"] == 0 and "decisions_per_s" in res["metrics"]
    assert ctrl["checked"] > 0
    assert (ctrl["fused_err"] > limits["fused_err"]
            or ctrl["regret"] > limits["regret"])
    assert ctrl["health_mismatch"] == 0 and ctrl["missing"] == 0


def _alter_first(orig, self, batch, *a, **kw):
    dec = orig(self, batch, *a, **kw)
    dec.tool_idx = np.asarray(dec.tool_idx).copy()
    dec.tool_idx[0] = (dec.tool_idx[0] + 1) % len(self._tool_server)
    return dec


def _ignore_health(orig, self, batch, *a, **kw):
    return orig(self, batch, *a, **dict(kw, failed_mask=None))


@pytest.mark.parametrize("fault", ["health_ignored", "ejected_frozen",
                                   "probes_doubled", "half_flush",
                                   "answer_altered"])
def test_broken_pool_path_is_not_correct(tiny, monkeypatch, fault):
    """Each fault the cell can have, planted under the timed path: the
    health rows left out of routing, the ejected set frozen, ejected
    replicas re-admitted at twice the probe probability, half of a flush
    left out, an answer altered where it is produced."""
    from repro.core.mesh_routing import ShardedRoutingEngine
    from repro.serving.gateway import SonarGateway

    def wrap(change):
        orig = ShardedRoutingEngine.route

        def route(self, batch, *a, **kw):
            return change(orig, self, batch, *a, **kw)

        monkeypatch.setattr(ShardedRoutingEngine, "route", route)

    if fault == "health_ignored":
        wrap(_ignore_health)
    elif fault == "ejected_frozen":
        monkeypatch.setattr(SonarGateway, "_record_outcome",
                            lambda self, idx, ok: None)
    elif fault == "probes_doubled":
        orig_mask = SonarGateway._health_mask

        def doubled(self, n_requests=None):
            self.probe_prob = 0.3
            return orig_mask(self, n_requests)

        monkeypatch.setattr(SonarGateway, "_health_mask", doubled)
    elif fault == "half_flush":
        monkeypatch.setattr(loads, "GRACE_MS", 3000.0)
        orig = SonarGateway.route_batch

        def half(self, texts, **kw):
            return orig(self, texts[: max(len(texts) // 2, 1)], **kw)

        monkeypatch.setattr(SonarGateway, "route_batch", half)
    else:
        wrap(_alter_first)
    out = _run(tiny)
    assert out["result"]["correct"] is False, out["checks"]


def test_window_flush_records_carry_the_health_state(tiny):
    """Every answer of the window carries its flush's record with the
    health span, the ejected replicas its decisions saw and the bytes of
    its health rows."""
    c = manifest.resolve(CELL, tiny)
    b = manifest.system(c.bench_dir, c.config["system"])
    sut = b.build(c, SEED)
    spec = c.traffic
    texts = tr.texts(sut.catalog, spec["text"], SEED, 600)
    w = loads.run_gateway(sut, spec, texts[:400], None, SECONDS, texts[400:],
                          loads.CompileCounter())
    recs = {id(s.result.flush): s.result.flush for s in w.samples
            if s.result is not None and s.t_done <= w.t1}
    n = c.config["n_replicas"]
    assert recs
    for rec in recs.values():
        assert rec.gauges["gateway_ejected"] >= 1
        assert rec.gauges["gateway_health_row_bytes_per_flush"] > 0
        assert rec.gauges["gateway_health_row_bytes_per_flush"] % n == 0
        assert rec.phases["gateway.health_mask"] > 0
