"""Whole benchmark runs on the CPU at a tiny size: the harness's look for
a chip is skipped and the rest of a run is driven, with the timed path
sound, with the control in its place, and broken underneath."""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
sys.path.insert(0, BENCH)

from harness import cell, loads, manifest  # noqa: E402

SECONDS = 1.0


def _run(root, workload="tiny.poisson", seed=2 ** 32 + 3, **kw):
    return cell.run(workload, seed, SECONDS, False, time.monotonic(),
                    require_chip=False, root=root, log=lambda *a: None, **kw)


def test_sound_run_is_correct_and_reports_its_metrics(tiny):
    out = _run(tiny)
    res = out["result"]
    assert res["correct"] is True and res["attempted"] > 0
    assert set(res["metrics"]) == {"decisions_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert out["reading"]["checked"] == res["attempted"] - res["failed"]
    assert out["compiles"] == 0


@pytest.mark.parametrize("workload", ["tiny.agents"])
def test_closed_loop_cell_reports_decisions_per_second(tiny, workload):
    res = _run(tiny, workload)["result"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"decisions_per_s", "setup_s"}


@pytest.mark.parametrize("workload", ["tiny.agents", "tiny.poisson"])
def test_control_fails_the_comparison(tiny, workload):
    """The reference one precision step down in the program's place
    (Precision.HIGH for the HIGHEST the catalog states): its fused scores
    leave the limit, the program's do not."""
    out = _run(tiny, workload, control=True)
    ctrl = out["control"]
    limits = manifest.resolve(workload, tiny).config["checks"]
    assert ctrl["checked"] > 0
    assert (ctrl["fused_err"] > limits["fused_err"]
            or ctrl["regret"] > limits["regret"])
    assert out["reading"]["fused_err"] < limits["fused_err"]


def _frozen_ring(self, idx, latency_ms):
    self.t += 1                      # the clock moves, the ring does not


def _no_load(self):
    return np.zeros_like(self.in_flight)


def _wrap_route(monkeypatch, cls, change):
    orig = cls.route

    def route(self, batch, *a, **kw):
        return change(orig, self, batch, *a, **kw)

    monkeypatch.setattr(cls, "route", route)


def _alter_first(orig, self, batch, *a, **kw):
    dec = orig(self, batch, *a, **kw)
    dec.tool_idx = np.asarray(dec.tool_idx).copy()
    dec.tool_idx[0] = (dec.tool_idx[0] + 1) % len(self._tool_server)
    return dec


@pytest.mark.parametrize("workload,fault", [
    ("tiny.poisson", "state_unchanged"),
    ("tiny.agents", "load_unchanged"),
    ("tiny.poisson", "half_batch"),
    ("tiny.poisson", "answer_altered"),
    ("tiny.agents", "state_unchanged"),
    ("tiny.agents", "half_batch"),
    ("tiny.agents", "answer_altered"),
])
def test_broken_timed_path_is_not_correct(tiny, monkeypatch, workload, fault):
    """Each fault the cell can have, planted under the timed path: a step
    that leaves its state unchanged (the telemetry ring, or the load
    feedback between chunks), half of a batch left out, an answer altered
    where it is produced.  One chip, so no exchange between chips to leave
    out."""
    from repro.core.batch_routing import BatchRoutingEngine
    from repro.serving.gateway import SonarGateway

    if fault == "state_unchanged":
        monkeypatch.setattr(SonarGateway, "_observe", _frozen_ring)
    elif fault == "load_unchanged":
        monkeypatch.setattr(SonarGateway, "_utilization", _no_load)
    elif fault == "half_batch":
        monkeypatch.setattr(loads, "GRACE_MS", 3000.0)
        orig = SonarGateway.route_batch

        def half(self, texts, **kw):
            return orig(self, texts[: max(len(texts) // 2, 1)], **kw)

        monkeypatch.setattr(SonarGateway, "route_batch", half)
    else:
        _wrap_route(monkeypatch, BatchRoutingEngine, _alter_first)
    out = _run(tiny, workload)
    assert out["result"]["correct"] is False, out["checks"]


def test_a_new_cell_is_new_files_only(tiny):
    """A configuration, traffic mix and per-layer metric added as files
    and manifest entries run without an edit to any file already there."""
    bench = os.path.join(tiny, "bench")
    with open(os.path.join(bench, "configs", "tiny.json")) as f:
        cfg = json.load(f)
    cfg.update(name="tiny-wide", n_servers=90, n_tools=300)
    with open(os.path.join(bench, "configs", "tiny-wide.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "traffic", "tiny-poisson.json")) as f:
        spec = json.load(f)
    spec["arrivals"]["rate_per_s"] = 60.0
    with open(os.path.join(bench, "traffic", "tiny-trickle.json"), "w") as f:
        json.dump(spec, f)
    with open(os.path.join(bench, "metrics", "frontend.flushes.py"), "w") as f:
        f.write("def read(ctx):\n    return ctx.window.frontend_flushes\n")
    man_path = os.path.join(tiny, "BENCHMARK.json")
    with open(man_path) as f:
        man = json.load(f)
    man["configs"].append(dict(man["configs"][0], name="tiny-wide",
                               file="bench/configs/tiny-wide.json"))
    man["workloads"].append({"name": "tiny-wide.trickle", "config": "tiny-wide",
                             "traffic": "tiny-trickle", "chips": 1,
                             "why": "a test cell"})
    for m in man["end_to_end"]:
        if m["name"] == "decisions_per_s":
            m["workloads"].append("tiny-wide.trickle")
    man["per_layer"].append({"name": "frontend.flushes", "unit": "flushes",
                             "better": "lower", "source": "program_counter",
                             "layer": "front end", "moves": "decisions_per_s",
                             "workloads": ["tiny-wide.trickle"]})
    with open(man_path, "w") as f:
        json.dump(man, f)
    out = cell.run("tiny-wide.trickle", 9, SECONDS, False, time.monotonic(),
                   require_chip=False, root=tiny, log=lambda *a: None)
    assert out["result"]["correct"] is True
    assert "decisions_per_s" in out["result"]["metrics"]
    c = manifest.resolve("tiny-wide.trickle", tiny)
    assert [m["name"] for m in c.per_layer] == ["frontend.flushes"]


def test_run_refuses_without_a_chip():
    """On the CPU the entry point exits non-zero and prints no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "toolbench-catalog.agents", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, cwd=REPO, timeout=300,
    )
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "TPU" in p.stderr
