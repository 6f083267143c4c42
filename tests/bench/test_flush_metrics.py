"""A traced run of the tiny closed-loop cell on the CPU reports the
server-timing metrics that read the program's flush records."""
from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "bench"))

from harness import cell  # noqa: E402

FLUSH_METRICS = (
    "engine.upload_ms_per_flush.rate",
    "engine.enqueue_ms_per_flush.rate",
    "engine.readback_ms_per_flush.rate",
    "gateway.ring_push_ms_per_flush.rate",
    "frontend.gap_ms_per_flush.rate",
)


def test_traced_run_reports_flush_phases(tiny):
    from repro.obs import trace as obs_trace

    # more agents than one flush holds, so requests wait while a flush
    # runs and the front end's gap between flushes is observed
    path = os.path.join(tiny, "bench", "traffic", "tiny-agents.json")
    with open(path) as f:
        spec = json.load(f)
    spec["clients"] = 48
    with open(path, "w") as f:
        json.dump(spec, f)
    try:
        out = cell.run("tiny.agents", 2 ** 32 + 11, 1.0, True, time.monotonic(),
                       require_chip=False, root=tiny, log=lambda *a: None)
    finally:
        obs_trace.enable_jax_annotations(False)
    res = out["result"]
    assert res["correct"] is True
    m = {k: v["value"] for k, v in res["metrics"].items()}
    for name in FLUSH_METRICS:
        assert m.get(name, 0.0) > 0.0, (name, m)
