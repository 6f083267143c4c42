"""Fixtures of the benchmark's CPU tests: a checkout-shaped temporary
root holding a copy of ``bench/`` and a manifest whose cells run a tiny
catalog on the CPU in a few seconds."""
from __future__ import annotations

import json
import os
import shutil
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)


# each configuration cut to a size the CPU runs in seconds
TINY = {
    "toolbench-catalog": ("tiny", dict(n_servers=120, n_tools=480,
                                       vocab_terms=600)),
}
POISSON = {"loop": "open_loop", "warm_rounds": 2,
           "arrivals": {"process": "poisson", "rate_per_s": 150.0}}
TINY_TRAFFIC = {
    "agents": dict(clients=16, text_pool=400, warm_rounds=2),
}


def tiny_root(path: str) -> str:
    """A root with ``bench/`` copied and every cell on a tiny copy of its
    configuration, named ``<tiny config>.<mix>`` (``tiny.agents``), plus
    ``tiny.poisson``: the same catalog under an open-loop Poisson mix, so
    that the open loop, which no committed cell uses yet, is driven too."""
    bench = os.path.join(path, "bench")
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        man = json.load(f)
    for c in man["configs"]:
        name, cut = TINY[c["name"]]
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        cfg.update(cut, name=name)
        c.update(name=name, file=f"bench/configs/{name}.json")
        with open(os.path.join(path, c["file"]), "w") as f:
            json.dump(cfg, f)
    for mix, extra in TINY_TRAFFIC.items():
        with open(os.path.join(bench, "traffic", f"{mix}.json")) as f:
            spec = json.load(f)
        spec.update(extra)
        with open(os.path.join(bench, "traffic", f"tiny-{mix}.json"), "w") as f:
            json.dump(spec, f)
    rename = {}
    for w in man["workloads"]:
        cfg = TINY[w["config"]][0]
        rename[w["name"]] = f"{cfg}.{w['traffic']}"
        w.update(name=rename[w["name"]], config=cfg,
                 traffic="tiny-" + w["traffic"])
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m:
            m["workloads"] = [rename[x] for x in m["workloads"]]
    with open(os.path.join(bench, "traffic", "tiny-poisson.json"), "w") as f:
        json.dump(dict(POISSON, text=spec["text"]), f)
    man["workloads"].append({"name": "tiny.poisson", "config": "tiny",
                             "traffic": "tiny-poisson", "chips": 1,
                             "why": "open loop"})
    for m in man["end_to_end"] + man["per_layer"]:
        if "workloads" in m and "tiny.agents" in m["workloads"]:
            m["workloads"].append("tiny.poisson")
    with open(os.path.join(path, "BENCHMARK.json"), "w") as f:
        json.dump(man, f)
    return path


@pytest.fixture
def tiny(tmp_path):
    return tiny_root(str(tmp_path))
