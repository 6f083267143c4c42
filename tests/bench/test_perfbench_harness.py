"""The benchmark's yardstick on the CPU: catalog generator, traffic,
reference, kernel counts, trace reduction and the manifest's rules."""
from __future__ import annotations

import gzip
import json
import os
import re
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "bench")
sys.path.insert(0, BENCH)

from harness import manifest, stats, tracefile  # noqa: E402
from harness import traffic as tr  # noqa: E402


def _config(name="toolbench-catalog"):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _intents():
    with open(os.path.join(BENCH, "ref", "intents.json")) as f:
        return json.load(f)


def _system():
    return manifest.system(BENCH, "catalog_gateway")


# -- catalog ------------------------------------------------------------------

def test_catalog_has_toolbench_counts_and_repeats_per_seed():
    cfg, intents, b = _config(), _intents(), _system()
    seed = 2 ** 33 + 17                      # seeds may pass 32 bits
    a = b.generate(cfg, seed, intents)
    again = b.generate(cfg, seed, intents)
    other = b.generate(cfg, seed + 1, intents)
    assert len({s[1] for s in a["servers"]}) == 49
    assert len(a["servers"]) == 3451 and len(a["tools"]) == 16464
    assert a == again and a["servers"] != other["servers"]
    descs = [s[2] for s in a["servers"]]
    assert len(set(descs)) == len(descs)     # distinct: no BM25 tie by copy
    words = set()
    for name, desc, _ in a["tools"]:
        words.update(desc.split())
        words.update(name.split("_"))
    assert len(words) <= cfg["vocab_terms"] == 21213    # Heaps' law, uncut
    assert len(words) > 0.9 * cfg["vocab_terms"]
    servers = {si for _, _, si in a["tools"]}
    assert servers == set(range(3451))       # every server hosts a tool


# -- traffic ------------------------------------------------------------------

def test_arrival_copy_repeats_and_keeps_the_rate():
    a = tr.poisson_arrivals(tr.rng_for(5, tr.STREAM_ARRIVALS), 1000.0, 20.0)
    b = tr.poisson_arrivals(tr.rng_for(5, tr.STREAM_ARRIVALS), 1000.0, 20.0)
    c = tr.poisson_arrivals(tr.rng_for(6, tr.STREAM_ARRIVALS), 1000.0, 20.0)
    assert np.array_equal(a, b) and not np.array_equal(a[:100], c[:100])
    assert np.all(np.diff(a) >= 0) and a[0] >= 0 and a[-1] < 20.0
    assert abs(len(a) - 20_000) < 5 * np.sqrt(20_000)
    gaps = np.diff(a)
    assert abs(gaps.mean() - 1e-3) < 5e-5 and abs(gaps.std() / gaps.mean() - 1) < 0.05


def test_ycsb_zipfian_is_skewed_and_in_range():
    r = tr.ycsb_zipfian(tr.rng_for(1, 9), 10_000, 0.99, 200_000)
    assert r.min() >= 0 and r.max() < 10_000
    counts = np.bincount(r, minlength=10_000)
    # rank 0 is the most popular; the hottest 1% take a large share
    assert counts[0] == counts.max()
    assert counts[:100].sum() / counts.sum() > 0.3


def test_catalog_texts_repeat_per_seed():
    cat = {"tool_words": [["alpha", "beta", "gamma"], ["delta", "eps"]],
           "general_words": ["api", "data"]}
    spec = {"zipf_theta": 0.99, "words_min": 4, "words_max": 16,
            "general_share": 0.25}
    a = tr.texts(cat, dict(spec, source="catalog_zipfian"), 3, 50)
    assert a == tr.texts(cat, dict(spec, source="catalog_zipfian"), 3, 50)
    assert all(4 <= len(t.split()) <= 16 for t in a)


def test_program_seed_fits_jax():
    for s in (0, 2 ** 31 + 5, 2 ** 40):
        assert 0 <= tr.program_seed(s) < 2 ** 31


def test_spread_is_the_quartile_distance_over_the_median():
    assert stats.spread([1.0, 2.0, 3.0, 4.0, 5.0]) == pytest.approx(3.0 / 3.0)
    assert stats.percentile([1.0, 2.0, 3.0], 50) == 2.0


# -- reference ----------------------------------------------------------------

@pytest.mark.parametrize("algo", ["sonar_lb", "sonar_ft"])
def test_reference_agrees_with_router_select(algo):
    from ref import sonar

    from repro.core.dataset import Server, Tool
    from repro.core.routing import RoutingConfig, make_router

    cfg = dict(_config(), n_servers=60, n_tools=240, vocab_terms=500)
    cat = _system().generate(cfg, 11, _intents())
    tools_of = [[] for _ in cat["servers"]]
    for name, desc, si in cat["tools"]:
        tools_of[si].append(Tool(name, desc))
    servers = [Server(n, c, d, tools_of[i])
               for i, (n, c, d) in enumerate(cat["servers"])]
    r = cfg["routing"]
    router = make_router(algo, servers, RoutingConfig(
        top_s=r["top_s"], top_k=r["top_k"], alpha=r["alpha"], beta=r["beta"],
        gamma=r["gamma"], load_knee=r["load_knee"], load_sharp=r["load_sharp"]))
    ref = sonar.Reference([s[2] for s in cat["servers"]], cat["tools"], r,
                          _intents())
    rng = np.random.default_rng(4)
    n = len(servers)
    texts = tr.catalog_texts(cat, {"zipf_theta": 0.99, "words_min": 4,
                                   "words_max": 16, "general_share": 0.25},
                             rng, 64)
    agree = 0
    for q in texts:
        hist = rng.gamma(2.0, 60.0, (n, 64)).astype(np.float32)
        hist[rng.random(n) < 0.1, -1] = 1200.0          # some offline
        load = (rng.random(n) * 1.5).astype(np.float32)
        dead = rng.random(n) < 0.1 if algo == "sonar_ft" else None
        d = router.select(q, hist, load, failed_mask=dead)
        n_srv = sonar.network_score(hist, r["qos"])
        cand_t, s = ref.fused(q, n_srv, load.astype(np.float64), dead=dead)
        best = int(np.argmax(s))
        assert int(cand_t[best]) == d.tool_idx
        assert s[best] == pytest.approx(d.fused, abs=1e-5)
        agree += 1
    assert agree == 64


def test_control_rounds_like_three_bf16_passes():
    from ref import sonar

    x = np.asarray([1.0 + 2.0 ** -20, 3.14159265, 1e-3], np.float32)
    h = sonar.high_pass(x)
    assert np.all(np.abs(h - x) <= np.abs(x) * 2.0 ** -16)
    assert np.any(h != x)
    assert np.all(sonar.to_bf16(x) != x)


# -- kernel counts ------------------------------------------------------------

def test_score_fuse_counts_by_hand():
    c = manifest.counts(BENCH, "score_fuse")
    # two queries with 30 and 10 candidate tools over a 3,072-term vocabulary
    assert c.flops([30, 10], 3072) == 2 * 40 * 3072
    # 35 distinct tool rows of 3,072 f32 terms, two query rows, three
    # 4-byte per-tool operands, 8 candidate servers and 4 outputs per query
    assert c.bytes_accessed(2, 35, 3072, 8) == (
        35 * 3072 * 4 + 2 * 3072 * 4 + 35 * 3 * 4 + 2 * 8 * 4 + 2 * 4 * 4)


def test_sparse_corpus_matches_dense_bm25():
    """The reference keeps BM25 weights sparse; its scores are the dense
    formula's, term by term."""
    from ref import sonar

    docs = ["alpha beta beta gamma", "beta delta", "gamma gamma gamma",
            "epsilon alpha delta beta"]
    k1, b = 1.5, 0.75
    c = sonar.Corpus(docs, k1, b)
    toks = [sonar.tokenize(d) for d in docs]
    terms = sorted({t for ts in toks for t in ts}, key=c.vocab.get)
    tf = np.asarray([[ts.count(t) for t in terms] for ts in toks], float)
    dl = tf.sum(axis=1)
    df = (tf > 0).sum(axis=0)
    idf = np.log((len(docs) - df + 0.5) / (df + 0.5) + 1.0)
    w = idf * tf * (k1 + 1) / (tf + k1 * (1 - b + b * dl / dl.mean())[:, None])
    q = c.counts("beta gamma gamma unknown")
    assert c.n_terms == len(terms) and q.sum() == 3.0
    want = w.astype(np.float32).astype(np.float64) @ q
    assert np.allclose(c.scores(q), want, rtol=1e-12, atol=0)


def test_runtime_flags_reach_the_tpu_runtime(monkeypatch):
    from harness import cell

    monkeypatch.setenv("LIBTPU_INIT_ARGS", "--a=1")
    cell._runtime_flags({"runtime": {"libtpu_init_args": ["--b=2", "--a=1"]}})
    assert os.environ["LIBTPU_INIT_ARGS"] == "--a=1 --b=2"
    cell._runtime_flags({})
    assert os.environ["LIBTPU_INIT_ARGS"] == "--a=1 --b=2"


# -- trace reduction ----------------------------------------------------------

def _fixture():
    with gzip.open(os.path.join(os.path.dirname(__file__), "data",
                                "trace_agents.json.gz"), "rt") as f:
        return json.load(f)


def test_trace_reduction_on_a_recorded_trace():
    ev = _fixture()
    red = tracefile.reduce(ev)
    assert 0.0 < red["busy_s"] <= red["window_s"]
    assert 0.0 <= red["idle_share"] < 1.0
    ops = red["breakdown"]["device_ops"]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, key=lambda x: -x[1])
    gaps = red["breakdown"]["idle_gaps"]
    assert 0 < len(gaps) <= 10
    # busy and idle tile the window
    idle = sum(v for _, v in red["breakdown"]["idle_gaps"])
    assert idle <= red["window_s"] - red["busy_s"] + 1e-9
    secs, calls = tracefile.kernel_time(red, "fused_score_select_pallas")
    assert secs > 0 and calls > 0


def test_trace_reduction_by_hand():
    ev = {"device": {"/device:TPU:0": [["a", 10.0, 10.0], ["b", 15.0, 10.0],
                                       ["a", 40.0, 20.0]]},
          "host": [["t", "bench.window", 0.0, 100.0],
                   ["t", "outer", 0.0, 100.0], ["t", "inner", 30.0, 5.0]]}
    red = tracefile.reduce(ev)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["busy_s"] == pytest.approx(35e-9)      # [10, 25] and [40, 60]
    assert red["idle_share"] == pytest.approx(0.65)
    gaps = dict(red["breakdown"]["idle_gaps"])
    assert gaps["inner"] == pytest.approx(15e-9)      # [25, 40], middle 32.5
    assert gaps["outer"] == pytest.approx(50e-9)      # [0, 10] and [60, 100]
    assert tracefile.kernel_time(red, "a") == (pytest.approx(30e-9), 2)


# -- the manifest -------------------------------------------------------------

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_manifest_keeps_the_contract():
    man = manifest.load_manifest(REPO)
    assert set(man) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert 1 <= man["run_seconds"] <= 51
    for p in man["paths"]:
        assert os.path.isdir(os.path.join(REPO, p))
    cells = {w["name"]: w for w in man["workloads"]}
    configs = {c["name"]: c for c in man["configs"]}
    metrics = man["end_to_end"] + man["per_layer"]
    for entry in list(cells.values()) + list(configs.values()) + metrics:
        assert NAME.match(entry["name"]), entry["name"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in configs.values():
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        with open(os.path.join(REPO, c["file"])) as f:
            assert json.load(f)["name"] == c["name"]
        assert all(NAME.match(k) for k in c["reduced"])
    for w in cells.values():
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert os.path.isfile(os.path.join(BENCH, "traffic",
                                           f"{w['traffic']}.json"))
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert os.path.isfile(os.path.join(BENCH, "metrics", f"{m['name']}.py"))
        for w in m.get("workloads", []):
            assert w in cells
    e2e = {m["name"]: m for m in man["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in man["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in man["per_layer"]:
        assert m["moves"] in e2e
        target = e2e[m["moves"]]
        for w in m.get("workloads", list(cells)):
            # every cell a per-layer metric lists reports what it moves
            assert w in target.get("workloads", list(cells)), (m["name"], w)
    for w in cells:
        cell = manifest.resolve(w, REPO, man)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2 and cell.per_layer
