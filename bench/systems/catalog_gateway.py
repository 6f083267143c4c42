"""A generated MCP catalog behind the served gateway.

`generate` turns a catalog configuration (``bench/configs/*.json`` with
``"system": "catalog_gateway"``) and a seed into server and tool
descriptions, with numpy alone.  `build` puts them behind the program's
`SonarGateway` and `AsyncServingGateway` as the configuration states, and
returns the system under test with a recorder of what the timed path
decided.
"""
from __future__ import annotations

import contextlib
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic as tr  # noqa: E402
from ref import sonar  # noqa: E402


def _vocabulary(cfg: dict, intents: dict, rng) -> tuple:
    """(vocab list, general words, per-category head words)."""
    general = list(cfg["assumed"]["general_words"])
    heads = {}
    for cat in cfg["categories"]:
        words = [w for w in cat.lower().split("_") if w]
        intent = cfg["category_intent"].get(cat)
        if intent is not None:
            words += _tokens(intents["canonical"][intent])
            words += dict(intents["keywords"])[intent] if intent in dict(
                intents["keywords"]) else []
        seen, head = set(general), []
        for w in words:
            if w not in seen:
                seen.add(w)
                head.append(w)
        heads[cat] = head
    named = list(general)
    for cat in cfg["categories"]:
        for w in heads[cat]:
            if w not in named:
                named.append(w)
    n_fill = int(cfg["vocab_terms"]) - len(named)
    if n_fill < 0:
        raise ValueError("vocab_terms is smaller than the named words")
    filler = [f"w{i:04d}" for i in range(n_fill)]
    rng.shuffle(filler)
    return named + filler, general, heads


def _tokens(text: str) -> list:
    import re

    return re.findall(r"[a-z0-9]+", text.lower())


def _split(total: int, weights: np.ndarray, floor: int) -> np.ndarray:
    """Integer shares of ``total`` proportional to ``weights``, each at
    least ``floor``, summing to ``total`` exactly (largest remainders)."""
    n = len(weights)
    rest = total - floor * n
    raw = weights / weights.sum() * rest
    out = np.floor(raw).astype(np.int64)
    short = rest - int(out.sum())
    out[np.argsort(-(raw - out), kind="stable")[:short]] += 1
    return out + floor


def generate(cfg: dict, seed: int, intents: dict) -> dict:
    """The catalog of ``cfg`` drawn from ``seed``.

    Returns a dict of plain lists: ``servers`` (name, category,
    description), ``tools`` (name, description, server index), the
    description words of every tool (``tool_words``, for the traffic) and
    the general words."""
    rng = tr.rng_for(seed, tr.STREAM_CATALOG)
    a = cfg["assumed"]
    vocab, general, heads = _vocabulary(cfg, intents, rng)
    index = {w: i for i, w in enumerate(vocab)}
    n_v = len(vocab)
    gen_ids = np.asarray([index[w] for w in general])
    s = float(a["zipf_s"])
    weight = (np.arange(n_v) + 1.0) ** -s
    weight /= weight.sum()

    cats = list(cfg["categories"])
    order = rng.permutation(len(cats))
    share = (np.arange(len(cats)) + 1.0) ** -float(a["servers_per_category_exponent"])
    per_cat = np.zeros(len(cats), np.int64)
    per_cat[order] = _split(int(cfg["n_servers"]), share, 1)
    server_cat = np.repeat(np.arange(len(cats)), per_cat)

    # each category's ranking of the vocabulary (its Zipf order)
    ranking = []
    for cat in cats:
        head = np.asarray([index[w] for w in heads[cat]], np.int64)
        taken = np.zeros(n_v, bool)
        taken[head] = True
        taken[gen_ids] = True
        rest = np.flatnonzero(~taken)
        rng.shuffle(rest)
        ranking.append(np.concatenate([head, gen_ids, rest]))

    def draw(cat: int, k: int) -> np.ndarray:
        return ranking[cat][rng.choice(n_v, size=k, p=weight)]

    lo, hi = a["server_words"]
    servers, seen, server_ids = [], set(), []
    for i, c in enumerate(server_cat):
        while True:
            ids = draw(int(c), int(rng.integers(lo, hi + 1)))
            desc = " ".join(vocab[j] for j in ids)
            if desc not in seen:
                break
        seen.add(desc)
        server_ids.append(ids)
        servers.append((f"{cats[c].lower()}-{i}", cats[c], desc))

    n_tools = int(cfg["n_tools"])
    per_server = _split(
        n_tools, rng.lognormal(0.0, float(a["tools_lognormal_sigma"]),
                               len(servers)), 1,
    )
    lo, hi = a["tool_words"]
    share_srv = float(a["tool_share_from_server"])
    n_name = int(a["tool_name_words"])
    tools, tool_words = [], []
    for si, k_tools in enumerate(per_server):
        c = int(server_cat[si])
        own = server_ids[si]
        for _ in range(int(k_tools)):
            k = int(rng.integers(lo, hi + 1))
            from_srv = rng.random(k) < share_srv
            ids = np.where(from_srv, own[rng.integers(0, len(own), k)],
                           draw(c, k))
            words = [vocab[j] for j in ids]
            name = "_".join(words[j] for j in rng.integers(0, k, n_name))
            tools.append((name, " ".join(words), si))
            tool_words.append(words)
    return dict(servers=servers, tools=tools, tool_words=tool_words,
                general_words=general)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def _span(on: bool, name: str):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Recorder:
    """What the timed path decided, flush by flush.

    Wraps the gateway's ``route_batch`` and its engine's ``route`` on the
    instances: each flush keeps its request texts, the server each request
    was given, and each engine chunk's (server, tool, fused score) of its
    real rows.  It holds references and slices only; the decisions are
    the host arrays the engine already returned."""

    def __init__(self, gw, engine, annotate: bool = False):
        self.flushes: list = []
        orig_batch, orig_route = gw.route_batch, engine.route
        flushes = self.flushes

        def route_batch(texts, client_regions=None, pad_to=None,
                        session_ids=None):
            rec = {"texts": list(texts), "chunks": []}
            flushes.append(rec)
            with _span(annotate, "bench.flush"):
                out = orig_batch(texts, client_regions=client_regions,
                                 pad_to=pad_to, session_ids=session_ids)
            rec["replicas"] = [r.replica_idx for r in out]
            return out

        def route(batch, *args, **kw):
            dec = orig_route(batch, *args, **kw)
            n = kw.get("n_real") or batch.n
            flushes[-1]["chunks"].append(
                (dec.server_idx[:n], dec.tool_idx[:n], dec.fused[:n])
            )
            return dec

        gw.route_batch = route_batch
        engine.route = route


class GatewaySUT:
    """A `SonarGateway` over the generated catalog, with its batching
    policy, recorder and the catalog itself (for traffic and reference)."""

    def __init__(self, cfg: dict, catalog: dict, seed: int, tracing: bool):
        from repro.core import latency as latlib
        from repro.core.dataset import Server, Tool
        from repro.core.qos import QosParams
        from repro.core.routing import RoutingConfig
        from repro.obs import Observability
        from repro.serving.gateway import SonarGateway
        from repro.serving.microbatch import BatchingPolicy

        r, g = cfg["routing"], cfg["gateway"]
        tools_of = [[] for _ in catalog["servers"]]
        for name, desc, si in catalog["tools"]:
            tools_of[si].append(Tool(name, desc))
        servers = [Server(name, cat, desc, tools_of[i])
                   for i, (name, cat, desc) in enumerate(catalog["servers"])]
        states = [
            latlib.outage_profile(probability=0.6),
            latlib.fluctuating_profile(),
            latlib.high_latency_profile(),
            latlib.high_jitter_profile(),
            latlib.ideal_profile(),
        ]
        profiles = [states[i % len(states)] for i in range(len(servers))]
        rcfg = RoutingConfig(
            top_s=r["top_s"], top_k=r["top_k"], alpha=r["alpha"],
            beta=r["beta"], gamma=r["gamma"], load_knee=r["load_knee"],
            load_sharp=r["load_sharp"], expertise_temp=r["expertise_temp"],
            qos=QosParams(**r["qos"]),
        )
        self.cfg = cfg
        self.catalog = catalog
        t0 = time.monotonic()
        self.gw = SonarGateway(
            servers, profiles=profiles, cfg=rcfg, seed=tr.program_seed(seed),
            history=g["history"], use_kernels=g["use_kernels"],
            algo=r["algo"], slots_per_replica=g["slots_per_replica"],
            lb_chunk=g["lb_chunk"], device_telemetry=g["device_telemetry"],
            obs=Observability(trace=False),
        )
        self.engine = self.gw.engine()
        self.setup_phases = {"gateway_s": time.monotonic() - t0}
        self.policy = BatchingPolicy(**cfg["batching"])
        self.recorder = Recorder(self.gw, self.engine, annotate=tracing)
        self.tracing = tracing

    def use_kernels(self) -> bool:
        return bool(self.engine.use_kernels)

    def frontend(self):
        from repro.serving.frontend import AsyncServingGateway

        return AsyncServingGateway(self.gw, self.policy)

    def phases(self) -> dict:
        """(count, total ms) of the gateway's raw per-flush phase
        histograms: encode, dispatch and merge."""
        reg = self.gw.obs.registry
        out = {}
        for ph in ("encode", "dispatch", "merge"):
            h = reg.get(f"gateway_phase_{ph}_ms")
            out[ph] = (int(h.count), float(h.total))
        return out

    def release(self) -> np.ndarray:
        """Drop the program's state and return the simulated network: each
        server's latency trace (ms), the samples the gateway feeds its ring
        one column per completion."""
        traces = np.array(self.gw.traces)
        self.gw = self.engine = None
        return traces


def build(cell, seed: int, tracing: bool = False) -> GatewaySUT:
    t0 = time.monotonic()
    intents = sonar.load_intents(cell.bench_dir)
    catalog = generate(cell.config, seed, intents)
    t_cat = time.monotonic() - t0
    sut = GatewaySUT(cell.config, catalog, seed, tracing)
    sut.setup_phases["catalog_s"] = t_cat
    return sut


CONTROL = "high"          # the reference one step below HIGHEST


def reference(cell, sut, precision: str = "exact"):
    """The numpy reference over this run's catalog; ``precision="high"``
    is the control."""
    cat = sut.catalog
    return sonar.Reference([d for _, _, d in cat["servers"]], cat["tools"],
                           cell.config["routing"], sonar.load_intents(cell.bench_dir),
                           precision=precision)


def judge(cell, sut, window, traces: np.ndarray, ref, control=None) -> dict:
    """The judge's readings of the window's decisions (with ``control``:
    of the control's decisions in their place)."""
    from ref import judge as jg

    return jg.judge_gateway(sut.recorder.flushes, window.first_flush, ref,
                            traces, cell.config["gateway"],
                            cell.config["routing"]["qos"], control=control)


def window_calls(sut, window) -> list:
    """The real rows' texts of every engine call the window made."""
    chunk = int(sut.cfg["gateway"]["lb_chunk"])
    out = []
    for rec in sut.recorder.flushes[window.first_flush:window.n_flushes]:
        t = rec["texts"]
        out += [t[lo:lo + chunk] for lo in range(0, len(t), chunk)]
    return out
