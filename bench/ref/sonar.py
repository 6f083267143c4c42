"""Plain numpy SONAR-LB and SONAR-FT, the reference the benchmark checks
the program's decisions against.

It imports nothing of the program and takes nothing the program made:
it tokenizes the catalog's texts, builds its own BM25 corpora (paper
Eq. 1-5, Okapi with k1 and b from the configuration), predicts the tool
type from ``ref/intents.json``, scores QoS (Eq. 6-7) from raw latency
samples and fuses (Eq. 8) with the load penalty of SONAR-LB, in float64
over the float32 corpus weights the configuration states.  The one input
it shares with the program is the simulated network: the latency samples
the gateway is fed.

``precision="high"`` is the control: every BM25 matmul operand rounded as
a TPU's ``Precision.HIGH`` (three bf16 passes) sees it, one step below the
``HIGHEST`` the configuration states.
"""
from __future__ import annotations

import json
import os
import re
from typing import Sequence

import ml_dtypes
import numpy as np

_TOKEN = re.compile(r"[a-z0-9]+")


def tokenize(text: str) -> list:
    return _TOKEN.findall(text.lower())


def to_bf16(x: np.ndarray) -> np.ndarray:
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(np.float32)


def high_pass(x: np.ndarray) -> np.ndarray:
    """``x`` as three bf16 passes carry it: the bf16 head plus the bf16 of
    the remainder (the head-by-head, head-by-tail, tail-by-head products
    of an integer query count keep exactly these two terms)."""
    hi = to_bf16(x)
    return hi + to_bf16(np.asarray(x, np.float32) - hi)


class Corpus:
    """BM25 weights of a list of documents: W[d, t] = IDF(t) * tf (k1 + 1)
    / (tf + k1 (1 - b + b |d| / avgdl)), stored in float32.  Kept sparse,
    one value per (document, term) pair that occurs: a catalog's matrix is
    almost all zeros."""

    def __init__(self, docs: Sequence[str], k1: float, b: float):
        toks = [tokenize(d) for d in docs]
        self.vocab = {}
        for ts in toks:
            for t in ts:
                self.vocab.setdefault(t, len(self.vocab))
        n, v = len(docs), max(len(self.vocab), 1)
        rows = np.repeat(np.arange(n, dtype=np.int64), [len(ts) for ts in toks])
        cols = np.asarray([self.vocab[t] for ts in toks for t in ts], np.int64)
        pair, tf = np.unique(rows * v + cols, return_counts=True)
        self.doc, self.term = pair // v, pair % v
        tf = tf.astype(np.float64)
        dl = np.bincount(self.doc, weights=tf, minlength=n)
        avgdl = max(dl.mean(), 1e-6)
        df = np.bincount(self.term, minlength=v)
        idf = np.log((n - df + 0.5) / (df + 0.5) + 1.0)
        norm = k1 * (1.0 - b + b * dl / avgdl)
        self.values = (idf[self.term] * tf * (k1 + 1.0)
                       / (tf + norm[self.doc])).astype(np.float32)
        self.n_docs, self.n_terms = n, v

    def counts(self, text: str) -> np.ndarray:
        q = np.zeros(self.n_terms, np.float64)
        for t in tokenize(text):
            j = self.vocab.get(t)
            if j is not None:
                q[j] += 1.0
        return q

    def scores(self, q: np.ndarray) -> np.ndarray:
        """W @ q in float64 over the stored weights."""
        return np.bincount(self.doc, minlength=self.n_docs,
                           weights=self.values.astype(np.float64) * q[self.term])


def load_intents(bench_dir: str) -> dict:
    """SONAR's tool-type prediction as data (``ref/intents.json``)."""
    with open(os.path.join(bench_dir, "ref", "intents.json")) as f:
        return json.load(f)


def predict(query: str, intents: dict) -> str:
    """The canonical description SONAR routes ``query`` as."""
    toks = tokenize(query)
    scores = {k: 0.0 for k, _ in intents["keywords"]}
    for pos, t in enumerate(toks):
        vote = (intents["early_vote"] if pos < intents["early_positions"]
                else intents["late_vote"])
        for intent, kws in intents["keywords"]:
            if t in kws:
                scores[intent] += vote
    best_intent, best = intents["default_intent"], intents["prior"]
    if toks and toks[0] in intents["question_words"]:
        best = intents["question_prior"]
    for intent, _ in intents["keywords"]:
        if scores[intent] > best:
            best_intent, best = intent, scores[intent]
    return intents["canonical"][best_intent]


def network_score(lat: np.ndarray, q: dict) -> np.ndarray:
    """Eq. 7 with the offline clamp: lat [n, T] ms -> N [n] in [-1, 1]."""
    lat = np.asarray(lat, np.float64)
    T = lat.shape[-1]
    w_n = min(int(q["window"]), T)
    a = q["ewma_alpha"]
    ew = lat[:, 0].copy()          # the average's state before the window
    for t in range(T):
        ew = a * lat[:, t] + (1.0 - a) * ew
    recent = lat[:, T - w_n:]
    x = np.arange(w_n, dtype=np.float64) - (w_n - 1) / 2.0
    slope = (recent * x).sum(axis=1) / max((x * x).sum(), 1e-6)
    p_trend = np.clip(slope * w_n / q["trend_scale_ms"], 0.0, 1.0)
    p_high = np.clip((ew - q["ideal_high_ms"]) / (4.0 * q["ideal_high_ms"]),
                     0.0, 1.0)
    p_out = np.clip(2.0 * (recent > q["outage_risk_ms"]).sum(axis=1) / w_n,
                    0.0, 1.0)
    mean = recent.mean(axis=1)
    cv = np.sqrt(((recent - mean[:, None]) ** 2).mean(axis=1)) / np.maximum(
        mean, 1e-6)
    p_inst = np.clip((cv - q["cv_low"]) / q["cv_scale"], 0.0, 1.0)
    excess = (np.maximum(ew - q["ideal_high_ms"], 0.0)
              + np.maximum(q["ideal_low_ms"] - ew, 0.0))
    base = 1.0 / (1.0 + excess / q["base_scale_ms"])
    n = (base * (1.0 - q["w_high"] * p_high) * (1.0 - q["w_trend"] * p_trend)
         * (1.0 - q["w_outage"] * p_out) * (1.0 - q["w_instab"] * p_inst))
    return np.where(lat[:, -1] >= q["offline_ms"], -1.0, n)


def load_penalty(rho: np.ndarray, knee: float, sharp: float) -> np.ndarray:
    x = np.maximum(rho, 0.0)
    return x + sharp * np.maximum(x - knee, 0.0) ** 2


class Reference:
    """SONAR-LB (and SONAR-FT with a failed mask) over one catalog."""

    def __init__(self, server_docs: Sequence[str], tools: Sequence[tuple],
                 routing: dict, intents: dict, precision: str = "exact"):
        self.r = routing
        self.intents = intents
        k1, b = routing["bm25_k1"], routing["bm25_b"]
        self.servers = Corpus(server_docs, k1, b)
        self.tools = Corpus(
            [f"{name.replace('_', ' ')} {desc}" for name, desc, _ in tools],
            k1, b,
        )
        self.tool_server = np.asarray([si for _, _, si in tools], np.int64)
        if precision == "high":
            self.servers.values = high_pass(self.servers.values)
            self.tools.values = high_pass(self.tools.values)
        elif precision != "exact":
            raise ValueError(precision)
        self._cache: dict = {}

    def candidates(self, query: str, dead=None) -> tuple:
        """(candidate servers, candidate tools, expertise C) of a query:
        the top-s servers by stage-1 BM25 (ties to the lower index, failed
        servers demoted below every live one), then the top-k of their
        tools by stage-2 BM25, softmax-normalized (Eq. 5)."""
        text = predict(query, self.intents)
        key = (text, None if dead is None else dead.tobytes())
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        s = self.servers.scores(self.servers.counts(text))
        if dead is not None:
            s = np.where(dead, -np.inf, s)
        cand_s = np.argsort(-s, kind="stable")[: self.r["top_s"]]
        in_c = np.isin(self.tool_server, cand_s)
        t = np.where(in_c, self.tools.scores(self.tools.counts(text)), -np.inf)
        k = min(self.r["top_k"], int(in_c.sum()))
        cand_t = np.argsort(-t, kind="stable")[:k]
        z = (t[cand_t] - t[cand_t].max()) / self.r["expertise_temp"]
        c = np.exp(z) / np.exp(z).sum()
        hit = (cand_s, cand_t, c)
        self._cache[key] = hit
        return hit

    def tool_rows(self, query: str, dead=None) -> np.ndarray:
        """The tools hosted by the query's stage-1 candidate servers: the
        rows stage 2 has to score."""
        cand_s, _, _ = self.candidates(query, dead)
        return np.flatnonzero(np.isin(self.tool_server, cand_s))

    def fused(self, query: str, n_server: np.ndarray, load: np.ndarray,
              dead=None) -> tuple:
        """(candidate tools, fused scores S) of one query given the QoS
        score and utilization of every server (Eq. 8 with SONAR-LB's load
        term; a failed server's candidates score -inf)."""
        _, cand_t, c = self.candidates(query, dead)
        hosts = self.tool_server[cand_t]
        r = self.r
        s = (r["alpha"] * c + r["beta"] * n_server[hosts]
             - r["gamma"] * load_penalty(load[hosts], r["load_knee"],
                                         r["load_sharp"]))
        if dead is not None:
            s = np.where(dead[hosts], -np.inf, s)
        return cand_t, s
