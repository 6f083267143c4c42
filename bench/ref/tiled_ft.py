"""Plain numpy SONAR-FT over a template-tiled fleet: the reference the
paper-pool cell's decisions are checked against, and its judge.

It imports nothing of the program.  The fleet is ``n`` replicas of a few
template servers (replica i is template ``i mod len(pool)``, as the
configuration states); the reference builds its own BM25 weights from the
template texts with the corpus statistics of the *expanded* fleet (each
template counted as often as it is replicated), rounds them once to the
storage precision the configuration states (that rounding is the storage
contract), and scores stage 1 over every replica, block by block, with
failed replicas set below every live one before the top-s.  Everything
else is `ref.sonar`'s: the tool-type prediction, Eq. 5 softmax, Eq. 7
QoS, the load penalty, in float64.

The inputs it shares with the program are the simulated network (one
latency trace per telemetry template, the replica -> template map and the
replicas that are down) and, like the traces, the health probes each
request drew: which ejected replicas it re-admitted as candidates.  The
judge checks the share of those re-admissions against the probe
probability.
"""
from __future__ import annotations

import numpy as np

from ref import sonar

BLOCK = 1 << 17          # replicas scored per stage-1 block
OFFLINE_MS = 1000.0      # a call whose latency reaches this failed


def stored(w: np.ndarray, dtype: str, precision: str) -> np.ndarray:
    """Weights as the scoring reads them: rounded once to the storage
    ``dtype``; with ``precision="high"`` as ``Precision.HIGH``'s three
    bf16 passes carry them (bf16 weights pass unchanged)."""
    w = np.asarray(w, np.float32)
    if dtype in ("bfloat16", "bf16"):
        w = sonar.to_bf16(w)
    elif dtype not in ("float32", "f32"):
        raise ValueError(dtype)
    return sonar.high_pass(w) if precision == "high" else w


class TiledCorpus:
    """BM25 weights of template documents, template i standing for
    ``counts[i]`` identical documents: IDF, document lengths and their
    average are those of the expanded corpus (paper Eq. 1)."""

    def __init__(self, docs: list, counts: np.ndarray, k1: float, b: float,
                 dtype: str, precision: str):
        toks = [sonar.tokenize(d) for d in docs]
        self.vocab: dict = {}
        for ts in toks:
            for t in ts:
                self.vocab.setdefault(t, len(self.vocab))
        tf = np.zeros((len(docs), max(len(self.vocab), 1)), np.float64)
        for i, ts in enumerate(toks):
            for t in ts:
                tf[i, self.vocab[t]] += 1.0
        counts = np.asarray(counts, np.float64)
        n = counts.sum()
        dl = tf.sum(axis=1)
        avgdl = max(float((dl * counts).sum() / n), 1e-6)
        df = ((tf > 0) * counts[:, None]).sum(axis=0)
        idf = np.log((n - df + 0.5) / (df + 0.5) + 1.0)
        w = idf[None, :] * tf * (k1 + 1.0) / (
            tf + k1 * (1.0 - b + b * dl / avgdl)[:, None])
        w = np.where(tf > 0, w, 0.0)
        self.weights = stored(w, dtype, precision).astype(np.float64)

    def scores(self, text: str) -> np.ndarray:
        """[n_templates] BM25 scores of ``text``, float64."""
        q = np.zeros(self.weights.shape[1], np.float64)
        for t in sonar.tokenize(text):
            j = self.vocab.get(t)
            if j is not None:
                q[j] += 1.0
        return self.weights @ q


def _stable_top(values: np.ndarray, ids: np.ndarray, k: int) -> tuple:
    """The ``k`` largest ``values`` (ties to the lower id), best first."""
    order = np.lexsort((ids, -values))[:k]
    return values[order], ids[order]


def _block_top(s: np.ndarray, k: int) -> np.ndarray:
    """Positions of the ``k`` largest of ``s`` (ties to the lower
    position), without sorting the block."""
    k = min(k, s.size)
    v = np.partition(s, s.size - k)[s.size - k]
    above = np.flatnonzero(s > v)
    tied = np.flatnonzero(s == v)[: k - above.size]
    return np.concatenate([above, tied])


class Reference:
    """SONAR-FT over ``n`` replicas of the template ``pool``."""

    def __init__(self, pool: list, n: int, routing: dict, intents: dict,
                 weights_dtype: str, precision: str = "exact"):
        """``precision``: ``"exact"``; ``"high"``, the BM25 operands as
        ``Precision.HIGH`` carries them; ``"bf16"``, the score terms and
        their fusion computed in bfloat16, one step below the float32
        the configuration states."""
        if precision not in ("exact", "high", "bf16"):
            raise ValueError(precision)
        self.precision = precision
        self.r = routing
        self.intents = intents
        self.n = int(n)
        k1, b = routing["bm25_k1"], routing["bm25_b"]
        m = len(pool)
        self.server_tpl = np.arange(self.n, dtype=np.int64) % m
        mult = np.bincount(self.server_tpl, minlength=m)
        self.servers = TiledCorpus([s["description"] for s in pool], mult,
                                   k1, b, weights_dtype, precision)
        tool_docs, tool_tpl = [], []
        for ti, s in enumerate(pool):
            for t in s["tools"]:
                tool_docs.append(f"{t['name'].replace('_', ' ')} "
                                 f"{t['description']}")
                tool_tpl.append(ti)
        tool_tpl = np.asarray(tool_tpl, np.int64)
        self.tools = TiledCorpus(tool_docs, mult[tool_tpl], k1, b,
                                 weights_dtype, precision)
        self.per_tpl = np.bincount(tool_tpl, minlength=m)
        self.doc0 = np.concatenate([[0], np.cumsum(self.per_tpl)])[:-1]
        per_server = self.per_tpl[self.server_tpl]
        self.k_slot = int(self.per_tpl.max())
        self.first_tool = np.cumsum(per_server) - per_server
        self.n_tools = int(per_server.sum())
        self._tool_server = np.repeat(np.arange(self.n), per_server)
        self._cache: dict = {}

    def tool_server(self, tool: int) -> int:
        return int(self._tool_server[tool])

    def stage1(self, text: str, dead: np.ndarray) -> np.ndarray:
        """The top-s replicas of a (predicted) query text, over every
        replica in blocks; ``dead`` (sorted ids) score below every live
        replica and re-fill the tail in id order."""
        tpl = self.servers.scores(text)
        top_s = min(int(self.r["top_s"]), self.n)
        best_v = np.empty(0, np.float64)
        best_i = np.empty(0, np.int64)
        for lo in range(0, self.n, BLOCK):
            hi = min(lo + BLOCK, self.n)
            s = tpl[self.server_tpl[lo:hi]]
            d = dead[(dead >= lo) & (dead < hi)] - lo
            s[d] = -np.inf
            pos = _block_top(s, top_s)
            best_v, best_i = _stable_top(np.concatenate([best_v, s[pos]]),
                                         np.concatenate([best_i, pos + lo]),
                                         top_s)
        return best_i

    def candidates(self, query: str, dead: np.ndarray) -> tuple:
        """(candidate servers, candidate tools, expertise C) of a query
        with the failed replicas ``dead``."""
        text = sonar.predict(query, self.intents)
        key = (text, dead.tobytes())
        hit = self._cache.get(key)
        if hit is not None:
            return hit
        cand_s = np.sort(self.stage1(text, dead))
        slot = np.arange(self.k_slot)[None, :]
        ok = slot < self.per_tpl[self.server_tpl[cand_s]][:, None]
        tools = (self.first_tool[cand_s][:, None] + slot)[ok]
        docs = (self.doc0[self.server_tpl[cand_s]][:, None] + slot)[ok]
        tpl_t = self.tools.scores(text)
        k = min(int(self.r["top_k"]), tools.size)
        vals, cand_t = _stable_top(tpl_t[docs], tools, k)
        z = (vals - vals.max()) / self.r["expertise_temp"]
        c = np.exp(z) / np.exp(z).sum()
        hit = (cand_s, cand_t, c)
        if len(self._cache) < 4096:
            self._cache[key] = hit
        return hit

    def fused(self, query: str, n_of, load: dict, dead: np.ndarray) -> tuple:
        """(candidate tools, fused scores S) of one query: Eq. 8 with
        SONAR-LB's load term; a failed replica's tools score -inf.
        ``n_of(hosts)`` is the QoS score of each host, ``load`` the
        utilization of the replicas that have any."""
        _, cand_t, c = self.candidates(query, dead)
        hosts = self._tool_server[cand_t]
        r = self.r
        u = np.asarray([load.get(int(h), 0.0) for h in hosts], np.float64)
        terms = (c, n_of(hosts),
                 sonar.load_penalty(u, r["load_knee"], r["load_sharp"]))
        if self.precision == "bf16":
            bf = sonar.to_bf16
            c, n, pen = (bf(t) for t in terms)
            s = bf(bf(bf(r["alpha"] * c) + bf(r["beta"] * n))
                   - bf(r["gamma"] * pen))
        else:
            c, n, pen = terms
            s = r["alpha"] * c + r["beta"] * n - r["gamma"] * pen
        s = np.where(np.isin(hosts, dead), -np.inf, np.asarray(s, np.float64))
        return cand_t, s


def _rows(rec: dict) -> list:
    rows = []
    for srv, tool, fused in rec["chunks"]:
        rows += list(zip(np.asarray(srv).tolist(), np.asarray(tool).tolist(),
                         np.asarray(fused, np.float64).tolist()))
    return rows


def judge_pool(flushes: list, first: int, ref: Reference,
               trace_rows: np.ndarray, trace_map: np.ndarray,
               down: np.ndarray, gateway: dict, qos: dict,
               telemetry_dtype: str,
               control: "Reference | None" = None) -> dict:
    """Replay every recorded flush, teacher-forced on the program's picks
    (or, with ``control``, on the control's own), and read the window's
    decisions.

    The state a decision sees is a function of what was decided before
    it: the telemetry ring (one column of every replica's trace per
    completion, rounded to the ring's dtype, with the completed call's
    own latency in its replica's place), the in-flight counts of the
    flush's earlier chunks, and the health of every replica (its streak
    of failed calls; ``eject_after`` in a row ejects it, a success
    readmits it).  A call fails when its latency reaches the offline
    latency; a call to a replica in ``down`` takes at least that.
    Health moves when a flush's answers complete, in their order.
    Besides the readings of `ref.judge`, ``health_mismatch`` counts the
    window's decisions whose ejected set differs from the replay's,
    ``ejected_seen`` those that saw any replica ejected, and ``probe_z``
    how far the share of ejected replicas the window's requests re-admitted
    lies from ``probe_prob``, in standard deviations of that many
    independent draws."""
    history = int(gateway["history"])
    slots = float(gateway["slots_per_replica"])
    chunk = int(gateway["lb_chunk"])
    eject_after = int(gateway["eject_after"])
    horizon = trace_rows.shape[1]
    rows_t = np.asarray(trace_rows, np.float32)
    if telemetry_dtype in ("bfloat16", "bf16"):
        ring_rows = sonar.to_bf16(rows_t)
    else:
        ring_rows = rows_t
    tmap = np.asarray(trace_map, np.int64)
    down = {int(i) for i in down}
    as_ring = sonar.to_bf16 if ring_rows is not rows_t else np.float32
    own: dict = {}          # replica -> {tick: latency} where its call's
                            # latency took its template's place in the ring
    streak: dict = {}
    ejected: set = set()
    tick = history
    trials = admitted = 0
    regret = fused_err = 0.0
    missing = checked = off_candidate = health_mismatch = ejected_seen = 0
    worst = None
    for fi, rec in enumerate(flushes):
        texts = rec["texts"]
        judged = fi >= first
        rows = _rows(rec) if control is None else None
        replicas = rec.get("replicas")
        health = rec.get("health", [])
        if judged and rows is not None and len(rows) < len(texts):
            missing += len(texts) - len(rows)
        cols = np.minimum(np.arange(tick - history, tick), horizon - 1)
        n_tpl = sonar.network_score(ring_rows[:, cols], qos)
        own = {h: {c: v for c, v in ts.items() if c >= tick - history}
               for h, ts in own.items()}
        own = {h: ts for h, ts in own.items() if ts}
        n_own = {}
        for h, ts in own.items():
            row = ring_rows[tmap[h], cols].copy()
            for c, v in ts.items():
                row[c - (tick - history)] = v
            n_own[h] = float(sonar.network_score(row[None], qos)[0])

        def n_of(hosts, n_tpl=n_tpl, n_own=n_own):
            n = n_tpl[tmap[hosts]]
            for i, h in enumerate(hosts.tolist()):
                if h in n_own:
                    n[i] = n_own[h]
            return n

        e_ref = np.asarray(sorted(ejected), np.int64)
        in_flight: dict = {}
        order = []
        for ci, lo in enumerate(range(0, len(texts), chunk)):
            load = {s: v / slots for s, v in in_flight.items()}
            e_seen, probes = (health[ci] if ci < len(health)
                              else (e_ref, None))
            picked = []
            for j in range(lo, min(lo + chunk, len(texts))):
                pr = (probes[j - lo] if probes is not None
                      and j - lo < len(probes) else np.empty(0, np.int64))
                dead = np.setdiff1d(e_ref, pr)
                if dead.size >= ref.n:
                    dead = np.empty(0, np.int64)
                if control is not None:
                    ct, cs = control.fused(texts[j], n_of, load, dead)
                    b = int(np.argmax(cs))
                    tool, fused = int(ct[b]), float(cs[b])
                    server = handed = control.tool_server(tool)
                elif j < len(rows):
                    server, tool, fused = rows[j]
                    handed = replicas[j] if replicas is not None else server
                    if judged and not np.array_equal(e_seen, e_ref):
                        health_mismatch += 1
                    if judged and e_seen.size:
                        trials += e_seen.size
                        admitted += pr.size
                else:
                    continue
                picked.append(server)
                order.append(handed)
                if not judged:
                    continue
                cand_t, s = ref.fused(texts[j], n_of, load, dead)
                checked += 1
                ejected_seen += e_ref.size > 0
                where = np.flatnonzero(cand_t == tool)
                best = float(s.max())
                if (where.size == 0 or server != ref.tool_server(tool)
                        or handed != server):
                    r = 1.0
                    off_candidate += 1
                elif best == -np.inf:          # every candidate failed:
                    r = 0.0 if tool == int(cand_t[0]) else 1.0
                else:
                    r = best - float(s[where[0]])
                    fused_err = max(fused_err, abs(fused - float(s[where[0]])))
                if r > regret:
                    regret = r
                    worst = dict(flush=fi, row=j, tool=tool,
                                 best_tool=int(cand_t[int(np.argmax(s))]),
                                 regret=r)
            for server in picked:
                in_flight[server] = in_flight.get(server, 0.0) + 1.0
        # the flush's answers complete in order: one tick each
        for server in order:
            lat = float(rows_t[tmap[server], min(tick, horizon - 1)])
            if server in down:
                lat = max(lat, OFFLINE_MS)
                own.setdefault(server, {})[tick] = float(
                    as_ring(np.float32(lat)))
            ok = lat < OFFLINE_MS
            if ok:
                streak[server] = 0
                ejected.discard(server)
            else:
                streak[server] = streak.get(server, 0) + 1
                if streak[server] >= eject_after:
                    ejected.add(server)
            tick += 1
    p = float(gateway["probe_prob"])
    probe_z = (abs(admitted - trials * p) / np.sqrt(trials * p * (1.0 - p))
               if trials else 0.0)
    return dict(checked=checked, regret=regret, fused_err=fused_err,
                missing=missing, off_candidate=off_candidate,
                health_mismatch=health_mismatch, ejected_seen=ejected_seen,
                probe_z=float(probe_z), probe_share=(
                    admitted / trials if trials else None),
                worst=worst)
