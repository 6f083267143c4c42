"""Judge the decisions the timed window made against the reference.

The gateway's state between decisions is a function of what it already
decided: a flush routes its requests in chunks of ``lb_chunk``, each with
the in-flight counts of the earlier chunks' picks, and every completion
advances the telemetry ring by one column of each server's trace.  The
judge replays that state from the recorded flushes, teacher-forced on the
program's own picks, and scores each pick with the reference:

* ``regret``: how far the reference's fused score (Eq. 8) of the picked
  tool lies below the reference's best candidate; a pick outside the
  reference's candidate tools, or a server that does not host the picked
  tool, reads 1;
* ``fused_err``: the gap between the fused score the program reported for
  its pick and the reference's score of that same pick;
* ``missing``: decisions a flush owes and did not return.

A control stands in the program's place with ``control=``: its own picks
and scores replace the recorded ones, and drive the state.
"""
from __future__ import annotations

import numpy as np

from ref import sonar


def _program_rows(rec: dict) -> list:
    """(server, tool, fused) per real row of a recorded flush, with the
    server the gateway handed back."""
    rows = []
    for srv, tool, fused in rec["chunks"]:
        rows += list(zip(np.asarray(srv).tolist(), np.asarray(tool).tolist(),
                         np.asarray(fused, np.float64).tolist()))
    return rows


def judge_gateway(flushes: list, first: int, ref: "sonar.Reference",
                  traces: np.ndarray, gateway: dict, qos: dict,
                  control: "sonar.Reference | None" = None) -> dict:
    history = int(gateway["history"])
    slots = float(gateway["slots_per_replica"])
    chunk = int(gateway["lb_chunk"])
    horizon = traces.shape[1]
    n_servers = traces.shape[0]
    tick = history + sum(len(f["texts"]) for f in flushes[:first])
    regret = fused_err = 0.0
    missing = checked = off_candidate = 0
    worst = None
    for fi in range(first, len(flushes)):
        rec = flushes[fi]
        texts = rec["texts"]
        rows = None if control is not None else _program_rows(rec)
        replicas = rec.get("replicas") if control is None else None
        if rows is not None and len(rows) < len(texts):
            missing += len(texts) - len(rows)
        cols = np.minimum(np.arange(tick - history, tick), horizon - 1)
        hosts = set()
        for q in texts:
            _, cand_t, _ = ref.candidates(q)
            hosts.update(ref.tool_server[cand_t].tolist())
            if control is not None:
                _, cand_c, _ = control.candidates(q)
                hosts.update(control.tool_server[cand_c].tolist())
        hosts = np.asarray(sorted(hosts), np.int64)
        n_server = np.zeros(n_servers, np.float64)
        n_server[hosts] = sonar.network_score(traces[hosts][:, cols], qos)
        in_flight = np.zeros(n_servers, np.float64)
        for lo in range(0, len(texts), chunk):
            load = in_flight / slots
            picked = []
            for j in range(lo, min(lo + chunk, len(texts))):
                cand_t, s = ref.fused(texts[j], n_server, load)
                best = float(s.max())
                if control is not None:
                    ct, cs = control.fused(texts[j], n_server, load)
                    b = int(np.argmax(cs))
                    tool, fused = int(ct[b]), float(cs[b])
                    server = int(control.tool_server[tool])
                    handed = server
                elif j < len(rows):
                    server, tool, fused = rows[j]
                    handed = replicas[j] if replicas is not None else server
                else:
                    continue
                checked += 1
                where = np.flatnonzero(cand_t == tool)
                if (where.size == 0 or server != ref.tool_server[tool]
                        or handed != server):
                    r, off_candidate = 1.0, off_candidate + 1
                else:
                    r = best - float(s[where[0]])
                    fused_err = max(fused_err, abs(fused - float(s[where[0]])))
                if r > regret:
                    regret = r
                    worst = dict(flush=fi, row=j, tool=tool,
                                 best_tool=int(cand_t[int(np.argmax(s))]),
                                 regret=r)
                picked.append(server)
            for server in picked:
                in_flight[server] += 1.0
        tick += len(texts)
    return dict(checked=checked, regret=regret, fused_err=fused_err,
                missing=missing, off_candidate=off_candidate, worst=worst)
