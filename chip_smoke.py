#!/usr/bin/env python3
"""Chip smoke test: the SONAR routing path on a TPU, kernels compiled.

    python chip_smoke.py               # one chip: served, parity, mega
    python chip_smoke.py --four-chips  # four chips: 4-shard mesh vs 1 chip

Phases (each raises on failure; the script then exits non-zero):

* served: the ``launch/serve.py --mode online`` path — `SonarGateway` with
  the device telemetry ring behind `AsyncServingGateway` — over 16,384
  replicas (the ~16k APIs of ToolBench, arXiv:2307.16789) routing a few
  hundred Poisson requests with SONAR-LB; offered == routed + shed +
  expired, routed > 0.
* parity: on a 16,384-server catalog with distinct descriptions and one
  telemetry snapshot, `BatchRoutingEngine` with compiled kernels, the same
  engine on the jnp path and the scalar host `Router.select` pick the same
  (server, tool) for SONAR, SONAR-LB, SONAR-FT and SONAR-GEO.
* mega: `ShardedRoutingEngine(n_shards=1)` on a 10^6-server bf16 tiled
  index routes SONAR-FT with load, age and failed-mask operands; kernel
  and jnp paths pick the same (server, tool).
* four_chips (``--four-chips`` only): the 10^6-server engine on a 4-device
  fleet mesh and `SonarGateway(shards=4)` with its telemetry ring sharded
  over that mesh pick what their one-chip counterparts pick.

Every engine route program that should hold the Pallas kernels is compiled
and checked for ``tpu_custom_call``.  The script refuses to run where JAX
finds no TPU.  The last line of stdout is one JSON object naming the
device.  Fleets, telemetry and queries are generated from ``--seed``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import bm25  # noqa: E402
from repro.core import latency as latlib  # noqa: E402
from repro.core.batch_routing import BatchRoutingEngine  # noqa: E402
from repro.core.dataset import Server, build_query_dataset, build_server_pool  # noqa: E402
from repro.core.mesh_routing import ShardedRoutingEngine  # noqa: E402
from repro.core.routing import RoutingConfig, make_router  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_fleet_mesh  # noqa: E402
from repro.serving.gateway import SonarGateway  # noqa: E402
from repro.traffic import mega_fleet_index, mega_platform, telemetry_palette  # noqa: E402

PARITY_ALGOS = ("sonar", "sonar_lb", "sonar_ft", "sonar_geo")
# fused scores of the kernel path vs the jnp and scalar paths: the
# sequential vs tree softmax-denominator carve-out (docs/benchmarks.md)
RTOL, ATOL = 2e-6, 2e-7
HISTORY = 64          # gateway telemetry window (SonarGateway default)


def catalog(n: int, seed: int) -> list:
    """``n`` servers with distinct descriptions: the 15-server pool tiled,
    each copy's description extended by 1-3 seeded words of the pool's own
    vocabulary, redrawn until no other server has it.  A plainly tiled
    pool ties on BM25 across copies and could never show a
    scoring-precision flip."""
    pool = build_server_pool(seed)
    vocab = sorted({w for s in pool for w in bm25.tokenize(s.description)})
    rng = np.random.default_rng(seed)
    out, seen = [], set()
    for i in range(n):
        t = pool[i % len(pool)]
        desc = t.description
        while desc in seen or desc == t.description:
            extra = " ".join(rng.choice(vocab, size=int(rng.integers(1, 4))))
            desc = f"{t.description} {extra}"
        seen.add(desc)
        out.append(Server(f"{t.name}-{i}", t.domain, desc, list(t.tools)))
    return out


def snapshot(n: int, seed: int) -> tuple:
    """One routing-time telemetry snapshot for ``n`` servers: latency
    window [n, HISTORY] ms, utilization, telemetry age (s), failed mask
    (~5%) and client RTT (ms)."""
    palette = telemetry_palette(16, seed)
    packed = latlib.pack_profiles([palette[i % 16] for i in range(n)])
    hist = latlib.generate_traces_cached(seed, packed, HISTORY)
    rng = np.random.default_rng(seed + 1)
    load = (rng.random(n) * 1.5).astype(np.float32)
    age = (rng.random(n) * 400.0).astype(np.float32)
    mask = rng.random(n) < 0.05
    rtt = (rng.random(n) * 300.0).astype(np.float32)
    return np.asarray(hist, np.float32), load, age, mask, rtt


# requests led by the keywords of each non-websearch intent: SONAR maps a
# query to its intent's canonical description, so a batch of web-search
# questions alone would reach one intent and one decision
OTHER_INTENTS = (
    "refactor the bug in this function", "buy and order from the amazon catalog",
    "run a sql query on the postgres database", "forecast rain and temperature",
    "stock ticker earnings for my portfolio", "flight and hotel booking",
    "linkedin profile of a recruiter", "read the file at this path",
    "email to my inbox", "schedule a meeting on my calendar",
    "transcribe this audio recording", "describe this image",
)


def queries(n: int, seed: int) -> list:
    """MCPBench-style web-search questions interleaved with requests for
    every other intent."""
    web = [q.text for q in build_query_dataset(n, seed=seed)]
    return [web[i] if i % 2 == 0 else OTHER_INTENTS[(i // 2) % len(OTHER_INTENTS)]
            for i in range(n)]


def require(ok: bool, msg) -> None:
    """Fail the phase unless ``ok`` (an ``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(msg)


def check_compiled(eng, batch, *args, interpret: bool, **kw) -> float:
    """Compile the engine's route program for these inputs; unless the
    kernels run interpreted, demand the compiled Pallas kernels in it.
    Returns the compile seconds."""
    t0 = time.perf_counter()
    text = eng.lower(batch, *args, **kw).compile().as_text()
    dt = time.perf_counter() - t0
    if not interpret:
        require("tpu_custom_call" in text, "route program holds no TPU kernel")
    return dt


def same_picks(name: str, a, b) -> None:
    """(server, tool) picks of two decision sets must match row for row."""
    a = np.stack([np.asarray(a[0]), np.asarray(a[1])], axis=1)
    b = np.stack([np.asarray(b[0]), np.asarray(b[1])], axis=1)
    bad = np.flatnonzero(np.any(a != b, axis=1))
    require(bad.size == 0, (
        f"{name}: {bad.size}/{len(a)} picks differ, first rows "
        f"{bad[:4].tolist()}: {a[bad[:4]].tolist()} vs {b[bad[:4]].tolist()}"
    ))


def picks(dec) -> tuple:
    return dec.server_idx, dec.tool_idx


def precision_probe(batch, weights: np.ndarray, top_s: int) -> dict:
    """Stage-1 BM25 scores of ``batch`` at the device's default f32 matmul
    precision against ``Precision.HIGHEST``, which every routing matmul
    uses: the largest relative score error, and the queries whose top-s
    candidate set the default precision would change."""
    q, w = jnp.asarray(batch.q_server), jnp.asarray(weights, jnp.float32)
    hi = jnp.matmul(q, w.T, precision=jax.lax.Precision.HIGHEST)
    lo = jnp.matmul(q, w.T)
    top = [np.sort(np.asarray(jax.lax.top_k(x, top_s)[1]), axis=1)
           for x in (hi, lo)]
    scale = float(jnp.max(jnp.abs(hi)))
    return dict(
        default_rel_err=float(jnp.max(jnp.abs(hi - lo))) / max(scale, 1e-30),
        default_topk_changed=int(np.any(top[0] != top[1], axis=1).sum()),
    )


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def phase_served(n_replicas: int, n_requests: int, rate: float, seed: int,
                 interpret: bool) -> dict:
    args = serve.build_parser().parse_args([
        "--mode", "online", "--algo", "sonar_lb", "--arrivals", "poisson",
        "--n-replicas", str(n_replicas), "--n-requests", str(n_requests),
        "--rate", str(rate), "--horizon-s", str(2.0 * n_requests / rate),
        "--seed", str(seed), "--quiet",
    ])
    summary, gw = serve.serve_online(args)
    require(summary["offered"] == n_requests, summary)
    require(summary["offered"] == (
        summary["routed"] + summary["shed"] + summary["expired"]
    ), summary)
    require(summary["routed"] > 0, summary)
    eng = gw.engine()
    require(isinstance(eng, BatchRoutingEngine), type(eng))
    batch = eng.encode((serve.QUERIES * gw.lb_chunk)[:gw.lb_chunk])
    compile_s = check_compiled(
        eng, batch, gw.telemetry, gw.in_flight / gw.capacity,
        interpret=interpret,
    )
    require(eng.use_kernels or interpret, "gateway engine left the kernel path")
    return dict(summary, compile_s=round(compile_s, 3))


def phase_parity(n_servers: int, n_queries: int, seed: int,
                 interpret: bool) -> dict:
    servers = catalog(n_servers, seed)
    hist, load, age, mask, rtt = snapshot(n_servers, seed)
    texts = queries(n_queries, seed)
    cfg = RoutingConfig(top_s=8, top_k=8)
    tel = (hist, load, age, mask, rtt)
    out = {}
    index = make_router("sonar", servers, cfg).index
    out["precision"] = precision_probe(
        BatchRoutingEngine(servers, cfg, algo="sonar", use_kernels=False,
                           index=index).encode(texts),
        index.server_corpus.weights, cfg.top_s,
    )
    for algo in PARITY_ALGOS:
        router = make_router(algo, servers, cfg)
        krn = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=True,
                                 interpret=interpret, index=router.index)
        ref = BatchRoutingEngine(servers, cfg, algo=algo, use_kernels=False,
                                 index=router.index)
        batch = krn.encode(texts)
        compile_s = check_compiled(krn, batch, *tel, interpret=interpret)
        d_k = krn.route(batch, *tel)
        d_j = ref.route(batch, *tel)
        scalar = [
            router.select(q, hist, load, telemetry_age_s=age,
                          failed_mask=mask, client_rtt_ms=rtt)
            for q in texts
        ]
        d_s = (np.asarray([d.server_idx for d in scalar]),
               np.asarray([d.tool_idx for d in scalar]))
        f_s = np.asarray([d.fused for d in scalar], np.float32)
        same_picks(f"{algo} kernel vs jnp", picks(d_k), picks(d_j))
        same_picks(f"{algo} kernel vs scalar", picks(d_k), d_s)
        np.testing.assert_allclose(d_k.fused, d_j.fused, rtol=RTOL, atol=ATOL)
        np.testing.assert_allclose(d_k.fused, f_s, rtol=RTOL, atol=ATOL)
        out[algo] = dict(
            compile_s=round(compile_s, 3),
            distinct_servers=int(np.unique(d_k.server_idx).size),
            max_fused_diff=float(np.max(np.abs(d_k.fused - f_s))),
        )
    return out


def mega_inputs(n_servers: int, seed: int) -> tuple:
    """bf16 tiled index over ``n_servers`` and a dense [n_servers, 32]
    telemetry ring plus load, age and failed-mask rows at fleet size."""
    index = mega_fleet_index(n_servers, seed=seed, weights_dtype="bfloat16")
    plat = mega_platform(n_servers, seed=seed, horizon_s=128.0, dt_s=1.0)
    compact, tmap = plat.compact_window(127, window=32)
    hist = np.asarray(compact, np.float32)[np.asarray(tmap)]
    rng = np.random.default_rng(seed + 2)
    load = (rng.random(n_servers) * 1.5).astype(np.float32)
    age = (rng.random(n_servers) * 400.0).astype(np.float32)
    mask = rng.random(n_servers) < 0.05
    return index, (hist, load, age, mask)


def phase_mega(n_servers: int, n_queries: int, seed: int,
               interpret: bool) -> dict:
    index, tel = mega_inputs(n_servers, seed)
    cfg = RoutingConfig(top_s=8, top_k=16)
    krn = ShardedRoutingEngine(cfg=cfg, algo="sonar_ft", n_shards=1,
                               use_kernels=True, interpret=interpret,
                               index=index)
    ref = ShardedRoutingEngine(cfg=cfg, algo="sonar_ft", n_shards=1,
                               use_kernels=False, index=index)
    batch = krn.encode(queries(n_queries, seed))
    compile_s = check_compiled(krn, batch, *tel, interpret=interpret)
    d_k = krn.route(batch, *tel)
    d_j = ref.route(batch, *tel)
    same_picks("mega sonar_ft kernel vs jnp", picks(d_k), picks(d_j))
    np.testing.assert_allclose(d_k.fused, d_j.fused, rtol=RTOL, atol=ATOL)
    require(not np.any(tel[3][d_k.server_idx]), "routed to a failed server")
    return dict(n_servers=n_servers, n_tools=int(index.n_tools),
                compile_s=round(compile_s, 3),
                distinct_servers=int(np.unique(d_k.server_idx).size))


def phase_four_chips(n_servers: int, n_replicas: int, n_queries: int,
                     seed: int, interpret: bool) -> dict:
    n_dev = len(jax.devices())
    require(n_dev >= 4, f"four-chip phase needs 4 devices, JAX has {n_dev}")
    mesh = make_fleet_mesh(4)
    index, tel = mega_inputs(n_servers, seed)
    cfg = RoutingConfig(top_s=8, top_k=16)
    kw = dict(cfg=cfg, algo="sonar_ft", use_kernels=True,
              interpret=interpret, index=index)
    e4 = ShardedRoutingEngine(n_shards=4, mesh=mesh, **kw)
    require(e4.mesh is not None and not e4.emulated, "4 shards emulated")
    e1 = ShardedRoutingEngine(n_shards=1, **kw)
    batch = e4.encode(queries(n_queries, seed))
    compile_s = check_compiled(e4, batch, *tel, interpret=interpret)
    d4 = e4.route(batch, *tel)
    d1 = e1.route(batch, *tel)
    same_picks("mega sonar_ft 4 shards vs 1", picks(d4), picks(d1))
    np.testing.assert_allclose(d4.fused, d1.fused, rtol=RTOL, atol=ATOL)

    servers = catalog(n_replicas, seed)
    profiles = serve.scenario_profiles("hybrid", n_replicas)
    gws = [
        SonarGateway(servers, profiles=profiles, algo="sonar_lb", seed=seed,
                     use_kernels=True, shards=shards)
        for shards in (4, 1)
    ]
    eng4 = gws[0].engine()
    require(eng4.mesh is not None and not eng4.emulated,
            "gateway shards emulated")
    ring = gws[0]._telemetry.raw()
    require(len(ring.sharding.device_set) == 4, ring.sharding)
    texts = queries(n_queries, seed)
    r4, r1 = ([r.replica_idx for r in gw.route_batch(texts)] for gw in gws)
    require(r4 == r1, f"gateway sonar_lb shards=4 vs 1: {r4} vs {r1}")
    return dict(n_servers=n_servers, n_replicas=n_replicas,
                compile_s=round(compile_s, 3),
                ring_devices=len(ring.sharding.device_set))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip mesh phase and its 1-chip twin")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {devs[0].platform!r});"
              " nothing was run", file=sys.stderr)
        return 1
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"device: {device}  compile cache: {enable_compile_cache()}",
          flush=True)
    seed = args.seed
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(
            1_000_000, 16_384, 64, seed, interpret=False))]
    else:
        phases = [
            ("served", lambda: phase_served(16_384, 300, 400.0, seed,
                                            interpret=False)),
            ("parity", lambda: phase_parity(16_384, 64, seed,
                                            interpret=False)),
            ("mega", lambda: phase_mega(1_000_000, 64, seed,
                                        interpret=False)),
        ]
    for name, run in phases:
        t0 = time.perf_counter()
        info = run()
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s: {info}",
              flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
