"""Canonical fleets for traffic experiments.

A pool of *identical* websearch replicas is the adversarial case for
load-blind routing (paper Sec. V-A runs identical backends): semantic
scores tie, QoS ties on a healthy network, so argmax herds every request
onto one replica until its observed latency degrades — exactly the
collapse `benchmarks/offered_load.py` measures.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from repro.core import latency as L
from repro.core.dataset import Server, Tool, WEBSEARCH
from repro.core.platform import NetMCPPlatform


def replica_fleet(n: int) -> list:
    """n equivalently-capable websearch replicas (identical descriptions)."""
    return [
        Server(
            name=f"websearch-replica-{i}",
            domain=WEBSEARCH,
            description=(
                "web search engine for live internet information retrieval"
            ),
            tools=[
                Tool(
                    "web_search",
                    "search the web for real-time information news and facts",
                )
            ],
        )
        for i in range(n)
    ]


def ideal_platform(
    servers: list,
    seed: int = 0,
    horizon_s: float = 900.0,
    dt_s: float = 1.0,
    geo=None,
) -> NetMCPPlatform:
    """Healthy network for every replica, at a 1 s observation tick so the
    feed-forward loop is responsive on traffic timescales.  An optional
    `repro.geo.GeoPlacement` composes propagation RTTs on top (the
    adversarial fleet for locality-blind routing: identical replicas,
    healthy server-side network, all the latency variance geographic)."""
    return NetMCPPlatform(
        servers,
        profiles=[L.ideal_profile() for _ in servers],
        scenario="ideal",
        seed=seed,
        horizon_s=horizon_s,
        dt_s=dt_s,
        geo=geo,
    )


# ---------------------------------------------------------------------------
# Mega fleets (10^5-10^6 servers): template-tiled descriptions + telemetry
# ---------------------------------------------------------------------------

def mega_fleet_index(
    n_servers: int,
    templates: Optional[Sequence[Server]] = None,
    seed: int = 0,
    weights_dtype: str = "float32",
):
    """Template-tiled index over `n_servers` instances of the canonical
    15-server pool (5 websearch + 10 distractor templates, round-robin).

    Returns a `core.mesh_routing.TiledFleetIndex` — BM25 weights stored
    once per template with expanded-corpus statistics, so building the
    index costs O(templates), not O(n_servers).  ``weights_dtype``
    selects the corpus-weight storage precision ("float32" / "bfloat16" /
    "int8" — see `core.quantize.round_weights`).
    """
    from repro.core import dataset
    from repro.core.mesh_routing import TiledFleetIndex

    if templates is None:
        templates = dataset.build_server_pool(seed=seed)
    tmap = np.arange(n_servers) % len(templates)
    return TiledFleetIndex(templates, tmap, weights_dtype=weights_dtype)


def telemetry_palette(n_templates: int = 16, seed: int = 0) -> list:
    """`n_templates` latency profiles cycling through the five canonical
    network states (ideal / high-latency / high-jitter / fluctuating /
    outage), each jittered by a seeded generator so no two templates are
    identical.  Seed semantics: the same (n_templates, seed) pair always
    yields the same palette."""
    rng = np.random.default_rng(seed)
    palette = []
    for i in range(n_templates):
        kind = i % 5
        if kind == 0:
            p = L.LatencyProfile(
                base_latency_ms=20.0 + 15.0 * rng.random(),
                std_dev_ms=3.0 + 4.0 * rng.random(),
            )
        elif kind == 1:
            p = L.LatencyProfile(
                base_latency_ms=250.0 + 150.0 * rng.random(), std_dev_ms=15.0
            )
        elif kind == 2:
            p = L.LatencyProfile(
                base_latency_ms=100.0, std_dev_ms=50.0 + 30.0 * rng.random()
            )
        elif kind == 3:
            p = L.fluctuating_profile(
                base_ms=150.0, amplitude_ms=120.0, period_s=3600.0,
                phase=float(2.0 * np.pi * rng.random()),
            )
        else:
            p = L.outage_profile(probability=0.2 + 0.3 * rng.random())
        palette.append(p)
    return palette


def telemetry_template_map(n_servers: int, n_templates: int) -> np.ndarray:
    """int64 [n_servers] telemetry template of each server: a stride
    co-prime to the description round-robin of `mega_fleet_index`, so
    semantic ties and network ties decorrelate (int64: the Knuth
    multiplier overflows default-int32 platforms)."""
    return (np.arange(n_servers, dtype=np.int64) * 2654435761) % n_templates


def mega_platform(
    n_servers: int,
    n_tel_templates: int = 16,
    seed: int = 0,
    horizon_s: float = 900.0,
    dt_s: float = 1.0,
) -> NetMCPPlatform:
    """Tiled `NetMCPPlatform` for a mega fleet: ground-truth traces are
    synthesized once per telemetry template ([n_tel_templates, T]) and
    servers map onto them with a stride co-prime to the description
    round-robin, so semantic ties and network ties decorrelate.  Storage
    is O(templates x T) + O(servers) regardless of fleet size."""
    return NetMCPPlatform(
        servers=None,
        profiles=telemetry_palette(n_tel_templates, seed),
        template_map=telemetry_template_map(n_servers, n_tel_templates),
        seed=seed,
        horizon_s=horizon_s,
        dt_s=dt_s,
    )
