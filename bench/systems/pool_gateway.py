"""The paper's server pool, replicated to a mega fleet, behind the served
SONAR-FT gateway.

`build` tiles the configuration's template servers (``bench/configs/*.json``
with ``"system": "pool_gateway"``) over ``n_replicas`` replicas as the
program's template-tiled index, gives each replica a telemetry template,
and puts them behind the program's `SonarGateway` (sharded engine, device
telemetry ring read through the template map) and `AsyncServingGateway`.
No per-replica `Server` object or trace is ever made.  The gateway's
calls go through this module's simulated network (`PoolSUT.call`): the
replica's trace, and a failed call on each replica the configuration
has down.  The system under
test carries a recorder of what the timed path decided, with the health
state each routing call saw.
"""
from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from harness import traffic as tr  # noqa: E402
from ref import sonar, tiled_ft  # noqa: E402
from systems import catalog_gateway as cg  # noqa: E402


def catalog(cfg: dict) -> dict:
    """What the text generator draws calls from: the pool's template
    tools' words and the configuration's general words."""
    tool_words = [sonar.tokenize(f"{t['name'].replace('_', ' ')} "
                                 f"{t['description']}")
                  for s in cfg["pool"] for t in s["tools"]]
    return dict(tool_words=tool_words,
                general_words=list(cfg["assumed"]["general_words"]))


def down_replicas(cfg: dict) -> np.ndarray:
    """The replicas that are down for the whole run (sorted ids): every
    call to one fails, while its network telemetry stays its template's."""
    down = cfg["faults"]["down"]
    lo = int(down["first"])
    return np.arange(lo, min(lo + int(down["count"]), int(cfg["n_replicas"])),
                     dtype=np.int64)


def telemetry_map(cfg: dict) -> np.ndarray:
    """Each replica's telemetry template (the configuration's stride)."""
    tel = cfg["telemetry"]
    return (np.arange(int(cfg["n_replicas"]), dtype=np.int64)
            * int(tel["map_stride"])) % int(tel["templates"])


class PoolRecorder(cg.Recorder):
    """`catalog_gateway.Recorder`, and per engine call the health state
    its rows saw: the ejected replicas (sorted ids) and, per real row,
    the ejected replicas its probes re-admitted as candidates."""

    def __init__(self, gw, engine, annotate: bool = False):
        super().__init__(gw, engine, annotate)
        inner, flushes = engine.route, self.flushes

        def route(batch, *args, **kw):
            ejected = np.flatnonzero(gw.ejected)
            n = kw.get("n_real") or batch.n
            mask = kw.get("failed_mask")
            if mask is None:
                probes = [ejected] * n
            else:
                seen = np.asarray(mask)[:n][:, ejected]
                probes = [ejected[~row] for row in seen]
            flushes[-1].setdefault("health", []).append((ejected, probes))
            return inner(batch, *args, **kw)

        engine.route = route


class PoolSUT(cg.GatewaySUT):
    """A `SonarGateway` over the tiled pool, with its batching policy,
    recorder and the text generator's catalog."""

    def __init__(self, cfg: dict, seed: int, tracing: bool):
        from repro.core.dataset import Server, Tool
        from repro.core.qos import QosParams
        from repro.core.routing import RoutingConfig
        from repro.obs import Observability
        from repro.serving.gateway import SonarGateway
        from repro.serving.microbatch import BatchingPolicy
        from repro.traffic import fleet

        r, g = cfg["routing"], cfg["gateway"]
        self.cfg = cfg
        self.down = np.zeros(int(cfg["n_replicas"]), bool)
        self.down[down_replicas(cfg)] = True
        self.catalog = catalog(cfg)
        t0 = time.monotonic()
        templates = [Server(s["name"], s["domain"], s["description"],
                            [Tool(t["name"], t["description"])
                             for t in s["tools"]]) for s in cfg["pool"]]
        index = fleet.mega_fleet_index(int(cfg["n_replicas"]), templates,
                                       weights_dtype=g["weights_dtype"])
        pseed = tr.program_seed(seed)
        palette = fleet.telemetry_palette(int(cfg["telemetry"]["templates"]),
                                          seed=pseed)
        t1 = time.monotonic()
        rcfg = RoutingConfig(
            top_s=r["top_s"], top_k=r["top_k"], alpha=r["alpha"],
            beta=r["beta"], gamma=r["gamma"], load_knee=r["load_knee"],
            load_sharp=r["load_sharp"], expertise_temp=r["expertise_temp"],
            qos=QosParams(**r["qos"]),
        )
        self.gw = SonarGateway(
            index, profiles=palette, template_map=telemetry_map(cfg),
            cfg=rcfg, seed=pseed, history=g["history"],
            use_kernels=g["use_kernels"], algo=r["algo"],
            slots_per_replica=g["slots_per_replica"], lb_chunk=g["lb_chunk"],
            eject_after=g["eject_after"], probe_prob=g["probe_prob"],
            shards=g["shards"], device_telemetry=True,
            telemetry_dtype=g["telemetry_dtype"],
            obs=Observability(trace=False), executor=self.call,
        )
        self.engine = self.gw.engine()
        t2 = time.monotonic()
        self.gw.warm(int(g["lb_chunk"]))
        self.setup_phases = {"index_s": t1 - t0, "gateway_s": t2 - t1,
                             "engine_warm_s": time.monotonic() - t2}
        self.policy = BatchingPolicy(**cfg["batching"])
        self.recorder = PoolRecorder(self.gw, self.engine, annotate=tracing)
        self.tracing = tracing

    def call(self, idx: int, text: str) -> float:
        """The simulated call to replica ``idx``: its trace sample, and on
        a replica that is down at least the offline latency, so it fails."""
        lat = self.gw.trace_at(idx)
        return max(lat, tiled_ft.OFFLINE_MS) if self.down[idx] else lat

    def release(self) -> tuple:
        """Drop the program's state and return the simulated network: one
        latency trace (ms) per telemetry template, each replica's template
        and the replicas that are down."""
        env = (np.array(self.gw.trace_rows), np.array(self.gw.trace_map),
               np.flatnonzero(self.down))
        self.gw = self.engine = None
        return env


def build(cell, seed: int, tracing: bool = False) -> PoolSUT:
    return PoolSUT(cell.config, seed, tracing)


CONTROL = "bf16"          # the reference's score arithmetic one step below
                          # the float32 the configuration states


def reference(cell, sut, precision: str = "exact"):
    """The numpy reference over this configuration's fleet;
    ``precision=CONTROL`` is the control."""
    cfg = cell.config
    return tiled_ft.Reference(cfg["pool"], cfg["n_replicas"], cfg["routing"],
                              sonar.load_intents(cell.bench_dir),
                              cfg["gateway"]["weights_dtype"],
                              precision=precision)


def judge(cell, sut, window, env: tuple, ref, control=None) -> dict:
    """The judge's readings of the window's decisions (with ``control``:
    of the control's decisions in their place)."""
    g = cell.config["gateway"]
    return tiled_ft.judge_pool(sut.recorder.flushes, window.first_flush, ref,
                               env[0], env[1], env[2], g,
                               cell.config["routing"]["qos"],
                               g["telemetry_dtype"], control=control)


window_calls = cg.window_calls
