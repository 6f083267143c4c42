"""Network-aware inference gateway — the paper's technique as a first-class
serving feature (DESIGN.md §2).

A fleet of model-serving replicas (pods) stands in for the paper's MCP
server pool: each replica advertises a capability description (its arch +
task competences, the analogue of d_m) and live latency telemetry.  The
gateway routes every request with SONAR: two-stage BM25 capability match
(Eq. 1-5) fused with the QoS score of each replica's telemetry (Eq. 7-8).
Feed-forward recording closes the loop (Sec. III-B).

At fleet scale the hot loop is the batched routing engine
(`use_kernels=True`): the whole request batch flows through one jit-compiled
pipeline — bm25_scores matmuls, a qos_scores pass over the telemetry matrix
and the fused top-k/softmax/fusion/argmax selection kernel (see
repro.core.batch_routing).  Past ~10^3 replicas, ``shards=N`` switches
`route_batch` to the mesh-sharded engine (repro.core.mesh_routing) and the
telemetry window to a device-resident ring buffer advanced in place
(donated) per tick.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.core import adaptive as _adaptive
from repro.core import latency as latlib
from repro.core.batch_routing import BatchRoutingEngine
from repro.core.dataset import Server, Tool
from repro.core.mesh_routing import ShardedRoutingEngine
from repro.core.qos import load_penalty, rtt_penalty
from repro.core.routing import (  # noqa: F401
    ALGORITHMS,
    RoutingConfig,
    SonarRouter,
    algo_terms,
)
from repro.obs import Observability
from repro.obs import trace as obs_trace
from repro.sessions.warmth import WarmthTracker

ARCH_CAPABILITIES = {
    "dense": "general purpose text generation chat completion dense transformer",
    "moe": "mixture of experts text generation high throughput sparse compute",
    "hybrid": "long context document summarization state space hybrid generation",
    "ssm": "streaming long context low latency recurrent state generation",
    "audio": "speech transcription audio translation whisper encoder decoder",
    "vlm": "image understanding visual question answering multimodal vision language",
}


def replica_pool(
    archs: Sequence[tuple],          # [(arch_id, family)], one per replica
) -> list:
    servers = []
    for i, (arch_id, family) in enumerate(archs):
        cap = ARCH_CAPABILITIES[family]
        servers.append(
            Server(
                name=f"{arch_id}-replica-{i}",
                domain=family,
                description=f"{arch_id} serving replica: {cap}",
                tools=[Tool("generate", f"generate text with {arch_id}: {cap}")],
            )
        )
    return servers


@dataclasses.dataclass
class RouteResult:
    replica_idx: int
    latency_ms: float
    ok: bool
    expertise: float
    network: float


def _telemetry_np_dtype(dtype: str):
    if dtype in ("bfloat16", "bf16"):
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(np.float32)


class _HostTelemetry:
    """Host telemetry window [n_replicas, history]: roll + assign per tick
    (the original gateway path — fine up to ~10^3 replicas).

    ``dtype="bfloat16"`` stores the window in bf16: samples are rounded
    once as they enter the ring and never re-rounded (the buffer stays
    bf16), and ``host()`` upcasts exactly — every consumer, scalar or
    batched, sees the identical rounded floats.
    """

    def __init__(self, init: np.ndarray, dtype: str = "float32"):
        self._np_dtype = _telemetry_np_dtype(dtype)
        self._win = np.array(init, self._np_dtype)

    def push(self, col: np.ndarray) -> None:
        self._win = np.roll(self._win, -1, axis=1)
        self._win[:, -1] = col

    def raw(self):
        return self._win

    def host(self) -> np.ndarray:
        if self._win.dtype == np.float32:
            return self._win
        return self._win.astype(np.float32)


class DeviceTelemetry:
    """Device-resident telemetry window, advanced **in place** per tick.

    The buffer is donated to the jit shift-append, so XLA reuses its
    storage instead of re-materializing [n_replicas, history] from the
    host on every observation — at mega-fleet scale the np.roll path would
    move the whole window through host memory once per completion.  The
    host view (for scalar `Router.select` calls) is materialized lazily
    and cached until the next push.
    """

    _shift = staticmethod(
        jax.jit(
            lambda buf, col: jnp.concatenate(
                [buf[:, 1:], col[:, None].astype(buf.dtype)], axis=1
            ),
            donate_argnums=0,
        )
    )
    # the column read through the template map on the device: only the
    # template column, the replica and its sample cross from the host
    _shift_mapped = staticmethod(
        jax.jit(
            lambda buf, tcol, tmap, idx, value: jnp.concatenate(
                [buf[:, 1:],
                 jnp.take(tcol, tmap).at[idx].set(value)[:, None]
                 .astype(buf.dtype)], axis=1,
            ),
            donate_argnums=0,
        )
    )

    def __init__(self, init: np.ndarray, sharding=None,
                 dtype: str = "float32",
                 template_map: Optional[np.ndarray] = None):
        # bf16 ring: halves the resident window and the per-route HBM
        # read; samples are rounded once on entry (the buffer never
        # leaves bf16, so there is no re-rounding drift) and upcast
        # exactly wherever f32 math needs them.
        self._dtype = (
            jnp.bfloat16 if dtype in ("bfloat16", "bf16") else jnp.float32
        )
        self._map = None
        if template_map is not None:
            # init holds one row per telemetry template: the window is
            # gathered on the device, never [n_replicas, history] on the host
            self._map = jnp.asarray(np.asarray(template_map, np.int32))
            if sharding:
                self._map = jax.device_put(self._map, sharding)
            buf = jnp.take(jnp.asarray(init, jnp.float32), self._map,
                           axis=0).astype(self._dtype)
        else:
            buf = jnp.asarray(init, self._dtype)
        self._buf = jax.device_put(buf, sharding) if sharding else buf
        self._host: Optional[np.ndarray] = None

    def push(self, col: np.ndarray) -> None:
        self._buf = DeviceTelemetry._shift(
            self._buf, jnp.asarray(col, jnp.float32)
        )
        self._host = None

    def push_mapped(self, tcol: np.ndarray, idx: int, value: float) -> None:
        """Advance by one column read through the template map: replica
        i takes ``tcol[template_map[i]]``, replica ``idx`` takes
        ``value``."""
        self._buf = DeviceTelemetry._shift_mapped(
            self._buf, jnp.asarray(tcol, jnp.float32), self._map,
            int(idx), np.float32(value),
        )
        self._host = None

    def raw(self):
        return self._buf

    def host(self) -> np.ndarray:
        if self._host is None:
            self._host = np.asarray(self._buf.astype(jnp.float32))
        return self._host


class SonarGateway:
    """Routes requests across serving replicas with SONAR.

    Parameters
    ----------
    replicas : Sequence[Server] | TiledFleetIndex
        Replica pool (capability descriptions are the routing corpus), or
        the prebuilt template-tiled index of a mega fleet
        (`core.mesh_routing.TiledFleetIndex`): the gateway then holds no
        `Server` objects, the scalar router scores through the index and
        batches route through the sharded engine (``shards``) over it.
    profiles : list[LatencyProfile], optional
        Per-replica network profiles (default: all ideal), or with
        ``template_map`` one per telemetry template.
    cfg : RoutingConfig
    seed : int
        Seeds both trace synthesis and the probe-readmission PRNG; the
        same (seed, profiles, history) gateway replays identically.
    history : int
        Telemetry window length in samples.
    executor : Callable, optional
        ``(replica_idx, request_text) -> latency_ms`` — real dispatch hook,
        on every path (`route`, and `route_batch` as each answer
        completes); default replays the synthesized traces (`trace_at`).
    use_kernels : bool
        Route batches through the jit engine (`route_batch` fast path).
    algo : str
        ``"sonar" | "sonar_lb" | "sonar_ft"`` (any network-aware algorithm).
    slots_per_replica : int
        Concurrency capacity behind the SONAR-LB utilization term.
    lb_chunk : int
        Chunk size for load-aware batched routing (in-flight feedback
        granularity).
    eject_after, probe_prob :
        SONAR-FT health tracking — consecutive failures before ejection,
        and the per-request canary re-admission probability.
    shards : int, optional
        Partition the replica axis across `shards` slices and route
        batches through the mesh-sharded engine
        (`core.mesh_routing.ShardedRoutingEngine`).  Also switches the
        telemetry window to a device-resident buffer advanced in place
        (donated) per tick instead of the host np.roll path.
    mesh : Mesh | "auto" | None
        Passed to the sharded engine (``"auto"`` uses a real device mesh
        when enough devices exist, else the bit-identical emulation).
    region_rtt_ms : np.ndarray, optional
        f32 [n_regions, n_replicas] propagation RTT from each client
        region to each replica (e.g. `repro.geo.GeoPlacement
        .region_server_rtt()`).  With a locality-aware algorithm
        (``algo="sonar_geo"``) requests routed with a ``client_region``
        pay attention to distance; other algorithms ignore it.
    template_map : np.ndarray, optional
        int [n_replicas] telemetry template of each replica (an index
        into ``profiles``).  Traces are synthesized once per template,
        [n_templates, T], and read through the map on ring init and on
        every ring push, in the device ring, so host memory stays
        O(n_templates x T + n_replicas) at any fleet size.
    obs : repro.obs.Observability, optional
        The observability bundle (docs/observability.md).  The gateway
        binds its counters/gauges/histograms in ``obs.registry`` — the
        single source of truth `report()` reads — passes ``obs.audit_tap``
        to scalar routing decisions, and threads ``obs.route_stats`` (the
        jit-safe device accumulator) through the batched engines.  The
        default bundle keeps tracing/audit/device-stats off; metrics
        registration alone is a few float adds per request.
    device_telemetry : bool, optional
        Keep the telemetry window device-resident (the donated
        `DeviceTelemetry` ring) even without ``shards``.  The ring is
        advanced by a jit in-place shift-append whose dispatch is
        asynchronous, so under the micro-batch front-end the feed-forward
        pushes of flush *k* overlap with the host-side encode of flush
        *k+1* and the window is already on device when the fused kernel
        runs — no per-flush host->device transfer.  Defaults to ``True``
        when ``shards`` is set, else ``False`` (the host np.roll window).
    telemetry_dtype : str
        Storage dtype of the telemetry ring, ``"float32"`` (default) or
        ``"bfloat16"``.  bf16 halves the resident window and the
        per-route HBM read; each sample is rounded once (RNE) as it
        enters the ring and never re-rounded, and every consumer —
        scalar router, batched engine, Pallas kernels — upcasts the same
        rounded floats exactly, so routing decisions stay identical
        across paths (the quantization carve-out, docs/benchmarks.md).
    """

    def __init__(
        self,
        replicas,
        profiles: Optional[list] = None,
        cfg: RoutingConfig = RoutingConfig(top_s=8, top_k=8),
        seed: int = 0,
        history: int = 64,
        executor: Optional[Callable] = None,   # (replica_idx, request) -> latency_ms
        use_kernels: bool = False,
        algo: str = "sonar",                   # "sonar" | "sonar_lb" | "sonar_ft"
        slots_per_replica: int = 4,            # capacity behind the load term
        lb_chunk: int = 8,                     # load-aware batch routing chunk
        eject_after: int = 3,                  # consecutive failures -> ejected
        probe_prob: float = 0.15,              # per-request re-admission probe
        shards: Optional[int] = None,
        mesh="auto",
        region_rtt_ms: Optional[np.ndarray] = None,
        device_telemetry: Optional[bool] = None,
        telemetry_dtype: str = "float32",
        obs: Optional[Observability] = None,
        session_half_life: float = 256.0,
        template_map: Optional[np.ndarray] = None,
    ):
        self.algo = algo.lower().replace("-", "_")
        if getattr(replicas, "is_tiled", False):
            if use_kernels and not shards:
                raise ValueError("a tiled fleet routes batches through the "
                                 "sharded engine: pass shards")
            if template_map is None:
                raise ValueError("a tiled fleet takes template telemetry: "
                                 "pass profiles and template_map")
            self.replicas: list = []
            self.n_replicas = int(replicas.n_servers)
            self.router = ALGORITHMS[self.algo]([], cfg, index=replicas)
        else:
            self.replicas = list(replicas)
            self.n_replicas = len(self.replicas)
            self.router = ALGORITHMS[self.algo](self.replicas, cfg)
        assert self.router.uses_network, "the gateway routes on telemetry"
        self.history = history
        self.executor = executor
        self.use_kernels = use_kernels
        self.lb_chunk = lb_chunk
        self.shards = shards
        self._mesh_opt = mesh
        self.region_rtt_ms = (
            None if region_rtt_ms is None
            else np.asarray(region_rtt_ms, np.float32)
        )
        # the terms a routing call here can carry, decided once: telemetry,
        # load and health rows always, RTT with a region matrix, warmth
        # with a session
        self.terms = algo_terms(self.algo).live(
            cfg, network=True, load=True, failover=True,
            rtt=self.region_rtt_ms is not None, affinity=True,
        )
        self._engine = None
        # observability: all gateway accounting lives in the registry
        # (report() reads it back — one source of truth shared with the
        # micro-batcher / front-end / engine layers bound to the same
        # bundle); the device-side route stats are threaded through the
        # batched engines when obs.jit_stats is on.
        self.obs = obs if obs is not None else Observability()
        n = self.n_replicas
        # in-flight accounting: callers running concurrent traffic use
        # begin()/finish() so the utilization the load term sees tracks
        # outstanding work; route()/route_batch() keep their own counts.
        self.in_flight = np.zeros(n, np.float32)
        self.capacity = float(max(slots_per_replica, 1))
        # health tracking (SONAR-FT): a replica with `eject_after`
        # consecutive failed calls is ejected (masked out of routing);
        # each subsequent request re-admits it as a candidate with
        # probability `probe_prob` (a canary probe), and one success fully
        # readmits it.  Only failover-aware algorithms consume the mask.
        self.eject_after = int(eject_after)
        self.probe_prob = float(probe_prob)
        self.fail_streak = np.zeros(n, np.int64)
        self.ejected = np.zeros(n, bool)
        self._probe_rng = np.random.default_rng(seed ^ 0x5EED)
        self._health_draws = 0        # probe uniforms drawn, ever
        if profiles is None:
            profiles = [latlib.ideal_profile() for _ in range(n)]
        packed = latlib.pack_profiles(profiles)
        steps = latlib.trace_horizon_steps()
        # one trace row per profile; replica i reads row trace_map[i]
        # (the identity when every replica has its own profile)
        self.trace_rows = latlib.generate_traces_cached(seed, packed, steps)
        self.trace_map = (
            None if template_map is None
            else np.asarray(template_map, np.int32)
        )
        init = self.trace_rows[:, :history]
        if device_telemetry is None:
            device_telemetry = bool(shards) or template_map is not None
        elif template_map is not None and not device_telemetry:
            raise ValueError("template telemetry lives in the device ring")
        self.telemetry_dtype = telemetry_dtype
        ring_sharding = None
        mesh = self.engine().mesh if shards and use_kernels else None
        if mesh is not None:
            # the ring lives beside the shards that read it: split over the
            # fleet axis when the replicas divide evenly, else replicated
            spec = P("fleet") if n % mesh.devices.size == 0 else P()
            ring_sharding = NamedSharding(mesh, spec)
        self._telemetry = (
            DeviceTelemetry(init, sharding=ring_sharding,
                            dtype=telemetry_dtype,
                            template_map=self.trace_map)
            if device_telemetry
            else _HostTelemetry(init, dtype=telemetry_dtype)
        )
        self.t = history
        self.stats: list = []
        reg = self.obs.registry
        self._m_requests = reg.counter("gateway_requests_total", "req")
        self._m_failures = reg.counter("gateway_failures_total", "req")
        self._m_ejections = reg.counter("gateway_ejections_total", "events")
        self._m_readmissions = reg.counter(
            "gateway_readmissions_total", "events"
        )
        self._m_latency = reg.histogram("gateway_latency_ms", "ms")
        self._m_in_flight = reg.gauge("gateway_in_flight", "req")
        self._m_unmatched = reg.counter(
            "gateway_unmatched_finish_total", "req"
        )
        self._m_ejected = reg.gauge("gateway_ejected", "replicas")
        self._m_health_bytes = reg.gauge(
            "gateway_health_row_bytes_per_flush", "bytes"
        )
        self._m_health_draws = reg.gauge(
            "gateway_health_draws_per_flush", "draws"
        )
        # per-flush walls of route_batch's phases, the ring push per
        # completion on every path, and the health rows per routing call
        self._m_phase = {
            ph: reg.histogram(f"gateway_phase_{ph}_ms", "ms")
            for ph in ("encode", "dispatch", "merge", "ring_push", "health")
        }
        self._route_stats = self.obs.ensure_route_stats(n)
        # SONAR-ADAPT: live weight-trajectory surface.  The scalar router
        # (route/begin+finish) and the batched engine (route_batch) each
        # hold learner state; the gauges publish whichever one last moved.
        self.adaptive = hasattr(self.router, "observe_outcome")
        self._m_adapt_w = None
        self._m_adapt_baseline = None
        self._m_adapt_steps = None
        if self.adaptive:
            self._m_adapt_w = {
                name: reg.gauge(f"adapt_weight_{name}", "w")
                for name in ("alpha", "beta", "gamma", "delta")
            }
            self._m_adapt_baseline = reg.gauge("adapt_baseline", "reward")
            self._m_adapt_steps = reg.gauge("adapt_steps", "updates")
            self._publish_adapt(self.router.state)
        # begin()/finish() credit assignment: winner features stashed at
        # begin, popped (FIFO per replica) at finish; `abandon` expires
        # the head entry when a dispatch is shed before finishing, so
        # later completions never pop a stale decision's features
        self._pending_feats: dict = {}
        # SONAR-SESSION sticky affinity: per-(session, server) warmth on
        # the gateway's tick clock (one tick per recorded completion).
        # Only affinity-aware routers read it; for everyone else the
        # tracker stays empty and adds nothing to the hot path.
        self.session_warmth = WarmthTracker(
            n, half_life_ms=float(session_half_life)
        )

    @property
    def traces(self) -> np.ndarray:
        """Each replica's latency trace [n_replicas, T] ms (materialized
        through the template map where there is one: small fleets only)."""
        if self.trace_map is None:
            return self.trace_rows
        return self.trace_rows[self.trace_map]

    def trace_at(self, idx: int) -> float:
        """Replica ``idx``'s trace sample (ms) at the gateway's clock: the
        latency a call to it takes when no ``executor`` is given."""
        row = idx if self.trace_map is None else self.trace_map[idx]
        return float(
            self.trace_rows[row, min(self.t, self.trace_rows.shape[1] - 1)]
        )

    @property
    def telemetry(self) -> np.ndarray:
        """Host view of the telemetry window [n_replicas, history] ms (the
        scalar routing paths consume this; the device buffer backing a
        sharded gateway is materialized lazily and cached per tick)."""
        return self._telemetry.host()

    def _call(self, idx: int, request_text: str) -> float:
        """The latency (ms) of the call to replica ``idx``: the executor's,
        else the trace's."""
        if self.executor is not None:
            return float(self.executor(idx, request_text))
        return self.trace_at(idx)

    def _observe(self, idx: int, latency_ms: float):
        with obs_trace.annotate("gateway.ring_push",
                                self._m_phase["ring_push"]):
            col = self.trace_rows[:, min(self.t, self.trace_rows.shape[1] - 1)]
            if self.trace_map is not None:
                self._telemetry.push_mapped(col, idx, latency_ms)
            else:
                col = np.array(col, np.float32)
                col[idx] = latency_ms
                self._telemetry.push(col)
        self.t += 1

    def _utilization(self) -> np.ndarray:
        return self.in_flight / self.capacity

    def _rtt_row(self, client_region: Optional[int]) -> Optional[np.ndarray]:
        """[n_replicas] RTT row for one client region (None when the
        RTT term is not live here, or the request is untagged)."""
        if not self.terms.rtt or client_region is None or client_region < 0:
            return None
        return self.region_rtt_ms[int(client_region)]

    def _session_affinity(
        self, session_id: Optional[int]
    ) -> Optional[np.ndarray]:
        """[n_replicas] warmth row for one session (None when the request
        is session-less, the affinity term is not live here, or the session
        has fully cooled — None keeps the router on the exact
        zero-affinity scoring path)."""
        if session_id is None or not self.terms.affinity:
            return None
        return self.session_warmth.warmth(int(session_id), float(self.t))

    def _session_touch(
        self, session_id: Optional[int], idx: int, ok: bool
    ) -> None:
        """A completion for ``session_id`` landed on replica ``idx``:
        mark the replica warm (successful completions only — a failed
        call leaves no context worth sticking to)."""
        if ok and session_id is not None:
            self.session_warmth.touch(int(session_id), idx, float(self.t))

    # -- SONAR-ADAPT: weight-trajectory observability -----------------------
    def _publish_adapt(self, state) -> None:
        """Mirror the live AdaptState into gauges + a trace instant so the
        dashboard renders the weight trajectory as it learns."""
        if self._m_adapt_w is None or state is None:
            return
        w = np.asarray(state.weights, np.float32)
        for i, name in enumerate(("alpha", "beta", "gamma", "delta")):
            self._m_adapt_w[name].set(float(w[i]))
        self._m_adapt_baseline.set(float(state.baseline))
        self._m_adapt_steps.set(float(state.step))
        self.obs.tracer.instant(
            "adapt_weights", cat="adapt",
            args={
                "alpha": float(w[0]), "beta": float(w[1]),
                "gamma": float(w[2]), "delta": float(w[3]),
                "baseline": float(state.baseline),
                "step": int(state.step),
            },
        )

    def _batch_feats(
        self, idx: int, expertise: float, network: float,
        client_region: Optional[int],
    ) -> np.ndarray:
        """[C, N, -U, -R] at a batched pick, rebuilt gateway-side from the
        decision metadata plus the load/RTT terms at dispatch time."""
        cfg = self.router.cfg
        u = 0.0
        if self.terms.load:
            u = float(load_penalty(
                self._utilization()[idx], cfg.load_knee, cfg.load_sharp
            ))
        r = 0.0
        rtt_row = self._rtt_row(client_region)
        if rtt_row is not None:
            r = float(rtt_penalty(rtt_row[idx], cfg.rtt_scale_ms))
        return _adaptive.decision_feats(expertise, network, u, r)

    # -- health tracking (SONAR-FT ejection + probe re-admission) -----------
    def _health_mask(self, n_requests: Optional[int] = None) -> Optional[np.ndarray]:
        """failed-mask for the next routing decision: ejected replicas are
        excluded unless the request probes them.  The probe is drawn per
        *request* — scalar callers get a [n_replicas] mask, `route_batch`
        passes `n_requests` and gets an independent [n_requests,
        n_replicas] row per request (the batched engine broadcasts
        per-query masks), so the re-admission rate stays `probe_prob` per
        request regardless of chunking.  Never masks the whole fleet for
        any request (a single-replica pool with its replica ejected must
        still route — the request *is* the probe)."""
        if not self.terms.failover:
            return None
        with obs_trace.annotate("gateway.health_mask",
                                self._m_phase["health"]):
            ids = np.flatnonzero(self.ejected)
            if not len(ids):
                return None
            rows = 1 if n_requests is None else n_requests
            # a probe bit only matters on an ejected replica, so only the
            # ejected columns draw one: rows x ejected uniforms per call
            masked = ~(
                self._probe_rng.random((rows, len(ids))) < self.probe_prob
            )
            self._health_draws += masked.size
            if len(ids) == self.n_replicas:
                masked[masked.all(axis=1)] = False
            if not masked.any():
                return None
            mask = np.zeros((rows, self.n_replicas), bool)
            mask[:, ids] = masked
            return mask[0] if n_requests is None else mask

    def _record_outcome(self, idx: int, ok: bool) -> None:
        was_ejected = bool(self.ejected[idx])
        if ok:
            self.fail_streak[idx] = 0
            self.ejected[idx] = False           # probe succeeded: readmit
            if was_ejected:
                self._m_readmissions.inc()
                self._m_ejected.dec()
                self.obs.tracer.instant(
                    "readmit", cat="health", args={"replica": idx}
                )
        else:
            self._m_failures.inc()
            self.fail_streak[idx] += 1
            if self.fail_streak[idx] >= self.eject_after:
                self.ejected[idx] = True
                if not was_ejected:
                    self._m_ejections.inc()
                    self._m_ejected.inc()
                    self.obs.tracer.instant(
                        "eject", cat="health", args={"replica": idx}
                    )

    def _account(self, res: RouteResult) -> RouteResult:
        """Single completion-accounting path (route / finish /
        route_batch): the stats list and the registry stay in lockstep."""
        self.stats.append(res)
        self._m_requests.inc()
        self._m_latency.observe(res.latency_ms)
        return res

    # -- concurrent dispatch accounting (SONAR-LB) --------------------------
    def begin(
        self, request_text: str, client_region: Optional[int] = None,
        session_id: Optional[int] = None,
    ) -> RouteResult:
        """Route and dispatch without completing: the pick is counted
        in-flight until `finish` is called.  This is the API a concurrent
        front door drives; `route` is the synchronous convenience.
        ``session_id`` tags the dispatch with its agent session so
        affinity-aware algorithms see the session's warmth vector."""
        aff = self._session_affinity(session_id)
        with self.obs.tracer.span("begin", cat="gateway"):
            decision = self.router.select(
                request_text, self.telemetry, self._utilization(),
                failed_mask=self._health_mask(),
                client_rtt_ms=self._rtt_row(client_region),
                audit=self.obs.audit_tap,
                **({} if aff is None else {"affinity": aff}),
            )
        idx = decision.server_idx
        self.in_flight[idx] += 1.0
        self._m_in_flight.inc()
        if self.adaptive:
            # FIFO per replica: `finish` is keyed by replica index only, so
            # concurrent dispatches to one replica complete oldest-first.
            self._pending_feats.setdefault(idx, []).append(
                getattr(self.router, "last_feats", None)
            )
        return RouteResult(
            replica_idx=idx, latency_ms=0.0, ok=True,
            expertise=decision.expertise, network=decision.network,
        )

    def finish(
        self, replica_idx: int, latency_ms: float,
        session_id: Optional[int] = None,
    ) -> Optional[RouteResult]:
        """Complete a begun dispatch: record telemetry, release the slot.

        A finish with no outstanding begun dispatch on the replica
        (double-finish, or a finish after `abandon`) is **rejected**: it
        is counted in ``gateway_unmatched_finish_total`` and returns
        ``None`` without touching the in-flight gauge, telemetry, health,
        or learner state — the in-flight array and gauge always move in
        lockstep."""
        if self.in_flight[replica_idx] <= 0.0:
            self._m_unmatched.inc()
            self.obs.tracer.instant(
                "unmatched_finish", cat="gateway",
                args={"replica": int(replica_idx)},
            )
            return None
        with self.obs.tracer.span("finish", cat="gateway"):
            self.in_flight[replica_idx] -= 1.0
            self._m_in_flight.dec()
            ok = latency_ms < latlib.OFFLINE_MS
            self._record_outcome(replica_idx, ok)
            self._observe(replica_idx, latency_ms)
            self._session_touch(session_id, replica_idx, ok)
            if self.adaptive:
                fifo = self._pending_feats.get(replica_idx)
                feats = fifo.pop(0) if fifo else None
                self.router.observe_outcome(latency_ms, ok=ok, feats=feats)
                self._publish_adapt(self.router.state)
            return self._account(RouteResult(
                replica_idx=replica_idx, latency_ms=latency_ms, ok=ok,
                expertise=0.0, network=0.0,
            ))

    def abandon(self, replica_idx: int) -> bool:
        """Release a begun dispatch that will never finish (the request
        was shed or expired downstream of routing).  Decrements the
        in-flight count and gauge in lockstep and expires the oldest
        pending feature stash for the replica, so a later completion
        cannot pop a stale decision's features and mis-credit the
        adaptive update.  Returns False (and counts an unmatched finish)
        when the replica has nothing outstanding."""
        if self.in_flight[replica_idx] <= 0.0:
            self._m_unmatched.inc()
            return False
        self.in_flight[replica_idx] -= 1.0
        self._m_in_flight.dec()
        if self.adaptive:
            fifo = self._pending_feats.get(replica_idx)
            if fifo:
                fifo.pop(0)
        return True

    def route(
        self, request_text: str, client_region: Optional[int] = None,
        session_id: Optional[int] = None,
    ) -> RouteResult:
        aff = self._session_affinity(session_id)
        with self.obs.tracer.span("route", cat="gateway"):
            decision = self.router.select(
                request_text, self.telemetry, self._utilization(),
                failed_mask=self._health_mask(),
                client_rtt_ms=self._rtt_row(client_region),
                audit=self.obs.audit_tap,
                **({} if aff is None else {"affinity": aff}),
            )
        idx = decision.server_idx
        latency = self._call(idx, request_text)
        ok = latency < latlib.OFFLINE_MS
        self._record_outcome(idx, ok)
        self._observe(idx, latency)
        self._session_touch(session_id, idx, ok)
        if self.adaptive:
            # Synchronous path: the router's `last_feats` stash is still the
            # decision we just executed.
            self.router.observe_outcome(latency, ok=ok)
            self._publish_adapt(self.router.state)
        return self._account(RouteResult(
            replica_idx=idx, latency_ms=latency, ok=ok,
            expertise=decision.expertise, network=decision.network,
        ))

    def engine(self):
        """The batched engine over this fleet (built once, lazily).
        Shares the scalar router's compiled ToolIndex so both paths score
        the exact same corpus.  With ``shards`` set this is the
        mesh-sharded engine (argmax-identical; see core.mesh_routing)."""
        if self._engine is None:
            if self.shards:
                self._engine = ShardedRoutingEngine(
                    self.replicas, self.router.cfg, algo=self.algo,
                    n_shards=self.shards, mesh=self._mesh_opt,
                    index=self.router.index, registry=self.obs.registry,
                )
            else:
                self._engine = BatchRoutingEngine(
                    self.replicas, self.router.cfg, algo=self.algo,
                    index=self.router.index, registry=self.obs.registry,
                )
        return self._engine

    def warm(self, rows: int) -> None:
        """Compile what `route_batch` runs for ``rows``-row engine calls:
        one engine call on empty queries per program it can pick, without
        health rows and, under a failover-aware algorithm, with them.
        The gateway's state is left as it was, so a later first ejection
        compiles nothing."""
        if not self.use_kernels:
            return
        eng = self.engine()
        sub = eng.encode([""] * rows)
        masks = [None]
        if self.terms.failover:
            masks.append(np.zeros((rows, self.n_replicas), bool))
            masks[-1][:, 0] = True
        for mask in masks:
            eng.route(sub, self._telemetry.raw(), self._utilization(),
                      failed_mask=mask)

    def route_batch(
        self,
        request_texts: Sequence[str],
        client_regions: Optional[Sequence[int]] = None,
        pad_to: Optional[int] = None,
        session_ids: Optional[Sequence] = None,
    ) -> list:
        """Fleet-scale batched routing: the request batch runs through the
        jit-compiled engine (two-stage BM25 + Pallas QoS + fused selection)
        against one telemetry snapshot; executions are then recorded in
        arrival order (feed-forward, Sec. III-B).  ``client_regions``
        (aligned with the texts) tags each request's origin for
        locality-aware algorithms; the per-request RTT rows are gathered
        inside the engine from the gateway's region RTT matrix.

        The whole request set is encoded in **one** host pass
        (`EncodedBatch.slice` is bit-identical to per-chunk encoding), so
        the per-chunk Python between engine calls is just array slicing.

        With a load-aware algorithm the batch is routed in `lb_chunk`-sized
        chunks: each chunk's picks are counted in-flight before the next
        chunk routes, so one hot batch spreads across replicas instead of
        herding onto the single top-scored one.  A single-replica pool
        skips the chunking: there is nothing to spread to, and chunk-by-
        chunk in-flight feedback would only inflate the utilization signal
        (every earlier chunk still counted outstanding) and distort the
        recorded scores.

        ``pad_to`` fixes the compiled batch shape for the micro-batch
        serving path: each engine call is padded with all-zero query rows
        to ``pad_to`` rows (or to ``lb_chunk`` on the chunked path), so
        arbitrary micro-batch sizes reuse one XLA program per bucket
        instead of compiling one per size.  Padded rows draw no health
        probes, carry no region tag, and their decisions are discarded
        before any accounting — the real rows' decisions are
        argmax-identical to the unpadded call (row-wise pipeline;
        parity-tested in tests/test_microbatch.py)."""
        if not request_texts:
            return []                 # nothing to route: do not build the
                                      # engine or touch accounting state
        if not self.use_kernels:
            return [
                self.route(
                    t,
                    None if client_regions is None else client_regions[i],
                    None if session_ids is None else session_ids[i],
                )
                for i, t in enumerate(request_texts)
            ]
        eng = self.engine()
        use_geo = client_regions is not None and self.terms.rtt
        regions_arr = (
            np.asarray(client_regions, np.int32) if use_geo else None
        )
        use_aff = session_ids is not None and self.terms.affinity
        with obs_trace.annotate("gateway.encode", self._m_phase["encode"]):
            enc = eng.encode(request_texts)
        dispatch_ms = 0.0
        health_bytes = 0
        draws0 = self._health_draws
        # the ejected set this flush's decisions see (health moves at merge)
        ejected_seen = self._m_ejected.value
        picks: list = []
        chunked = self.router.uses_load and self.n_replicas > 1
        step = self.lb_chunk if chunked else (pad_to or len(request_texts))
        step = max(step, 1)
        for lo in range(0, len(request_texts), step):
            n_chunk = min(step, len(request_texts) - lo)
            sub = enc.slice(lo, lo + n_chunk)
            mask = self._health_mask(n_chunk)
            health_bytes += 0 if mask is None else mask.nbytes
            reg = regions_arr[lo : lo + n_chunk] if use_geo else None
            if pad_to is not None and sub.n < step:
                sub = sub.pad_to(step)
                if mask is not None:
                    mask = np.concatenate(
                        [mask, np.zeros((step - n_chunk, mask.shape[1]),
                                        bool)], axis=0,
                    )
                if reg is not None:
                    reg = np.concatenate(
                        [reg, np.full(step - n_chunk, -1, np.int32)]
                    )
            geo_kw = {}
            if use_geo:
                geo_kw = dict(
                    client_region=reg, region_rtt_ms=self.region_rtt_ms
                )
            aff = None
            if use_aff:
                # per-request warmth rows [sub.n, n_replicas]: cold /
                # session-less / padded rows stay zero; an all-zero
                # matrix is dropped so affinity-free chunks keep the
                # exact historical scoring graph (byte-identity gate)
                aff = np.zeros((sub.n, self.n_replicas), np.float32)
                warm_any = False
                for qi in range(n_chunk):
                    row = self._session_affinity(session_ids[lo + qi])
                    if row is not None:
                        aff[qi] = row
                        warm_any = True
                if not warm_any:
                    aff = None
            with obs_trace.annotate("gateway.dispatch") as ph:
                dec = eng.route(
                    sub, self._telemetry.raw(), self._utilization(),
                    failed_mask=mask,
                    affinity=aff,
                    route_stats=self._route_stats,
                    n_real=n_chunk if sub.n != n_chunk else None,
                    **geo_kw,
                )
            dispatch_ms += ph.ms
            adapting = getattr(eng, "adapt_state", None) is not None
            for qi in range(n_chunk):
                idx = int(dec.server_idx[qi])
                expertise = float(dec.expertise[qi])
                network = float(dec.network[qi])
                feats = None
                if adapting:
                    feats = self._batch_feats(
                        idx, expertise, network,
                        None if reg is None else int(reg[qi]),
                    )
                self.in_flight[idx] += 1.0
                self._m_in_flight.inc()
                sid = None if session_ids is None else session_ids[lo + qi]
                picks.append((idx, expertise, network, feats, sid,
                              request_texts[lo + qi]))
        # one observe per flush: the chunks' dispatch walls summed
        self._m_phase["dispatch"].observe(dispatch_ms)
        health_draws = float(self._health_draws - draws0)
        self._m_health_bytes.set(float(health_bytes))
        self._m_health_draws.set(health_draws)
        obs_trace.note(gateway_ejected=ejected_seen,
                       gateway_health_row_bytes_per_flush=float(health_bytes),
                       gateway_health_draws_per_flush=health_draws)
        out = []
        with obs_trace.annotate("gateway.merge", self._m_phase["merge"]):
            for idx, expertise, network, feats, sid, text in picks:
                latency = self._call(idx, text)
                ok = latency < latlib.OFFLINE_MS
                self._record_outcome(idx, ok)
                self._observe(idx, latency)
                self._session_touch(sid, idx, ok)
                if feats is not None:
                    eng.observe_feedback(latency, ok=ok, feats=feats)
                self.in_flight[idx] = max(self.in_flight[idx] - 1.0, 0.0)
                self._m_in_flight.dec()
                out.append(self._account(RouteResult(
                    replica_idx=idx, latency_ms=latency, ok=ok,
                    expertise=expertise, network=network,
                )))
            if getattr(eng, "adapt_state", None) is not None:
                self._publish_adapt(eng.adapt_state)
        return out

    def report(self) -> dict:
        """Gateway summary, read from the metrics registry (the same
        instruments the serving layers above update — one source of
        truth for request counts, failures, health ejections, shed, and
        in-flight).  ``p99_ms`` is the log-bucket histogram quantile
        (docs/observability.md lists the error bound); count, mean, and
        failure rate are exact."""
        reg = self.obs.registry
        n = int(self._m_latency.count)
        return {
            "n": n,
            "al_ms": self._m_latency.mean,
            "p99_ms": self._m_latency.p99,
            "failure_rate": self._m_failures.value / n if n else 0.0,
            "in_flight": self._m_in_flight.value,
            "unmatched_finish": self._m_unmatched.value,
            "ejected": self._m_ejected.value,
            "ejections": self._m_ejections.value,
            "readmissions": self._m_readmissions.value,
            "shed": reg.value("serving_shed_total"),
            "expired": reg.value("serving_expired_total"),
        }
