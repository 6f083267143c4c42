"""Batched, end-to-end jit-compiled SONAR routing engine.

The scalar `Router.select` routes one query at a time through numpy
argsorts; this module runs the whole decision for a *batch* of queries
inside one jit-compiled JAX pipeline (paper Sec. IV, Eq. 1-9):

  1. stage-1 server scoring + top-s         (Eq. 1-2, BM25 matmul + top_k)
  2. stage-1 candidate mask over tools      (Eq. 3 mask)
  3. stage-2 tool scoring                   (Eq. 3-4, BM25 matmul)
  4. fused candidate top-k + softmax expertise + QoS fusion + argmax
                                            (Eq. 4, 5, 8, 9)

On the kernel path steps 3-4 run as ONE single-pass Pallas kernel
(`kernels/score_fuse`): the stage-2 matmul, candidate mask, streaming
top-k, softmax, fusion and argmax are fused over tool stripes so the
[n_q, n_tools] score matrix never exists in HBM; the unfused jnp path
(`kernels/ref.fused_select_ref` on materialized matrices) remains the
oracle.

with the QoS scores N (Eq. 7) produced by the Pallas `qos_scores` kernel
over the telemetry matrix.  No per-query Python runs anywhere between the
encoded inputs and the [n_queries] decision vectors.

Tokenization/encoding is inherently host work (string -> term counts); it
happens once per batch in `encode`, producing an `EncodedBatch` that can be
routed repeatedly (e.g. every retry turn of the batched episode driver)
without touching Python strings again.

Selection parity: for identical inputs the engine is argmax-identical to
`Router.select` for every algorithm (RAG / RerankRAG / PRAG / SONAR /
SONAR-LB / SONAR-FT / SONAR-GEO / SONAR-SESSION) — top-k ties break toward lower indices in
both (stable argsort vs lax.top_k), invalid candidates (fewer than k
tools on candidate servers) are excluded from both softmax mass and the
final argmax, and the argmax tie-breaks toward the higher-ranked
candidate.  `tests/test_batch_routing` asserts identical (server_idx,
tool_idx) across all scenarios x algorithms, and the mesh-sharded engine
(`core.mesh_routing`) extends the same guarantee across device shards.

Telemetry can be shared ([n_servers, T] — one snapshot for the whole batch,
the serving-gateway case) or per-query ([n_q, n_servers, T] — each query
routed at its own simulated time, the episode-driver case).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bm25
from repro.core.dataset import Server
from repro.core.qos import (
    QosParams,
    load_penalty,
    network_score,
    rtt_penalty,
    staleness_discount,
)
from repro.core.routing import (
    ALGORITHMS,
    BM25_STAGE_MS,
    LLM_CALL_MS,
    LLM_RERANK_MS,
    RoutingConfig,
    ToolIndex,
    predict_tool_type,
)
from repro.core import adaptive as _adaptive
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.obs import trace as obs_trace
from repro.obs.metrics import MetricsRegistry

NEG = kref.NEG


@dataclasses.dataclass
class EncodedBatch:
    """Host-encoded query batch (built once, routed many times)."""

    q_server: np.ndarray          # [n_q, V_server] term counts
    q_tool: np.ndarray            # [n_q, V_tool]
    q_rerank: Optional[np.ndarray]  # [n_q, V_tool] canonical intents (rerank)
    n: int

    def slice(self, lo: int, hi: int) -> "EncodedBatch":
        """Rows [lo, hi) as a new batch.  Encoding is strictly per-row
        (`Bm25Corpus.encode_query` builds each term-count vector
        independently), so slicing a whole-set encoding is bit-identical
        to encoding the chunk's texts directly — the serving gateway
        relies on this to encode a request set once and feed its chunks
        to the engine without re-touching Python strings."""
        hi = min(hi, self.n)
        return EncodedBatch(
            q_server=self.q_server[lo:hi],
            q_tool=self.q_tool[lo:hi],
            q_rerank=None if self.q_rerank is None else self.q_rerank[lo:hi],
            n=max(hi - lo, 0),
        )

    def pad_to(self, n_rows: int) -> "EncodedBatch":
        """Pad with all-zero query rows up to ``n_rows`` (no-op when
        already that long).  Zero rows carry no query terms, so every
        candidate ties at score 0 and the padded decisions are discarded
        by the caller; real rows are untouched — the jit pipeline is
        row-wise, so padding only fixes the compiled batch shape (one
        XLA program per bucket instead of one per micro-batch size)."""
        pad = n_rows - self.n
        if pad <= 0:
            return self
        z = lambda m: np.concatenate(  # noqa: E731
            [m, np.zeros((pad, m.shape[1]), m.dtype)], axis=0
        )
        return EncodedBatch(
            q_server=z(self.q_server),
            q_tool=z(self.q_tool),
            q_rerank=None if self.q_rerank is None else z(self.q_rerank),
            n=n_rows,
        )


@dataclasses.dataclass
class BatchDecisions:
    """Struct-of-arrays routing decisions for one batch."""

    server_idx: np.ndarray        # [n_q] i32
    tool_idx: np.ndarray          # [n_q] i32
    expertise: np.ndarray         # [n_q] f32 — C(i*) (Eq. 5)
    network: np.ndarray           # [n_q] f32 — N(i*) (Eq. 7)
    fused: np.ndarray             # [n_q] f32 — S(i*) (Eq. 8)
    select_latency_ms: float      # per-query SL (same accounting as scalar)

    def __len__(self) -> int:
        return len(self.server_idx)


def encode_for_index(
    index, uses_prediction: bool, rerank: bool, queries: Sequence[str]
) -> EncodedBatch:
    """Encode query strings against an index's corpora.

    The only per-query Python in any batched routing path (strings ->
    term-count matrices); shared by `BatchRoutingEngine.encode` and the
    mesh-sharded engine so both paths score byte-identical encodings.

    Parameters
    ----------
    index : ToolIndex or TiledFleetIndex
        Must expose ``server_corpus`` / ``tool_corpus`` with
        ``encode_queries`` and ``vocab``.
    uses_prediction : bool
        Apply the deterministic LLM-preprocess stand-in
        (`predict_tool_type`) before encoding (PRAG/SONAR family).
    rerank : bool
        Also encode the canonical-intent rerank queries (RerankRAG).
    queries : Sequence[str]

    Returns
    -------
    EncodedBatch
        ``q_server`` [n_q, V_server], ``q_tool`` [n_q, V_tool] f32 term
        counts, optional ``q_rerank`` [n_q, V_tool], and ``n`` = len(queries).
    """
    if uses_prediction:
        qtexts = [predict_tool_type(q)[1] for q in queries]
    else:
        qtexts = list(queries)
    if not qtexts:
        v_s = len(index.server_corpus.vocab)
        v_t = len(index.tool_corpus.vocab)
        empty = lambda v: np.zeros((0, v), np.float32)  # noqa: E731
        return EncodedBatch(
            q_server=empty(v_s), q_tool=empty(v_t),
            q_rerank=empty(v_t) if rerank else None, n=0,
        )
    q_server = index.server_corpus.encode_queries(qtexts)
    q_tool = index.tool_corpus.encode_queries(qtexts)
    q_rerank = None
    if rerank:
        q_rerank = index.tool_corpus.encode_queries(
            [predict_tool_type(q)[1] for q in queries]
        )
    return EncodedBatch(
        q_server=q_server, q_tool=q_tool, q_rerank=q_rerank, n=len(queries)
    )


# ---------------------------------------------------------------------------
# The jit pipeline (module-level so the compile cache is shared by engines)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=(
        "top_s", "top_k", "alpha", "beta", "gamma", "load_knee", "load_sharp",
        "delta", "rtt_scale", "temp", "stale_half_life", "use_network",
        "use_load", "use_staleness", "use_failover", "use_rtt", "use_aff",
        "eps", "rerank", "use_kernels", "qos_params", "interpret", "n_servers",
        "row_blocks",
    ),
)
def _route_pipeline(
    q_server: jax.Array,          # [n_q, V_s]
    q_tool: jax.Array,            # [n_q, V_t]
    q_rerank: Optional[jax.Array],
    w_server: jax.Array,          # [n_servers, V_s] (kernel path: zero-
                                  # padded to ops.bm25_corpus_shape)
    w_tool: jax.Array,            # [n_tools, V_t] (kernel path: zero-padded
                                  # to ops.score_fuse_corpus_shape)
    tool_server: jax.Array,       # [n_tools] i32
    latency_hist: Optional[jax.Array],  # [n_servers, T] or [n_q, n_servers, T]
    server_load: Optional[jax.Array],   # [n_servers] or [n_q, n_servers] rho
    telemetry_age: Optional[jax.Array],  # [n_servers] or [n_q, n_servers] s
    dead_mask: Optional[jax.Array],      # [n_servers] or [n_q, n_servers] 0/1
    client_rtt: Optional[jax.Array],     # [n_servers] or [n_q, n_servers] ms
    region_idx: Optional[jax.Array],     # [n_q] i32 client region per request
    region_rtt: Optional[jax.Array],     # [n_regions, n_servers] ms
    affinity: Optional[jax.Array] = None,  # [n_servers] or [n_q, n_servers]
                                           # session warmth W in [0,1]
    adapt_w: Optional[jax.Array] = None,  # [4] f32 live [alpha, beta, gamma,
                                          # delta] (SONAR-ADAPT); None keeps
                                          # the static specialization
    *,
    top_s: int,
    top_k: int,
    alpha: float,
    beta: float,
    gamma: float,
    load_knee: float,
    load_sharp: float,
    delta: float,
    rtt_scale: float,
    temp: float,
    stale_half_life: float,
    use_network: bool,
    use_load: bool,
    use_staleness: bool,
    use_failover: bool,
    use_rtt: bool,
    use_aff: bool = False,
    eps: float = 0.0,
    rerank: bool,
    use_kernels: bool,
    qos_params: QosParams,
    interpret: Optional[bool],
    n_servers: int,
    row_blocks: bool = False,
):
    # the real counts: the kernel path's corpora carry zero rows past them
    n_tools = tool_server.shape[0]

    # each stage runs under a named scope, so a profile's op metadata says
    # which stage a device op belongs to (instruction names do not change)
    # -- stage 1: server scores + top-s candidate mask (Eq. 1-2) --
    with jax.named_scope("stage1_bm25"):
        if use_kernels:
            s_scores = ops.bm25_scores(q_server, w_server, n_docs=n_servers,
                                       interpret=interpret)
        else:
            s_scores = bm25.bm25_scores(w_server, q_server, row_blocks)
        # SONAR-FT: demote known-failed servers below every live one before
        # the top-s, so failover escapes an all-dead candidate set (mirrors
        # the scalar `_candidates` masking; NEG ties re-fill in index order)
        if use_failover and dead_mask is not None:
            dm_server = dead_mask.astype(jnp.float32)
            if dm_server.ndim == 1:
                dm_server = dm_server[None, :]
            s_scores = jnp.where(dm_server > 0.0, NEG, s_scores)
        _, cand_servers = jax.lax.top_k(s_scores, min(top_s, n_servers))
    with jax.named_scope("candidates"):
        member = jnp.any(
            cand_servers[:, :, None] == jnp.arange(n_servers)[None, None, :], axis=1
        )                                                       # [n_q, n_servers]
        in_cand = jnp.take(member, tool_server, axis=1)         # [n_q, n_tools]

    # -- stage 2: tool scores, masked outside candidate servers (Eq. 3-4),
    # plus the rerank re-valuation (RerankRAG).  Only the unfused path
    # materializes the [n_q, n_tools] matrices — the kernel path streams
    # them stripe-by-stripe inside `ops.fused_score_select` below --
    with jax.named_scope("score_fuse"):
        if not use_kernels:
            t_scores = bm25.bm25_scores(w_tool, q_tool, row_blocks)
            sel = jnp.where(in_cand, t_scores, NEG)
            val = (bm25.bm25_scores(w_tool, q_rerank, row_blocks) if rerank
                   else sel)

    # -- QoS N per tool (Eq. 6-7): Pallas kernel over the telemetry matrix --
    with jax.named_scope("qos"):
        if use_network and latency_hist is not None:
            if latency_hist.ndim == 3:                          # per-query windows
                n_q = latency_hist.shape[0]
                flat = latency_hist.reshape(n_q * n_servers, latency_hist.shape[-1])
                if use_kernels:
                    n_server = ops.qos_scores(flat, qos_params, interpret=interpret)
                else:
                    n_server = network_score(flat, qos_params)
                n_server = n_server.reshape(n_q, n_servers)
            else:
                if use_kernels:
                    n_server = ops.qos_scores(latency_hist, qos_params,
                                              interpret=interpret)
                else:
                    n_server = network_score(latency_hist, qos_params)
            # SONAR-FT staleness discount: elementwise per-server multiply
            # commutes with the per-tool gather below, so this matches the
            # scalar router's per-candidate discount bit-for-bit.
            if use_staleness and telemetry_age is not None:
                n_server = n_server * staleness_discount(
                    telemetry_age, stale_half_life
                )
            if n_server.ndim == 2:
                tool_qos = jnp.take(n_server, tool_server, axis=1)  # [n_q, n_tools]
            else:
                tool_qos = n_server[tool_server]                # [n_tools]
            # SONAR-ADAPT: the live weight vector replaces the static floats
            # only on its *active* terms — inactive terms keep their structural
            # literals, preserving the reduction identities below
            if adapt_w is not None:
                eff_alpha, eff_beta = adapt_w[0], adapt_w[1]
            else:
                eff_alpha, eff_beta = alpha, beta
        else:
            tool_qos = jnp.zeros((n_tools,), jnp.float32)
            eff_alpha, eff_beta = 1.0, 0.0                      # S = C (scalar path)

    # -- SONAR-LB load term: per-server utilization penalty, broadcast to
    # tools of the host server (shared [n_servers] or per-query) --
    with jax.named_scope("candidates"):
        if use_load and server_load is not None:
            pen = load_penalty(server_load, load_knee, load_sharp)
            if server_load.ndim == 2:                           # [n_q, n_servers]
                tool_load = jnp.take(pen, tool_server, axis=1)  # [n_q, n_tools]
            else:
                tool_load = pen[tool_server]                    # [n_tools]
            eff_gamma = adapt_w[2] if adapt_w is not None else gamma
        else:
            tool_load = jnp.zeros((n_tools,), jnp.float32)
            eff_gamma = 0.0

        # -- SONAR-GEO locality term: per-(client-region, server) RTT penalty,
        # broadcast to tools of the host server.  The RTT arrives either as an
        # explicit vector (shared [n_servers] or per-query [n_q, n_servers]) or
        # as a per-request region index gathered from the [n_regions,
        # n_servers] RTT matrix — the gather runs inside the jit pipeline. --
        if use_rtt and (
            client_rtt is not None
            or (region_idx is not None and region_rtt is not None)
        ):
            if client_rtt is None:
                # untagged requests carry region -1 (the simulator's sentinel):
                # clamp the gather and zero their row — R(0) = 0, so they pay
                # no locality penalty, matching the scalar path's convention
                client_rtt = jnp.take(
                    region_rtt, jnp.maximum(region_idx, 0), axis=0
                )
                client_rtt = jnp.where(
                    (region_idx >= 0)[:, None], client_rtt, 0.0
                )
            pen_r = rtt_penalty(client_rtt, rtt_scale)
            if client_rtt.ndim == 2:                            # [n_q, n_servers]
                tool_rtt = jnp.take(pen_r, tool_server, axis=1)  # [n_q, n_tools]
            else:
                tool_rtt = pen_r[tool_server]                   # [n_tools]
            eff_delta = adapt_w[3] if adapt_w is not None else delta
        else:
            tool_rtt = jnp.zeros((n_tools,), jnp.float32)
            eff_delta = 0.0

        # -- SONAR-SESSION sticky-affinity bonus: per-(session, server) warmth
        # W in [0,1], broadcast to the host server's tools.  The warmth array
        # is *data* (eps alone is static), so per-request affinity changes
        # never recompile; when absent the term vanishes from the traced graph
        # and the compiled program is byte-identical to SONAR-GEO's. --
        if use_aff and affinity is not None:
            if affinity.ndim == 2:                              # [n_q, n_servers]
                tool_aff = jnp.take(affinity, tool_server, axis=1)
            else:
                tool_aff = affinity[tool_server]                # [n_tools]
        else:
            tool_aff = None

        # -- SONAR-FT failed-server mask, broadcast to the host server's tools --
        if use_failover and dead_mask is not None:
            dm = dead_mask.astype(jnp.float32)
            if dm.ndim == 2:                                    # [n_q, n_servers]
                tool_dead = jnp.take(dm, tool_server, axis=1)   # [n_q, n_tools]
            else:
                tool_dead = dm[tool_server]                     # [n_tools]
        else:
            tool_dead = None

    # -- fused stage-2 scoring + candidate top-k + Eq. 5 softmax + Eq. 8
    # fusion + argmax: one Pallas pass (kernels/score_fuse) on the kernel
    # path; the unfused jnp oracle otherwise --
    with jax.named_scope("score_fuse"):
        if use_kernels:
            tool_idx, c, n, s = ops.fused_score_select(
                q_tool, w_tool, tool_server, cand_servers,
                tool_qos, tool_load, tool_dead,
                q_rerank if rerank else None,
                k=top_k, alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
                tool_rtt=tool_rtt, delta=eff_delta,
                tool_aff=tool_aff, eps=eps,
                temp=temp, interpret=interpret,
            )
        else:
            tool_idx, c, n, s = kref.fused_select_ref(
                sel, val, tool_qos, tool_load, tool_dead,
                k=top_k, alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
                tool_rtt=tool_rtt, delta=eff_delta,
                tool_aff=tool_aff, eps=eps,
                temp=temp,
            )
    with jax.named_scope("select"):
        server_idx = tool_server[tool_idx]
    return server_idx, tool_idx, c, n, s


@functools.partial(
    jax.jit,
    static_argnames=(
        "top_s", "top_k", "alpha", "beta", "gamma", "load_knee", "load_sharp",
        "delta", "rtt_scale", "temp", "stale_half_life", "use_network",
        "use_load", "use_staleness", "use_failover", "use_rtt", "use_aff",
        "eps", "rerank", "use_kernels", "qos_params", "interpret", "acfg",
        "n_servers", "row_blocks",
    ),
    donate_argnums=(0,),
)
def _route_adaptive(
    adapt_state,                  # AdaptState pytree (donated, like the
                                  # gateway's telemetry ring)
    fb_reward: jax.Array,         # [FEEDBACK_BUCKET] f32 shaped rewards
    fb_feats: jax.Array,          # [FEEDBACK_BUCKET, 4] f32 [C, N, -U, -R]
    fb_valid: jax.Array,          # [FEEDBACK_BUCKET] f32 pad mask
    q_server: jax.Array,
    q_tool: jax.Array,
    q_rerank: Optional[jax.Array],
    w_server: jax.Array,
    w_tool: jax.Array,
    tool_server: jax.Array,
    latency_hist: Optional[jax.Array],
    server_load: Optional[jax.Array],
    telemetry_age: Optional[jax.Array],
    dead_mask: Optional[jax.Array],
    client_rtt: Optional[jax.Array],
    region_idx: Optional[jax.Array],
    region_rtt: Optional[jax.Array],
    affinity: Optional[jax.Array] = None,
    *,
    acfg,
    top_s: int,
    top_k: int,
    alpha: float,
    beta: float,
    gamma: float,
    load_knee: float,
    load_sharp: float,
    delta: float,
    rtt_scale: float,
    temp: float,
    stale_half_life: float,
    use_network: bool,
    use_load: bool,
    use_staleness: bool,
    use_failover: bool,
    use_rtt: bool,
    use_aff: bool = False,
    eps: float = 0.0,
    rerank: bool,
    use_kernels: bool,
    qos_params: QosParams,
    interpret: Optional[bool],
    n_servers: int,
    row_blocks: bool = False,
):
    """SONAR-ADAPT hot path: ONE jit program that applies the pending EG
    update and routes the batch with the freshly-updated weights.  The
    update is a handful of FLOPs over a fixed-size feedback bucket fused
    ahead of the (dominating) scoring pipeline, so learning adds no extra
    dispatch and no host sync — the state round-trips device-side.

    `_adaptive._adapt_step` is looked up on the module at trace time so
    the mutation harness can monkeypatch it (with `jax.clear_caches()`)
    and prove the trajectory assertions have teeth."""
    new_state = _adaptive._adapt_step(
        adapt_state, fb_reward, fb_feats, fb_valid, acfg
    )
    server_idx, tool_idx, c, n, s = _route_pipeline(
        q_server, q_tool, q_rerank, w_server, w_tool, tool_server,
        latency_hist, server_load, telemetry_age, dead_mask,
        client_rtt, region_idx, region_rtt, affinity, new_state.weights,
        top_s=top_s, top_k=top_k, alpha=alpha, beta=beta, gamma=gamma,
        load_knee=load_knee, load_sharp=load_sharp, delta=delta,
        rtt_scale=rtt_scale, temp=temp, stale_half_life=stale_half_life,
        use_network=use_network, use_load=use_load,
        use_staleness=use_staleness, use_failover=use_failover,
        use_rtt=use_rtt, use_aff=use_aff, eps=eps,
        rerank=rerank, use_kernels=use_kernels,
        qos_params=qos_params, interpret=interpret, n_servers=n_servers,
        row_blocks=row_blocks,
    )
    return server_idx, tool_idx, c, n, s, new_state


def engine_phase_histograms(registry: Optional[MetricsRegistry]) -> dict:
    """The ``engine_phase_{upload,enqueue,readback}_ms`` histograms a
    routing engine times each `route` call into: host arrays to device
    operands, the jit call up to its (asynchronous) return, and the host
    conversions of the outputs (waiting on the device, then D2H).  In
    ``registry`` (a gateway's), or a private one."""
    reg = registry if registry is not None else MetricsRegistry()
    return {ph: reg.histogram(f"engine_phase_{ph}_ms", "ms")
            for ph in ("upload", "enqueue", "readback")}


class BatchRoutingEngine:
    """Vectorized drop-in for a fleet of `Router.select` calls.

    One engine per (server pool, algorithm, config); `encode` turns query
    strings into term-count matrices on the host, `route` runs the full
    jit-compiled decision for the batch and times its phases into
    ``registry`` (see `engine_phase_histograms`).  The gauge
    ``engine_corpus_pad_bytes_per_call`` holds the corpus bytes the route
    program pads per call (`ops.corpus_pad_bytes`): 0 on both paths, the
    kernel path storing its corpora aligned.
    """

    def __init__(
        self,
        servers: Sequence[Server],
        cfg: RoutingConfig = RoutingConfig(),
        algo: str = "sonar",
        use_kernels: Optional[bool] = None,
        interpret: Optional[bool] = None,
        index: Optional[ToolIndex] = None,
        adapt: Optional[_adaptive.AdaptConfig] = None,
        registry: Optional[MetricsRegistry] = None,
    ):
        if use_kernels is None:
            # The Pallas kernels are the fast path on TPU; on CPU they run
            # in interpret mode (an emulator), where the argmax-identical
            # pure-jnp pipeline is ~8x faster — pick per backend.
            use_kernels = jax.default_backend() == "tpu"
        self.cfg = cfg
        self.algo = algo.lower().replace("-", "_")
        router_cls = ALGORITHMS[self.algo]
        self.uses_prediction = router_cls.uses_prediction
        self.uses_network = router_cls.uses_network
        self.uses_load = router_cls.uses_load
        self.uses_staleness = router_cls.uses_staleness
        self.uses_failover = router_cls.uses_failover
        self.uses_rtt = router_cls.uses_rtt
        self.uses_affinity = router_cls.uses_affinity
        self.rerank = router_cls.rerank
        self.use_kernels = use_kernels
        self.interpret = interpret
        self.index = index if index is not None else ToolIndex(servers)
        self._tool_server = jnp.asarray(self.index.tool_server)
        w_server = self.index.server_corpus.weights
        w_tool = self.index.tool_corpus.weights
        self.n_servers = int(w_server.shape[0])
        if use_kernels:
            # stored once at the shapes the kernels read, so no route call
            # pads or relays the corpora (zero rows and terms are exact)
            self._w_server = ops.bm25_corpus(w_server)
            self._w_tool = ops.score_fuse_corpus(w_tool)
            pad_bytes = ops.corpus_pad_bytes(self._w_server.shape,
                                             self._w_tool.shape)
        else:
            self._w_server = jnp.asarray(w_server)
            self._w_tool = jnp.asarray(w_tool)
            pad_bytes = 0
        reg = registry if registry is not None else MetricsRegistry()
        reg.gauge("engine_corpus_pad_bytes_per_call", "B").set(pad_bytes)
        # SONAR-ADAPT learner state (None for the hand-tuned algorithms)
        self.adapt_cfg: Optional[_adaptive.AdaptConfig] = None
        self.adapt_state: Optional[_adaptive.AdaptState] = None
        self._fb_rewards: list = []
        self._fb_feats: list = []
        if self.algo == "sonar_adapt" or adapt is not None:
            self.adapt_cfg = adapt if adapt is not None else _adaptive.AdaptConfig()
            self.adapt_state = _adaptive.init_state(cfg, self.adapt_cfg)
        self._m_phase = engine_phase_histograms(reg)

    # -- host side ----------------------------------------------------------
    def encode(self, queries: Sequence[str]) -> EncodedBatch:
        """Strings -> term-count matrices (the only per-query Python)."""
        return encode_for_index(
            self.index, self.uses_prediction, self.rerank, queries
        )

    def select_latency_ms(self) -> float:
        """Per-query SL with the same accounting as the scalar router."""
        sl = LLM_CALL_MS + 2 * BM25_STAGE_MS
        if self.rerank:
            sl += LLM_RERANK_MS
        return sl

    # -- SONAR-ADAPT feedback -----------------------------------------------
    @property
    def adapt_weights(self) -> Optional[np.ndarray]:
        """Live [alpha, beta, gamma, delta] (host copy), or None."""
        if self.adapt_state is None:
            return None
        return np.asarray(self.adapt_state.weights, np.float32)

    def observe_feedback(
        self,
        latency_ms: float,
        ok: bool = True,
        feats: Optional[np.ndarray] = None,
    ) -> None:
        """Record one completed call's outcome (host side, cheap append).
        The shaped reward + winner features are folded into the weight
        vector by the next `route` call's fused update."""
        if self.adapt_state is None or feats is None:
            return
        self._fb_rewards.append(
            _adaptive.shape_reward(latency_ms, ok, self.adapt_cfg.slo_ms)
        )
        self._fb_feats.append(np.asarray(feats, np.float32))

    def _drain_feedback(self):
        """Pending outcomes -> one padded (reward, feats, valid) bucket.
        Overflow beyond FEEDBACK_BUCKET is applied immediately through the
        standalone jit update (same `_adapt_step`, same bucket shape) so
        no feedback is ever dropped and no new program shape appears."""
        B = _adaptive.FEEDBACK_BUCKET
        while len(self._fb_rewards) > B:
            r, f, v = _adaptive.pad_feedback(
                self._fb_rewards[:B], self._fb_feats[:B], B
            )
            self.adapt_state = _adaptive.adapt_update(
                self.adapt_state, r, f, v, self.adapt_cfg
            )
            del self._fb_rewards[:B]
            del self._fb_feats[:B]
        r, f, v = _adaptive.pad_feedback(self._fb_rewards, self._fb_feats, B)
        self._fb_rewards.clear()
        self._fb_feats.clear()
        # host arrays go straight into the jit call: its batched transfer
        # is cheaper than three eager device_puts on the flush hot path
        return r, f, v

    # -- device side --------------------------------------------------------
    def route(
        self,
        batch: EncodedBatch,
        latency_hist: Optional[np.ndarray] = None,
        server_load: Optional[np.ndarray] = None,
        telemetry_age_s: Optional[np.ndarray] = None,
        failed_mask: Optional[np.ndarray] = None,
        client_rtt_ms: Optional[np.ndarray] = None,
        client_region: Optional[np.ndarray] = None,
        region_rtt_ms: Optional[np.ndarray] = None,
        affinity: Optional[np.ndarray] = None,
        route_stats=None,
        n_real=None,
    ) -> BatchDecisions:
        """Route an encoded batch through the jit pipeline.

        Every telemetry input comes in two shapes: *shared* (one snapshot
        for the whole batch — the serving-gateway case) or *per-query*
        (each query routed at its own simulated time — the episode-driver
        case).

        Parameters
        ----------
        batch : EncodedBatch
            From `encode` — reusable across calls (e.g. retry turns).
        latency_hist : np.ndarray, optional
            f32 [n_servers, T] or [n_q, n_servers, T], **ms**, most recent
            sample last.
        server_load : np.ndarray, optional
            f32 [n_servers] or [n_q, n_servers] utilization rho
            (dimensionless).
        telemetry_age_s : np.ndarray, optional
            f32 [n_servers] or [n_q, n_servers], **seconds** since last
            fresh sample.
        failed_mask : np.ndarray, optional
            bool [n_servers] or [n_q, n_servers]; True excludes the
            server from the argmax (SONAR-FT).
        client_rtt_ms : np.ndarray, optional
            f32 [n_servers] (every request from one region — the
            gateway case) or [n_q, n_servers] (per-request RTT rows),
            **ms**.  SONAR-GEO only.
        client_region : np.ndarray, optional
            i32 [n_q] per-request client-region index; paired with
            ``region_rtt_ms`` [n_regions, n_servers] the RTT row is
            gathered *inside* the jit pipeline (ignored when
            ``client_rtt_ms`` is given).  SONAR-GEO only.
        region_rtt_ms : np.ndarray, optional
            f32 [n_regions, n_servers] region->server propagation RTT
            matrix (e.g. `repro.geo.GeoPlacement.region_server_rtt`).
        affinity : np.ndarray, optional
            f32 [n_servers] (one session per batch — the gateway
            micro-batch case) or [n_q, n_servers] (per-request warmth
            rows) session warmth W in [0, 1].  SONAR-SESSION only; the
            bonus ``+eps*W`` rides as data, so warmth updates between
            batches never trigger a recompile.
        route_stats : repro.obs.DeviceRouteStats, optional
            Jit-safe observability accumulator: the pipeline's *device*
            outputs are folded into it by a donated jit `.at[].add`
            before any host conversion — one extra async dispatch, zero
            added syncs.  ``n_real`` (dynamic scalar) excludes trailing
            padded rows (the gateway's ``pad_to`` path) from the stats
            without specializing the compiled program per real count.

        Returns
        -------
        BatchDecisions
            Struct-of-arrays, each [n_q]; argmax-identical to a scalar
            `Router.select` loop over the same inputs.  Deterministic.
        """
        if batch.n == 0:
            z = np.zeros((0,), np.float32)
            return BatchDecisions(
                server_idx=z.astype(np.int32), tool_idx=z.astype(np.int32),
                expertise=z, network=z, fused=z,
                select_latency_ms=self.select_latency_ms(),
            )
        adapting = self.adapt_state is not None and self.adapt_cfg.lr != 0.0
        with obs_trace.annotate("engine.upload", self._m_phase["upload"]):
            operands, statics = self._pipeline_args(
                batch, latency_hist, server_load, telemetry_age_s,
                failed_mask, client_rtt_ms, client_region, region_rtt_ms,
                affinity,
            )
            if adapting:
                fb_r, fb_f, fb_v = self._drain_feedback()
        with obs_trace.annotate("engine.enqueue", self._m_phase["enqueue"]):
            if adapting:
                # fused update + route: one program, no extra dispatch.
                # At lr == 0 we fall through to the static path below,
                # whose compiled program is byte-identical to the
                # hand-tuned variant's (the weights can never leave their
                # init).
                server_idx, tool_idx, c, n, s, self.adapt_state = (
                    _route_adaptive(
                        self.adapt_state, fb_r, fb_f, fb_v, *operands,
                        acfg=self.adapt_cfg, **statics,
                    )
                )
            else:
                server_idx, tool_idx, c, n, s = _route_pipeline(
                    *operands, **statics,
                )
            if route_stats is not None:
                route_stats.accumulate(server_idx, c, n, s, n_real=n_real)
        with obs_trace.annotate("engine.readback", self._m_phase["readback"]):
            return BatchDecisions(
                server_idx=np.asarray(server_idx),
                tool_idx=np.asarray(tool_idx),
                expertise=np.asarray(c),
                network=np.asarray(n),
                fused=np.asarray(s),
                select_latency_ms=self.select_latency_ms(),
            )

    def lower(self, batch: EncodedBatch, *args, **kw) -> jax.stages.Lowered:
        """The program `route` would run on these inputs (same arguments,
        without ``route_stats``/``n_real``), lowered but not run.  Its
        ``.compile().as_text()`` names every Pallas kernel compiled into
        the route (``tpu_custom_call`` on a TPU)."""
        operands, statics = self._pipeline_args(batch, *args, **kw)
        if self.adapt_state is not None and self.adapt_cfg.lr != 0.0:
            fb = _adaptive.pad_feedback([], [], _adaptive.FEEDBACK_BUCKET)
            return _route_adaptive.lower(
                self.adapt_state, *fb, *operands, acfg=self.adapt_cfg,
                **statics,
            )
        return _route_pipeline.lower(*operands, **statics)

    def _pipeline_args(
        self,
        batch: EncodedBatch,
        latency_hist=None,
        server_load=None,
        telemetry_age_s=None,
        failed_mask=None,
        client_rtt_ms=None,
        client_region=None,
        region_rtt_ms=None,
        affinity=None,
    ) -> tuple:
        """(operands, static kwargs) of the jit pipeline for one call."""
        lat = None
        if self.uses_network and latency_hist is not None:
            lat = jnp.asarray(latency_hist, jnp.float32)
        load = None
        if self.uses_load and server_load is not None and self.cfg.gamma != 0.0:
            load = jnp.asarray(server_load, jnp.float32)
        age = None
        if self.uses_staleness and telemetry_age_s is not None:
            age = jnp.asarray(telemetry_age_s, jnp.float32)
        dead = None
        if self.uses_failover and failed_mask is not None:
            dead = jnp.asarray(failed_mask, jnp.float32)
        rtt = reg_idx = reg_rtt = None
        if self.uses_rtt and self.cfg.delta != 0.0:
            if client_rtt_ms is not None:
                rtt = jnp.asarray(client_rtt_ms, jnp.float32)
            elif client_region is not None and region_rtt_ms is not None:
                reg_idx = jnp.asarray(client_region, jnp.int32)
                reg_rtt = jnp.asarray(region_rtt_ms, jnp.float32)
        aff = None
        if self.uses_affinity and affinity is not None and self.cfg.eps != 0.0:
            aff = jnp.asarray(affinity, jnp.float32)
        statics = dict(
            top_s=self.cfg.top_s,
            top_k=self.cfg.top_k,
            alpha=self.cfg.alpha,
            beta=self.cfg.beta,
            gamma=self.cfg.gamma,
            load_knee=self.cfg.load_knee,
            load_sharp=self.cfg.load_sharp,
            delta=self.cfg.delta,
            rtt_scale=self.cfg.rtt_scale_ms,
            temp=self.cfg.expertise_temp,
            stale_half_life=self.cfg.stale_half_life_s,
            use_network=self.uses_network and lat is not None,
            use_load=load is not None,
            use_staleness=age is not None,
            use_failover=dead is not None,
            use_rtt=rtt is not None or reg_idx is not None,
            use_aff=aff is not None,
            eps=self.cfg.eps if aff is not None else 0.0,
            rerank=self.rerank,
            use_kernels=self.use_kernels,
            qos_params=self.cfg.qos,
            interpret=self.interpret,
            n_servers=self.n_servers,
            row_blocks=bool(getattr(self.index, "row_blocks", False)),
        )
        operands = (
            jnp.asarray(batch.q_server),
            jnp.asarray(batch.q_tool),
            jnp.asarray(batch.q_rerank)
            if batch.q_rerank is not None else None,
            self._w_server,
            self._w_tool,
            self._tool_server,
            lat,
            load,
            age,
            dead,
            rtt,
            reg_idx,
            reg_rtt,
            aff,
        )
        return operands, statics

    def route_texts(
        self,
        queries: Sequence[str],
        latency_hist: Optional[np.ndarray] = None,
        server_load: Optional[np.ndarray] = None,
        telemetry_age_s: Optional[np.ndarray] = None,
        failed_mask: Optional[np.ndarray] = None,
        client_rtt_ms: Optional[np.ndarray] = None,
        client_region: Optional[np.ndarray] = None,
        region_rtt_ms: Optional[np.ndarray] = None,
        affinity: Optional[np.ndarray] = None,
    ) -> BatchDecisions:
        return self.route(
            self.encode(queries), latency_hist, server_load,
            telemetry_age_s, failed_mask, client_rtt_ms,
            client_region, region_rtt_ms, affinity,
        )

    def route_failover(
        self,
        batch: EncodedBatch,
        latency_hist: Optional[np.ndarray] = None,
        server_load: Optional[np.ndarray] = None,
        telemetry_age_s: Optional[np.ndarray] = None,
        alive: Optional[np.ndarray] = None,      # [n_servers] or
                                                 # [n_q, n_servers] bool
        failed_mask: Optional[np.ndarray] = None,
        budget: Optional[int] = None,
        client_rtt_ms: Optional[np.ndarray] = None,
    ) -> tuple[BatchDecisions, np.ndarray]:
        """Vectorized failover loop: route the batch, probe every pick
        against `alive`, mask the dead picks per query and re-route — at
        most `budget` extra rounds.  Queries whose masks did not grow
        reproduce their decision exactly (identical inputs), so this is the
        batched mirror of `Router.select_failover`.  Returns the final
        decisions and the per-query failover counts."""
        budget = self.cfg.failover_budget if budget is None else int(budget)
        n = batch.n
        mask = np.zeros((n, self.n_servers), bool)
        if failed_mask is not None:
            mask |= np.asarray(failed_mask, bool)
        up = None if alive is None else np.asarray(alive, bool)
        failovers = np.zeros(n, np.int64)
        dec = self.route(
            batch, latency_hist, server_load, telemetry_age_s,
            mask if mask.any() else None, client_rtt_ms,
        )
        if up is None or n == 0:
            return dec, failovers
        for _ in range(budget):
            picks = np.asarray(dec.server_idx)
            if up.ndim == 2:
                pick_up = up[np.arange(n), picks]
            else:
                pick_up = up[picks]
            todo = ~pick_up & (failovers < budget)
            if not todo.any():
                break
            mask[np.flatnonzero(todo), picks[todo]] = True
            failovers[todo] += 1
            dec = self.route(
                batch, latency_hist, server_load, telemetry_age_s, mask,
                client_rtt_ms,
            )
        return dec, failovers


def make_engine(
    algo: str,
    servers: Sequence[Server],
    cfg: RoutingConfig = RoutingConfig(),
    **kw,
) -> BatchRoutingEngine:
    return BatchRoutingEngine(servers, cfg, algo=algo, **kw)
