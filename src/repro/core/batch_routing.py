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
`Router.select` for every registered algorithm (RAG / RerankRAG / PRAG /
SONAR / SONAR-LB / SONAR-FT / SONAR-GEO / SONAR-SESSION / SONAR-ADAPT) —
top-k ties break toward lower indices in both (stable argsort vs
lax.top_k), invalid candidates (fewer than k tools on candidate servers)
are excluded from both softmax mass and the final argmax, and the argmax
tie-breaks toward the higher-ranked candidate.  `tests/test_batch_routing`
asserts identical (server_idx, tool_idx) across all scenarios x
algorithms, and the mesh-sharded engine (`core.mesh_routing`) extends the
same guarantee across device shards.

Telemetry can be shared ([n_servers, T] — one snapshot for the whole batch,
the serving-gateway case) or per-query ([n_q, n_servers, T] — each query
routed at its own simulated time, the episode-driver case).

Both engines share one host layer, `RoutingEngineBase`: encoding, the
call's live terms, the SONAR-ADAPT learner and the timed `route`.  Which
optional terms a call carries is decided once, by `routing.Terms.live`
(the algorithm uses the term, its input is given, its weight is not 0);
the program takes the `RoutingConfig` and that term set as its two
statics.  SONAR-ADAPT's EG step runs in the standalone donated
`adaptive.adapt_update` before the call, and its live weights enter the
program as the ``adapt_w`` operand.
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
    load_penalty,
    network_score,
    rtt_penalty,
    staleness_discount,
)
from repro.core.routing import (
    BM25_STAGE_MS,
    LLM_CALL_MS,
    LLM_RERANK_MS,
    RoutingConfig,
    Terms,
    ToolIndex,
    algo_terms,
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


# ---------------------------------------------------------------------------
# The jit pipeline (module-level so the compile cache is shared by engines)
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("cfg", "terms", "use_kernels", "interpret", "n_servers",
                     "row_blocks"),
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
    cfg: RoutingConfig,
    terms: Terms,                 # the call's live terms (`Terms.live`): an
                                  # input is given exactly when its term is
    use_kernels: bool,
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
        if terms.failover:
            dm_server = dead_mask.astype(jnp.float32)
            if dm_server.ndim == 1:
                dm_server = dm_server[None, :]
            s_scores = jnp.where(dm_server > 0.0, NEG, s_scores)
        _, cand_servers = jax.lax.top_k(s_scores, min(cfg.top_s, n_servers))
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
            val = (bm25.bm25_scores(w_tool, q_rerank, row_blocks)
                   if terms.rerank else sel)

    # -- QoS N per tool (Eq. 6-7): Pallas kernel over the telemetry matrix --
    with jax.named_scope("qos"):
        if terms.network:
            if latency_hist.ndim == 3:                          # per-query windows
                n_q = latency_hist.shape[0]
                flat = latency_hist.reshape(n_q * n_servers, latency_hist.shape[-1])
                if use_kernels:
                    n_server = ops.qos_scores(flat, cfg.qos, interpret=interpret)
                else:
                    n_server = network_score(flat, cfg.qos)
                n_server = n_server.reshape(n_q, n_servers)
            else:
                if use_kernels:
                    n_server = ops.qos_scores(latency_hist, cfg.qos,
                                              interpret=interpret)
                else:
                    n_server = network_score(latency_hist, cfg.qos)
            # SONAR-FT staleness discount: elementwise per-server multiply
            # commutes with the per-tool gather below, so this matches the
            # scalar router's per-candidate discount bit-for-bit.
            if terms.staleness:
                n_server = n_server * staleness_discount(
                    telemetry_age, cfg.stale_half_life_s
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
                eff_alpha, eff_beta = cfg.alpha, cfg.beta
        else:
            tool_qos = jnp.zeros((n_tools,), jnp.float32)
            eff_alpha, eff_beta = 1.0, 0.0                      # S = C (scalar path)

    # -- SONAR-LB load term: per-server utilization penalty, broadcast to
    # tools of the host server (shared [n_servers] or per-query) --
    with jax.named_scope("candidates"):
        if terms.load:
            pen = load_penalty(server_load, cfg.load_knee, cfg.load_sharp)
            if server_load.ndim == 2:                           # [n_q, n_servers]
                tool_load = jnp.take(pen, tool_server, axis=1)  # [n_q, n_tools]
            else:
                tool_load = pen[tool_server]                    # [n_tools]
            eff_gamma = adapt_w[2] if adapt_w is not None else cfg.gamma
        else:
            tool_load = jnp.zeros((n_tools,), jnp.float32)
            eff_gamma = 0.0

        # -- SONAR-GEO locality term: per-(client-region, server) RTT penalty,
        # broadcast to tools of the host server.  The RTT arrives either as an
        # explicit vector (shared [n_servers] or per-query [n_q, n_servers]) or
        # as a per-request region index gathered from the [n_regions,
        # n_servers] RTT matrix — the gather runs inside the jit pipeline. --
        if terms.rtt:
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
            pen_r = rtt_penalty(client_rtt, cfg.rtt_scale_ms)
            if client_rtt.ndim == 2:                            # [n_q, n_servers]
                tool_rtt = jnp.take(pen_r, tool_server, axis=1)  # [n_q, n_tools]
            else:
                tool_rtt = pen_r[tool_server]                   # [n_tools]
            eff_delta = adapt_w[3] if adapt_w is not None else cfg.delta
        else:
            tool_rtt = jnp.zeros((n_tools,), jnp.float32)
            eff_delta = 0.0

        # -- SONAR-SESSION sticky-affinity bonus: per-(session, server) warmth
        # W in [0,1], broadcast to the host server's tools.  The warmth array
        # is *data* (eps alone is static), so per-request affinity changes
        # never recompile; when absent the term vanishes from the traced graph
        # and the compiled program is byte-identical to SONAR-GEO's. --
        if terms.affinity:
            if affinity.ndim == 2:                              # [n_q, n_servers]
                tool_aff = jnp.take(affinity, tool_server, axis=1)
            else:
                tool_aff = affinity[tool_server]                # [n_tools]
        else:
            tool_aff = None

        # -- SONAR-FT failed-server mask, broadcast to the host server's tools --
        if terms.failover:
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
    eps = cfg.eps if terms.affinity else 0.0
    with jax.named_scope("score_fuse"):
        if use_kernels:
            tool_idx, c, n, s = ops.fused_score_select(
                q_tool, w_tool, tool_server, cand_servers,
                tool_qos, tool_load, tool_dead,
                q_rerank if terms.rerank else None,
                k=cfg.top_k, alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
                tool_rtt=tool_rtt, delta=eff_delta,
                tool_aff=tool_aff, eps=eps,
                temp=cfg.expertise_temp, interpret=interpret,
            )
        else:
            tool_idx, c, n, s = kref.fused_select_ref(
                sel, val, tool_qos, tool_load, tool_dead,
                k=cfg.top_k, alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
                tool_rtt=tool_rtt, delta=eff_delta,
                tool_aff=tool_aff, eps=eps,
                temp=cfg.expertise_temp,
            )
    with jax.named_scope("select"):
        server_idx = tool_server[tool_idx]
    return server_idx, tool_idx, c, n, s


class RoutingEngineBase:
    """The host layer both routing engines share.

    `BatchRoutingEngine` (one device) and `mesh_routing.ShardedRoutingEngine`
    (the fleet axis in shards) keep their own jit programs; everything
    around them lives here: query encoding, the call's live terms
    (`Terms.live`), the SONAR-ADAPT learner, and `route` — the empty
    batch, the ``engine_phase_{upload,enqueue,readback}_ms`` histograms
    (host arrays to device operands, the jit call up to its asynchronous
    return, the host conversions of its outputs), the route stats and the
    readback into `BatchDecisions`.  An engine supplies `_program`: its
    jit program and that program's operands for one call.

    SONAR-ADAPT (lr != 0): `observe_feedback` queues each outcome; the
    next `route` folds the queue into the weights through the donated
    standalone `adaptive.adapt_update`, ``FEEDBACK_BUCKET`` outcomes a
    step, and the program takes the live weights as its ``adapt_w``
    operand.  With lr == 0 the weights can never leave their init, so the
    program is the static one, byte-identical to the hand-tuned
    variant's.
    """

    def __init__(self, index, cfg: RoutingConfig, algo: str,
                 use_kernels: Optional[bool], interpret: Optional[bool],
                 adapt: Optional[_adaptive.AdaptConfig],
                 registry: Optional[MetricsRegistry]):
        if use_kernels is None:
            # The Pallas kernels are the fast path on TPU; on CPU they run
            # in interpret mode (an emulator), where the argmax-identical
            # pure-jnp pipeline is ~8x faster — pick per backend.
            use_kernels = jax.default_backend() == "tpu"
        self.cfg = cfg
        self.algo = algo.lower().replace("-", "_")
        self.terms = algo_terms(self.algo)
        self.use_kernels = use_kernels
        self.interpret = interpret
        self.index = index
        # jnp BM25 scores the index's rows as its densified expansion's
        # rows score (`bm25.bm25_scores`)
        self._row_blocks = bool(getattr(index, "row_blocks", False))
        self.registry = registry if registry is not None else MetricsRegistry()
        self._m_phase = {
            ph: self.registry.histogram(f"engine_phase_{ph}_ms", "ms")
            for ph in ("upload", "enqueue", "readback")
        }
        # SONAR-ADAPT learner state (None for the hand-tuned algorithms)
        self.adapt_cfg: Optional[_adaptive.AdaptConfig] = None
        self.adapt_state: Optional[_adaptive.AdaptState] = None
        self._fb_rewards: list = []
        self._fb_feats: list = []
        if self.algo == "sonar_adapt" or adapt is not None:
            self.adapt_cfg = adapt if adapt is not None else _adaptive.AdaptConfig()
            self.adapt_state = _adaptive.init_state(cfg, self.adapt_cfg)

    # -- host side ----------------------------------------------------------
    def encode(self, queries: Sequence[str]) -> EncodedBatch:
        """Strings -> term-count matrices against the index's corpora: the
        only per-query Python in either engine, so both score
        byte-identical encodings.  ``q_server`` [n_q, V_server] and
        ``q_tool`` [n_q, V_tool] f32 term counts, after the deterministic
        LLM-preprocess stand-in (`predict_tool_type`) for the
        PRAG/SONAR family, and ``q_rerank`` [n_q, V_tool] (the canonical
        intents) for RerankRAG."""
        index, rerank = self.index, self.terms.rerank
        if self.terms.prediction:
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
        q_rerank = None
        if rerank:
            q_rerank = index.tool_corpus.encode_queries(
                [predict_tool_type(q)[1] for q in queries]
            )
        return EncodedBatch(
            q_server=index.server_corpus.encode_queries(qtexts),
            q_tool=index.tool_corpus.encode_queries(qtexts),
            q_rerank=q_rerank, n=len(queries),
        )

    def select_latency_ms(self) -> float:
        """Per-query SL with the same accounting as the scalar router."""
        sl = LLM_CALL_MS + 2 * BM25_STAGE_MS
        if self.terms.rerank:
            sl += LLM_RERANK_MS
        return sl

    def _live(self, latency_hist=None, server_load=None, telemetry_age_s=None,
              failed_mask=None, client_rtt_ms=None, client_region=None,
              region_rtt_ms=None, affinity=None, templates=False) -> Terms:
        """The terms this call carries (``templates``: telemetry comes in
        template-compact form)."""
        return self.terms.live(
            self.cfg,
            network=latency_hist is not None or templates,
            load=server_load is not None,
            staleness=telemetry_age_s is not None,
            failover=failed_mask is not None,
            rtt=client_rtt_ms is not None
            or (client_region is not None and region_rtt_ms is not None),
            affinity=affinity is not None,
        )

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
        vector by the next `route` call."""
        if self.adapt_state is None or feats is None:
            return
        self._fb_rewards.append(
            _adaptive.shape_reward(latency_ms, ok, self.adapt_cfg.slo_ms)
        )
        self._fb_feats.append(np.asarray(feats, np.float32))

    def _adapt_w(self, fold: bool) -> Optional[jax.Array]:
        """The ``adapt_w`` operand: the live weights after folding every
        pending outcome (``fold``), or None for the static program (no
        learner, or lr == 0)."""
        if self.adapt_state is None or self.adapt_cfg.lr == 0.0:
            return None
        B = _adaptive.FEEDBACK_BUCKET
        while fold and self._fb_rewards:
            r, f, v = _adaptive.pad_feedback(
                self._fb_rewards[:B], self._fb_feats[:B], B
            )
            self.adapt_state = _adaptive.adapt_update(
                self.adapt_state, r, f, v, self.adapt_cfg
            )
            del self._fb_rewards[:B]
            del self._fb_feats[:B]
        return self.adapt_state.weights

    # -- device side --------------------------------------------------------
    def _program(self, batch: EncodedBatch, *inputs, adapt_w=None, **kw):
        """(jit program, positional operands, keyword operands and
        statics) for one call on ``batch`` with `route`'s inputs."""
        raise NotImplementedError

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
        **extra,
    ) -> BatchDecisions:
        """Route an encoded batch through the engine's jit program.

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
            gathered *inside* the jit program (ignored when
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
            Jit-safe observability accumulator: the program's *device*
            outputs are folded into it by a donated jit `.at[].add`
            before any host conversion — one extra async dispatch, zero
            added syncs.  ``n_real`` (dynamic scalar) excludes trailing
            padded rows (the gateway's ``pad_to`` path) from the stats
            without specializing the compiled program per real count.
        **extra
            The engine's own keyword inputs (the sharded engine's
            ``telemetry_templates``).

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
        with obs_trace.annotate("engine.upload", self._m_phase["upload"]):
            fn, args, kw = self._program(
                batch, latency_hist, server_load, telemetry_age_s,
                failed_mask, client_rtt_ms, client_region, region_rtt_ms,
                affinity, adapt_w=self._adapt_w(fold=True), **extra,
            )
        with obs_trace.annotate("engine.enqueue", self._m_phase["enqueue"]):
            server_idx, tool_idx, c, n, s = fn(*args, **kw)
            if route_stats is not None:
                route_stats.accumulate(server_idx, c, n, s, n_real=n_real)
        with obs_trace.annotate("engine.readback", self._m_phase["readback"]):
            return BatchDecisions(
                server_idx=np.asarray(server_idx, np.int32),
                tool_idx=np.asarray(tool_idx, np.int32),
                expertise=np.asarray(c),
                network=np.asarray(n),
                fused=np.asarray(s),
                select_latency_ms=self.select_latency_ms(),
            )

    def lower(self, batch: EncodedBatch, *args, **kw) -> jax.stages.Lowered:
        """The program `route` would run on these inputs (same arguments,
        without ``route_stats``/``n_real``; pending feedback is not
        folded), lowered but not run.  Its ``.compile().as_text()`` names
        every Pallas kernel compiled into the route (``tpu_custom_call``
        on a TPU)."""
        fn, a, k = self._program(batch, *args,
                                 adapt_w=self._adapt_w(fold=False), **kw)
        return fn.lower(*a, **k)

    def route_texts(self, queries: Sequence[str], *args,
                    **kw) -> BatchDecisions:
        """`route` on the encoding of ``queries``."""
        return self.route(self.encode(queries), *args, **kw)


class BatchRoutingEngine(RoutingEngineBase):
    """Vectorized drop-in for a fleet of `Router.select` calls.

    One engine per (server pool, algorithm, config); `encode` turns query
    strings into term-count matrices on the host, `route` runs the full
    jit-compiled decision (`_route_pipeline`) for the batch and times its
    phases into ``registry`` (see `RoutingEngineBase`).  The gauge
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
        super().__init__(index if index is not None else ToolIndex(servers),
                         cfg, algo, use_kernels, interpret, adapt, registry)
        self._tool_server = jnp.asarray(self.index.tool_server)
        w_server = self.index.server_corpus.weights
        w_tool = self.index.tool_corpus.weights
        self.n_servers = int(w_server.shape[0])
        if self.use_kernels:
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
        self.registry.gauge("engine_corpus_pad_bytes_per_call",
                            "B").set(pad_bytes)

    def _program(
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
        *,
        adapt_w=None,
    ) -> tuple:
        """`_route_pipeline`, its operands and its statics for one call."""
        terms = self._live(latency_hist, server_load, telemetry_age_s,
                           failed_mask, client_rtt_ms, client_region,
                           region_rtt_ms, affinity)

        def f32(x, live):
            return jnp.asarray(x, jnp.float32) if live else None

        rtt = reg_idx = reg_rtt = None
        if terms.rtt and client_rtt_ms is not None:
            rtt = jnp.asarray(client_rtt_ms, jnp.float32)
        elif terms.rtt:
            reg_idx = jnp.asarray(client_region, jnp.int32)
            reg_rtt = jnp.asarray(region_rtt_ms, jnp.float32)
        operands = (
            jnp.asarray(batch.q_server),
            jnp.asarray(batch.q_tool),
            jnp.asarray(batch.q_rerank)
            if batch.q_rerank is not None else None,
            self._w_server,
            self._w_tool,
            self._tool_server,
            f32(latency_hist, terms.network),
            f32(server_load, terms.load),
            f32(telemetry_age_s, terms.staleness),
            f32(failed_mask, terms.failover),
            rtt,
            reg_idx,
            reg_rtt,
            f32(affinity, terms.affinity),
            adapt_w,
        )
        statics = dict(
            cfg=self.cfg, terms=terms, use_kernels=self.use_kernels,
            interpret=self.interpret, n_servers=self.n_servers,
            row_blocks=self._row_blocks,
        )
        return _route_pipeline, operands, statics

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
