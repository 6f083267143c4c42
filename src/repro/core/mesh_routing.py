"""Mesh-sharded SONAR routing engine (the fleet axis distributed over devices).

`BatchRoutingEngine` runs the whole routing decision on one device, which
caps realistic fleets at ~10^3 servers.  This module partitions the
**server axis** (and the tool axis, which is grouped by host server) across
a 1-D jax device mesh (`launch.mesh.make_fleet_mesh`, axis ``"fleet"``) and
runs a hierarchical two-stage selection:

  1. each shard scores its server slice (stage-1 BM25) and extracts its
     local top-``min(top_s, S_shard)`` servers;
  2. a small all-gather merges the per-shard winners; every device takes
     the same global top-s candidate set (Eq. 2);
  3. each shard scores its tool slice (stage-2 BM25), masks tools outside
     the candidate servers, computes its local QoS / load / staleness /
     dead terms over its telemetry slice, and extracts its local
     top-``min(top_k, T_shard)`` candidate tools with their metadata;
  4. a second all-gather merges the per-shard candidate lists and the
     fused softmax-expertise + QoS-fusion + argmax tail (the Pallas
     ``select_fuse`` kernel, or its jnp oracle) runs on the merged set.

Selection parity: the result is **bit-identical** to the single-device
engine for every algorithm.  The global top-k is always a subset of the
union of the per-shard top-ks, and the merge preserves the single-device
tie-break order: per-shard candidate lists are value-sorted with ties
broken toward the lower (local == global, shards are contiguous) index,
and lists are concatenated in shard order, so "first max" over the merged
axis is "lowest global index" over the full axis — exactly
``lax.top_k``'s tie rule.  Because the final candidate values arrive in
the same order as the single-device extraction, the Eq. 5 softmax
reduction runs over the same floats in the same order, and the fused
scores (Eq. 8) and argmax (Eq. 9) are reproduced bit-for-bit.
``tests/test_mesh_routing.py`` property-tests the argmax identity across
all registered algorithms, and ``benchmarks/mega_fleet.py`` gates on it at 10^5+
servers.  One carve-out: SONAR-GEO's active ``-delta*R`` term extends the
fusion to four products, which XLA may FMA-contract differently in the
two independently-compiled programs — its fused *score* is reproduced to
~1 ulp (decisions remain argmax-identical; bit-identical candidate inputs
contract identically, so exact ties still break the same way).  All other
algorithms keep full bit-identity (``delta`` folds to zero).

Shard padding uses ``PAD_NEG`` (strictly below the ``NEG`` mask value), so
pad servers/tools rank below every real entry — including dead-demoted
ones — and never perturb the merge.

Mega fleets (10^5-10^6 servers) use a `TiledFleetIndex`: servers are
instances of a small set of template servers, BM25 weights are stored once
per template (corpus statistics computed over the *expanded* fleet) and
per-shard scores are gathered from one small template matmul instead of a
fleet-sized one.  On the kernel path stage 1 does not gather them: each
shard streams the [n_q, M] template scores over its replica -> template
map through a running top-s (`ops.stage1_select`), bit-identical to the
gather plus ``lax.top_k`` the jnp path runs, without that row or its
sort.  Telemetry can likewise stay compact: ``route`` accepts
``telemetry_templates=(compact [M, T], template_map [n_servers])`` and
computes QoS per template row, then gathers per server — identical scores
(identical rows), no [n_servers, T] densification anywhere.

With a multi-device mesh the per-shard stages run under ``shard_map``;
without one (the CPU-test default) the same stage functions run on the
shard-stacked arrays directly, so the emulated and distributed paths share
every line of math.

`ShardedRoutingEngine` shares its host layer with `BatchRoutingEngine`
(`batch_routing.RoutingEngineBase`): encoding, the timed `route`, and the
call's live terms (`routing.Terms.live`), which decide the key set of the
program's ``dyn`` operands and enter the static `_StaticCfg` beside the
`RoutingConfig`.  SONAR-ADAPT's weights are updated once per route by the
standalone `adaptive.adapt_update` and enter as the replicated
``adapt_w`` operand, as they enter `BatchRoutingEngine`'s program.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.core import adaptive as _adaptive
from repro.core import bm25, quantize
from repro.core.batch_routing import EncodedBatch, RoutingEngineBase
from repro.core.dataset import Server
from repro.core.qos import (
    load_penalty,
    network_score,
    rtt_penalty,
    staleness_discount,
)
from repro.core.routing import RoutingConfig, Terms, ToolIndex
from repro.kernels import ops
from repro.kernels import ref as kref
from repro.kernels.stage1_select import MAX_TEMPLATES
from repro.kernels.stage1_select import PAD_NEG  # below every real score
from repro.obs import trace as obs_trace

NEG = kref.NEG


# ---------------------------------------------------------------------------
# Tiled index for mega fleets
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class _DenseIndexView:
    """ToolIndex-compatible view over densified tiled weights (feeds the
    single-device `BatchRoutingEngine` in parity gates)."""

    server_corpus: bm25.Bm25Corpus
    tool_corpus: bm25.Bm25Corpus
    tool_server: np.ndarray
    n_tools: int

    # score its rows as the tiled index scores its template rows (see
    # `bm25.bm25_scores`)
    row_blocks = True


class TiledFleetIndex:
    """Template-tiled two-level BM25 index for 10^5-10^6-server fleets.

    Parameters
    ----------
    templates : Sequence[Server]
        The distinct server templates (descriptions + tools).
    server_template : np.ndarray
        int [n_servers] — template id of each fleet server.  Tools of
        server ``i`` are its template's tools, in template order, so the
        global tool axis stays grouped by host server (ascending), which
        the shard plan requires.
    weights_dtype : str
        Storage dtype of the template BM25 weights: ``"float32"`` (exact),
        ``"bfloat16"`` (weights rounded once to the nearest bf16 at build
        time) or ``"int8"`` (symmetric per-template-doc scales).  Rounding
        happens HERE, before any path consumes the index, so the scalar
        oracle, the batched engine, the Pallas kernels and the sharded
        engine all score the *identical* rounded operands and stay
        argmax-identical to each other by construction (the documented
        quantization carve-out in docs/benchmarks.md).  ``densify()``
        gathers from the already-rounded rows and therefore inherits the
        exact same values.

    BM25 corpus statistics (IDF, average doc length) are computed as if
    every template doc were replicated its multiplicity — scoring against
    the template weights row-equals scoring against the expanded corpus.
    ``densify()`` materializes the expanded weights for single-device
    parity runs; routing at scale never does.
    """

    is_tiled = True
    # jnp BM25 scores each template row as its densified expansion's rows
    # score (`bm25.bm25_scores`)
    row_blocks = True

    def __init__(
        self,
        templates: Sequence[Server],
        server_template: np.ndarray,
        weights_dtype: str = "float32",
    ):
        self.templates = list(templates)
        stpl = np.asarray(server_template, np.int64)
        assert stpl.min() >= 0 and stpl.max() < len(self.templates)
        self.n_servers = int(stpl.size)
        self.server_doc_map = stpl.astype(np.int32)
        counts = np.bincount(stpl, minlength=len(self.templates))
        self.server_corpus = bm25.build_corpus_tiled(
            [s.description for s in self.templates], counts
        )

        tool_docs, tool_tpl = [], []
        for mi, s in enumerate(self.templates):
            for t in s.tools:
                tool_docs.append(f"{t.name.replace('_', ' ')} {t.description}")
                tool_tpl.append(mi)
        tool_tpl = np.asarray(tool_tpl, np.int64)
        tools_per_tpl = np.bincount(tool_tpl, minlength=len(self.templates))
        self.tool_corpus = bm25.build_corpus_tiled(
            tool_docs, counts[tool_tpl]
        )

        n_per_server = tools_per_tpl[stpl]                     # [n_servers]
        self.n_tools = int(n_per_server.sum())
        self.tool_server = np.repeat(
            np.arange(self.n_servers), n_per_server
        ).astype(np.int32)
        # doc id of each fleet tool: template's first tool doc + offset
        doc0 = np.concatenate([[0], np.cumsum(tools_per_tpl)])[:-1]
        starts = np.cumsum(n_per_server) - n_per_server
        within = np.arange(self.n_tools) - np.repeat(starts, n_per_server)
        self.tool_doc_map = (
            np.repeat(doc0[stpl], n_per_server) + within
        ).astype(np.int32)

        # one-time operand rounding (quantized storage contract): every
        # consumer — template matmuls and densified parity views alike —
        # sees the same rounded weights, so decisions cannot diverge
        # across routing paths because of the storage dtype.
        self.weights_dtype = weights_dtype
        if weights_dtype not in ("float32", "f32"):
            self.server_corpus = bm25.Bm25Corpus(
                vocab=self.server_corpus.vocab,
                weights=quantize.round_weights(
                    self.server_corpus.weights, weights_dtype
                ),
                n_docs=self.server_corpus.n_docs,
            )
            self.tool_corpus = bm25.Bm25Corpus(
                vocab=self.tool_corpus.vocab,
                weights=quantize.round_weights(
                    self.tool_corpus.weights, weights_dtype
                ),
                n_docs=self.tool_corpus.n_docs,
            )

    def server_scores(self, qtext: str) -> np.ndarray:
        """[n_servers] stage-1 BM25 scores of one query, for the scalar
        `Router` (per template row, then gathered: every row is reduced
        on its own, so each equals its expanded row's `ToolIndex` score)."""
        q = self.server_corpus.encode_query(qtext)
        return ToolIndex._row_scores(self.server_corpus.weights, q)[
            self.server_doc_map]

    def tool_scores(self, qtext: str) -> np.ndarray:
        """[n_tools] stage-2 BM25 scores of one query (see
        `server_scores`)."""
        q = self.tool_corpus.encode_query(qtext)
        return ToolIndex._row_scores(self.tool_corpus.weights, q)[
            self.tool_doc_map]

    def densify(self) -> _DenseIndexView:
        """Expanded-weights view (for the single-device parity engine)."""
        sc = bm25.Bm25Corpus(
            vocab=self.server_corpus.vocab,
            weights=self.server_corpus.weights[self.server_doc_map],
            n_docs=self.n_servers,
        )
        tc = bm25.Bm25Corpus(
            vocab=self.tool_corpus.vocab,
            weights=self.tool_corpus.weights[self.tool_doc_map],
            n_docs=self.n_tools,
        )
        return _DenseIndexView(
            server_corpus=sc, tool_corpus=tc,
            tool_server=self.tool_server, n_tools=self.n_tools,
        )


# ---------------------------------------------------------------------------
# Shard plan (host-side, built once per engine)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class ShardPlan:
    """Static partition of the server/tool axes into `n_shards` slices.

    Servers are split contiguously ([j*s_pad, (j+1)*s_pad)); each shard's
    tools are the (contiguous, because `tool_server` is non-decreasing)
    block hosted on its servers.  Both axes are padded to a common
    per-shard size; pad entries carry valid=False and score `PAD_NEG`.
    """

    n_shards: int
    s_pad: int                    # servers per shard (padded)
    t_pad: int                    # tools per shard (padded)
    server_gid: np.ndarray        # [J, s_pad] i32 global server id (clipped)
    server_valid: np.ndarray      # [J, s_pad] bool
    tool_gid: np.ndarray          # [J, t_pad] i32 global tool id (clipped)
    tool_valid: np.ndarray        # [J, t_pad] bool
    tool_host_global: np.ndarray  # [J, t_pad] i32 host server (global)
    tool_host_local: np.ndarray   # [J, t_pad] i32 host row in shard slice


def make_shard_plan(
    tool_server: np.ndarray, n_servers: int, n_shards: int
) -> ShardPlan:
    tool_server = np.asarray(tool_server, np.int64)
    assert np.all(np.diff(tool_server) >= 0), "tools must be grouped by server"
    n_shards = max(1, min(int(n_shards), int(n_servers)))
    s_pad = -(-n_servers // n_shards)
    j = np.arange(n_shards)
    gid = j[:, None] * s_pad + np.arange(s_pad)[None, :]
    server_valid = gid < n_servers
    server_gid = np.minimum(gid, n_servers - 1).astype(np.int32)

    t_lo = np.searchsorted(tool_server, j * s_pad, side="left")
    t_hi = np.searchsorted(
        tool_server, np.minimum((j + 1) * s_pad, n_servers), side="left"
    )
    t_pad = max(int((t_hi - t_lo).max()), 1)
    tg = t_lo[:, None] + np.arange(t_pad)[None, :]
    tool_valid = tg < t_hi[:, None]
    tool_gid = np.minimum(tg, len(tool_server) - 1).astype(np.int32)
    tool_host_global = tool_server[tool_gid].astype(np.int32)
    tool_host_local = np.clip(
        tool_host_global - (j * s_pad)[:, None], 0, s_pad - 1
    ).astype(np.int32)
    return ShardPlan(
        n_shards=n_shards, s_pad=int(s_pad), t_pad=int(t_pad),
        server_gid=server_gid, server_valid=server_valid,
        tool_gid=tool_gid, tool_valid=tool_valid,
        tool_host_global=tool_host_global, tool_host_local=tool_host_local,
    )


# ---------------------------------------------------------------------------
# Static (hashable) pipeline configuration
# ---------------------------------------------------------------------------

class _StaticCfg(NamedTuple):
    cfg: RoutingConfig
    terms: Terms                  # the call's live terms (`Terms.live`)
    n_shards: int
    n_servers: int
    n_tools: int
    s_keep: int                   # per-shard stage-1 candidates
    k_keep: int                   # per-shard stage-2 candidates
    use_kernels: bool
    interpret: Optional[bool]
    # compacted candidate stage-2 (tiled mega fleets): score only the
    # ≤ top_s * k_slot tools hosted on candidate servers instead of
    # running shard-local top-k over the full tool axis
    compact2: bool = False
    k_slot: int = 0               # max tools hosted on any one server
    # jnp BM25 in fixed row blocks (`bm25.bm25_scores`): the index's rows
    # score as its densified expansion's do
    row_blocks: bool = False


# ---------------------------------------------------------------------------
# Per-shard stages.  Every function takes shard-stacked arrays [J, ...]; the
# emulated path calls them with the full stack, the mesh path calls them
# under shard_map with J=1 blocks — one implementation, two executions.
# ---------------------------------------------------------------------------

def _bm25_2d(q: jax.Array, w: jax.Array, sc: _StaticCfg) -> jax.Array:
    if sc.use_kernels:
        return ops.bm25_scores(q, w, interpret=sc.interpret)
    return bm25.bm25_scores(w, q, row_blocks=sc.row_blocks)


def _qos_2d(lat: jax.Array, sc: _StaticCfg) -> jax.Array:
    if sc.use_kernels:
        return ops.qos_scores(lat, sc.cfg.qos, interpret=sc.interpret)
    return network_score(lat, sc.cfg.qos)


def _stage1_streams(use_kernels: bool, n_templates: int) -> bool:
    """Whether stage 1 of a tiled index streams the template scores
    through `ops.stage1_select`: on the kernel path, whenever they fit its
    block."""
    return use_kernels and n_templates <= MAX_TEMPLATES


def _stage1_stacked(d: dict, sc: _StaticCfg) -> tuple:
    """Shard-local stage 1: server scores + local top-s.

    Returns (values [J, n_q, s_keep], global server ids [J, n_q, s_keep]).
    """
    if "s_full" in d:
        # tiled, kernel path: the template scores [n_q, M] streamed over
        # the shard's replica map — no [n_q, s_pad] row and no sort
        dead = d["dead"] if sc.terms.failover else None
        v, li = ops.stage1_select(d["s_full"], d["server_doc_map"], dead,
                                  k=sc.s_keep, interpret=sc.interpret)
    else:
        if "s_pre" in d:
            s = d["s_pre"]                               # [J, n_q, s_pad]
        else:
            w = d["w_server"]                            # [J, s_pad, V]
            if sc.use_kernels or sc.row_blocks:
                J, S, V = w.shape
                s = _bm25_2d(d["q_server"], w.reshape(J * S, V), sc)
                s = s.reshape(-1, J, S).transpose(1, 0, 2)
            else:
                s = jnp.einsum("qv,jsv->jqs", d["q_server"], w,
                               precision=bm25.HIGHEST)
        if sc.terms.failover:
            s = jnp.where(d["dead"] > 0.0, NEG, s)       # [J, B, s_pad] bcast
        s = jnp.where(d["server_valid"][:, None, :], s, PAD_NEG)
        v, li = jax.lax.top_k(s, sc.s_keep)              # [J, n_q, s_keep]
    # the ids of the kept replicas, read without broadcasting the map
    gid = jnp.take_along_axis(d["server_gid"][:, None, :], li, axis=-1)
    return v, gid


def _stage2_stacked(d: dict, cand_gids: jax.Array, sc: _StaticCfg) -> tuple:
    """Shard-local stage 2: tool scores masked to the global candidate
    servers, QoS/load/staleness/RTT/dead terms over the shard's telemetry
    slice, local top-k extraction with metadata.

    Returns eight [J, n_q, k_keep] arrays:
    (sel, val, qos, load, rtt, dead, aff, gid).
    """
    if "t_pre" in d:
        t = d["t_pre"]                                   # [J, n_q, t_pad]
    else:
        w = d["w_tool"]                                  # [J, t_pad, V]
        if sc.use_kernels or sc.row_blocks:
            J, T, V = w.shape
            t = _bm25_2d(d["q_tool"], w.reshape(J * T, V), sc)
            t = t.reshape(-1, J, T).transpose(1, 0, 2)
        else:
            t = jnp.einsum("qv,jtv->jqt", d["q_tool"], w,
                           precision=bm25.HIGHEST)
    J, n_q, t_pad = t.shape

    in_cand = jnp.any(
        d["tool_host_global"][:, None, :, None]
        == cand_gids[None, :, None, :],
        axis=-1,
    )                                                     # [J, n_q, t_pad]
    sel = jnp.where(in_cand, t, NEG)
    sel = jnp.where(d["tool_valid"][:, None, :], sel, PAD_NEG)

    if sc.terms.rerank:
        if "val_pre" in d:
            val_full = d["val_pre"]
        elif sc.use_kernels or sc.row_blocks:
            w = d["w_tool"]
            val_full = _bm25_2d(
                d["q_rerank"], w.reshape(J * t_pad, -1), sc
            ).reshape(-1, J, t_pad).transpose(1, 0, 2)
        else:
            val_full = jnp.einsum("qv,jtv->jqt", d["q_rerank"], d["w_tool"],
                                  precision=bm25.HIGHEST)
    else:
        val_full = sel

    host_l = d["tool_host_local"]                         # [J, t_pad]

    def per_tool(per_server):                             # [J, B, s_pad] ->
        B = per_server.shape[1]                           # [J, B, t_pad]
        idx = jnp.broadcast_to(host_l[:, None, :], (J, B, t_pad))
        return jnp.take_along_axis(per_server, idx, axis=-1)

    if sc.terms.network:
        if "qos_pre" in d:
            n_server = d["qos_pre"]                       # [J, B, s_pad]
        elif d["lat"].ndim == 4:                          # per-query windows
            Jl, B, S, T = d["lat"].shape
            n_server = _qos_2d(d["lat"].reshape(Jl * B * S, T), sc)
            n_server = n_server.reshape(Jl, B, S)
        else:                                             # shared snapshot
            Jl, S, T = d["lat"].shape
            n_server = _qos_2d(d["lat"].reshape(Jl * S, T), sc)
            n_server = n_server.reshape(Jl, 1, S)
        if sc.terms.staleness:
            n_server = n_server * staleness_discount(
                d["age"], sc.cfg.stale_half_life_s
            )
        tool_qos = per_tool(n_server)
    else:
        tool_qos = jnp.zeros((J, 1, t_pad), jnp.float32)

    if sc.terms.load:
        pen = load_penalty(d["load"], sc.cfg.load_knee, sc.cfg.load_sharp)
        tool_load = per_tool(pen)
    else:
        tool_load = jnp.zeros((J, 1, t_pad), jnp.float32)

    # SONAR-GEO: client-region -> server RTT penalty over the shard's
    # server slice, as an explicit vector or gathered from the sharded
    # [J, n_regions, s_pad] RTT matrix by the replicated region indices
    if sc.terms.rtt:
        if "rtt_region" in d:
            # clamp the gather and zero untagged (region < 0) requests'
            # rows — no locality penalty, matching the scalar convention
            ridx = d["region_idx"]
            rtt_s = jnp.take(
                d["rtt_region"], jnp.maximum(ridx, 0), axis=1
            )                                             # [J, B, s_pad]
            rtt_s = jnp.where((ridx >= 0)[None, :, None], rtt_s, 0.0)
        else:
            rtt_s = d["rtt"]                              # [J, 1|B, s_pad]
        tool_rtt = per_tool(rtt_penalty(rtt_s, sc.cfg.rtt_scale_ms))
    else:
        tool_rtt = jnp.zeros((J, 1, t_pad), jnp.float32)

    if sc.terms.failover:
        tool_dead = per_tool(d["dead"])
    else:
        tool_dead = jnp.zeros((J, 1, t_pad), jnp.float32)

    # SONAR-SESSION: per-(session, server) warmth over the shard's server
    # slice, broadcast to the host server's tools like load/dead
    if sc.terms.affinity:
        tool_aff = per_tool(d["aff"])
    else:
        tool_aff = jnp.zeros((J, 1, t_pad), jnp.float32)

    v, li = jax.lax.top_k(sel, sc.k_keep)                 # [J, n_q, k_keep]

    def gather(x):                                        # [J, B, t_pad]
        x = jnp.broadcast_to(x, (J, n_q, t_pad))
        return jnp.take_along_axis(x, li, axis=-1)

    gid = jnp.take_along_axis(
        jnp.broadcast_to(d["tool_gid"][:, None, :], (J, n_q, t_pad)),
        li, axis=-1,
    )
    return v, gather(val_full), gather(tool_qos), gather(tool_load), \
        gather(tool_rtt), gather(tool_dead), gather(tool_aff), gid


def _gflat(x: jax.Array) -> jax.Array:
    """[J, B, s_pad] -> [B, J*s_pad]; columns land in global server-id
    order because shard slices are contiguous ([j*s_pad, (j+1)*s_pad))."""
    J, B, S = x.shape
    return jnp.transpose(x, (1, 0, 2)).reshape(B, J * S)


def _stage2_compact(
    d: dict, t_full: jax.Array, v_full, nt, cand_gids: jax.Array,
    sc: _StaticCfg, kmesh: Optional[Mesh] = None,
) -> tuple:
    """Candidate-compacted stage 2 for tiled mega fleets.

    Instead of scoring/masking/top-k'ing the full tool axis (the
    dominant cost at 10^5+ servers: the mask and ``lax.top_k`` are both
    O(n_tools)), expand only the tools hosted on the ≤ top_s candidate
    servers: candidate server ids are sorted ascending and each expands
    ``k_slot`` slots (global tool id = server's first tool + slot; pad
    slots beyond the server's tool count carry ``NEG`` and gid 0).

    Parity with the full stage-2 + merge (and hence with the
    single-device engine): the compacted axis lists candidate tools in
    ascending-global-id order (ascending candidate gids × per-server
    tool blocks contiguous and ascending), so ``lax.top_k``'s
    first-max-wins tie rule resolves to the lowest global tool id —
    exactly the full-axis order.  All candidate-tool values (BM25 sel,
    rerank val, QoS, load, RTT, dead) are gathered from the same
    replicated template scores / per-server vectors the full path uses,
    so the downstream softmax + fusion runs over identical floats in
    identical order.  Requires every server to host ≥ 1 tool and
    ``n_servers >= top_s`` (no pad/duplicate candidates) — the engine
    falls back to the full stage-2 otherwise.

    Returns eight flattened [n_q, W] arrays (sel, val, qos, load, rtt,
    dead, aff, gid) with ``W = top_s_eff * k_slot`` (padded up to the
    final top-k width so the merge semantics match the full path).
    ``kmesh`` is the mesh the QoS kernel call is replicated over.
    """
    n_q = t_full.shape[0]
    m_docs = t_full.shape[1]
    cand = jnp.sort(cand_gids, axis=-1).astype(jnp.int32)  # [n_q, S] asc
    S = cand.shape[1]
    K = sc.k_slot
    start = jnp.take(d["tool_start_g"], cand)              # [n_q, S]
    count = jnp.take(d["tool_count_g"], cand)
    doc0 = jnp.take(d["tool_doc0_g"], cand)
    slot = jnp.arange(K, dtype=jnp.int32)
    ok3 = slot[None, None, :] < count[:, :, None]          # [n_q, S, K]
    gid3 = jnp.where(ok3, start[:, :, None] + slot[None, None, :], 0)
    doc3 = jnp.clip(doc0[:, :, None] + slot[None, None, :], 0, m_docs - 1)
    W = S * K
    ok = ok3.reshape(n_q, W)
    gid = gid3.reshape(n_q, W)
    doc = doc3.reshape(n_q, W)

    sel = jnp.where(ok, jnp.take_along_axis(t_full, doc, axis=1), NEG)
    if sc.terms.rerank:
        val = jnp.where(ok, jnp.take_along_axis(v_full, doc, axis=1), NEG)
    else:
        val = sel

    def gath(x):                                           # [J, B, s_pad]
        f = _gflat(x)                                      # -> [n_q, S]
        if f.shape[0] == 1:
            return f[0][cand]
        return jnp.take_along_axis(f, cand, axis=1)

    def expand(x):                                         # [n_q, S] ->
        return jnp.broadcast_to(                           # [n_q, W]
            x[:, :, None], (n_q, S, K)
        ).reshape(n_q, W)

    qos_fn = functools.partial(_qos_2d, sc=sc)
    if sc.terms.network:
        if nt is not None:                                 # template QoS
            tmf = d["tel_map"].reshape(-1)                 # [J*s_pad]
            qos_s = jnp.take(nt, jnp.take(tmf, cand))      # [n_q, S]
        elif d["lat"].ndim == 4:                           # per-query hist
            J, B, Sp, T = d["lat"].shape
            flat = jnp.transpose(d["lat"], (1, 0, 2, 3)).reshape(B, J * Sp, T)
            rows = jnp.take_along_axis(
                flat, cand[:, :, None], axis=1
            )                                              # [n_q, S, T]
            qos_s = _replicated(qos_fn, kmesh)(
                rows.reshape(n_q * S, T)
            ).reshape(n_q, S)
        else:                                              # shared snapshot
            J, Sp, T = d["lat"].shape
            rows = d["lat"].reshape(J * Sp, T)[cand.reshape(-1)]
            qos_s = _replicated(qos_fn, kmesh)(rows).reshape(n_q, S)
        if sc.terms.staleness:
            qos_s = qos_s * staleness_discount(gath(d["age"]),
                                               sc.cfg.stale_half_life_s)
        qos = expand(qos_s)
    else:
        qos = jnp.zeros((n_q, W), jnp.float32)

    if sc.terms.load:
        load = expand(load_penalty(gath(d["load"]), sc.cfg.load_knee,
                                   sc.cfg.load_sharp))
    else:
        load = jnp.zeros((n_q, W), jnp.float32)

    if sc.terms.rtt:
        if "rtt_region" in d:
            ridx = d["region_idx"]
            rr = jnp.transpose(d["rtt_region"], (1, 0, 2))  # [R, J, s_pad]
            rr = rr.reshape(rr.shape[0], -1)                # [R, J*s_pad]
            rows = jnp.take(rr, jnp.maximum(ridx, 0), axis=0)  # [n_q, J*s_pad]
            rtt_s = jnp.take_along_axis(rows, cand, axis=1)
            rtt_s = jnp.where((ridx >= 0)[:, None], rtt_s, 0.0)
        else:
            rtt_s = gath(d["rtt"])
        rtt = expand(rtt_penalty(rtt_s, sc.cfg.rtt_scale_ms))
    else:
        rtt = jnp.zeros((n_q, W), jnp.float32)

    if sc.terms.failover:
        dead = expand(gath(d["dead"]))
    else:
        dead = jnp.zeros((n_q, W), jnp.float32)

    if sc.terms.affinity:
        aff = expand(gath(d["aff"]))
    else:
        aff = jnp.zeros((n_q, W), jnp.float32)

    k_final = min(sc.cfg.top_k, sc.n_tools)
    if W < k_final:                                        # keep the merge
        pad = k_final - W                                  # k identical to
        sel = jnp.pad(sel, ((0, 0), (0, pad)), constant_values=NEG)
        val = jnp.pad(val, ((0, 0), (0, pad)), constant_values=NEG)
        qos = jnp.pad(qos, ((0, 0), (0, pad)))
        load = jnp.pad(load, ((0, 0), (0, pad)))
        rtt = jnp.pad(rtt, ((0, 0), (0, pad)))
        dead = jnp.pad(dead, ((0, 0), (0, pad)))
        aff = jnp.pad(aff, ((0, 0), (0, pad)))
        gid = jnp.pad(gid, ((0, 0), (0, pad)))
    return sel, val, qos, load, rtt, dead, aff, gid


def _packed(stage_fn, layout: tuple, sc: _StaticCfg, *extra):
    """Positional-args adapter so optional inputs can run under shard_map
    (which needs one PartitionSpec per positional argument)."""

    def fn(*arrays):
        return stage_fn(dict(zip(layout, arrays)), *extra, sc)

    return fn


# Logical-axis sharding rules (resolved through nn.sharding.logical_to_spec,
# which enforces the single-use and divisibility invariants): "shard" is the
# only sharded logical dim, mapped onto the 1-D "fleet" mesh axis; every
# other dim replicates.
FLEET_RULES = {"shard": ("fleet",)}


def _specs_for(mesh: Mesh, layouts, arrays):
    from repro.nn.sharding import logical_to_spec

    return tuple(
        logical_to_spec(names, a.shape, mesh, FLEET_RULES)
        for names, a in zip(layouts, arrays)
    )


def _run_stage(fn, mesh: Optional[Mesh], arrays, layouts, n_out: int):
    """Run a per-shard stage: directly on the shard-stacked arrays (no
    mesh), or under shard_map with specs derived from the logical layouts
    (a real mesh).  `layouts` holds one tuple of logical dim names per
    array, e.g. ("shard", None, None)."""
    if mesh is None:
        return fn(*arrays)
    from repro.nn.sharding import logical_to_spec

    out_spec = logical_to_spec(
        ("shard", None, None), (mesh.devices.size, 1, 1), mesh, FLEET_RULES
    )
    return jax.shard_map(
        fn, mesh=mesh, in_specs=_specs_for(mesh, layouts, arrays),
        out_specs=tuple([out_spec] * n_out), check_vma=False,
    )(*arrays)


def _replicated(fn, mesh: Optional[Mesh]):
    """``fn`` run once per device on replicated operands.  XLA cannot
    partition a Mosaic kernel, so on a real mesh each kernel call outside
    the per-shard stages goes through a fully replicated shard_map (every
    device computes the same small result); without a mesh, ``fn`` as is."""
    if mesh is None:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=P(), out_specs=P(),
                         check_vma=False)


def _flatten_shards(x: jax.Array) -> jax.Array:
    """[J, n_q, K] -> [n_q, J*K], shard blocks in shard (= global) order."""
    J, n_q, K = x.shape
    return jnp.transpose(x, (1, 0, 2)).reshape(n_q, J * K)


# ---------------------------------------------------------------------------
# The jit pipeline
# ---------------------------------------------------------------------------

# logical layouts (dim names fed to nn.sharding.logical_to_spec)
_REP1 = (None,)
_REP2 = (None, None)
_SH2 = ("shard", None)
_SH3 = ("shard", None, None)
_SH4 = ("shard", None, None, None)


@functools.partial(jax.jit, static_argnames=("mesh", "sc"))
def _route_sharded(dyn: dict, *, mesh: Optional[Mesh], sc: _StaticCfg):
    """Hierarchical sharded routing.  The `dyn` key set follows the input
    mode (dense vs tiled weights/telemetry) and the call's live terms
    (``sc.terms``: an optional vector is there exactly when its term is)
    — a different key set is a different pytree structure, so jit
    re-traces exactly when the mode changes."""
    # -- tiled template scoring (replicated small matmuls + gathers) --
    # Quantized storage: template weights may live in bf16 on device; the
    # upcast to f32 is exact (bf16 ⊂ f32), so scoring matches scoring the
    # rounded-f32 weights bit-for-bit.  All accumulation stays f32.
    compact2 = sc.compact2 and "tool_doc_map" in dyn
    # kernel calls outside the per-shard stages need a replicated
    # shard_map on a real mesh (the jnp path keeps XLA's own partitioning)
    kmesh = mesh if sc.use_kernels else None
    bm25_fn = _replicated(functools.partial(_bm25_2d, sc=sc), kmesh)
    pre: dict = {}
    t_full = v_full = nt = None
    if "server_doc_map" in dyn:
        w_server_t = dyn["w_server_t"].astype(jnp.float32)
        s_full = bm25_fn(dyn["q_server"], w_server_t)      # [n_q, M]
        if _stage1_streams(sc.use_kernels, s_full.shape[1]):
            pre["s_full"] = s_full
        else:
            # the map's pad replicas (-1) score PAD_NEG in stage 1
            s_pad = dyn["server_gid"].shape[1]
            pre["s_pre"] = jnp.transpose(
                jnp.take(s_full, dyn["server_doc_map"][:, 0, :s_pad],
                         axis=1, mode="clip"), (1, 0, 2)
            )
    if "tool_doc_map" in dyn:
        w_tool_t = dyn["w_tool_t"].astype(jnp.float32)
        t_full = bm25_fn(dyn["q_tool"], w_tool_t)
        if sc.terms.rerank:
            v_full = bm25_fn(dyn["q_rerank"], w_tool_t)
        if not compact2:
            pre["t_pre"] = jnp.transpose(
                jnp.take(t_full, dyn["tool_doc_map"], axis=1), (1, 0, 2)
            )
            if sc.terms.rerank:
                pre["val_pre"] = jnp.transpose(
                    jnp.take(v_full, dyn["tool_doc_map"], axis=1), (1, 0, 2)
                )
    if "lat_t" in dyn:
        nt = _replicated(functools.partial(_qos_2d, sc=sc), kmesh)(
            dyn["lat_t"].astype(jnp.float32)
        )                                                     # [M_t]
        if not compact2:
            pre["qos_pre"] = jnp.transpose(
                jnp.take(nt[None, :], dyn["tel_map"], axis=1), (1, 0, 2)
            )

    # -- stage 1: shard-local server top-s --
    layout1, specs1 = [], []

    def add1(name, spec):
        if pre.get(name, dyn.get(name)) is not None:
            layout1.append(name)
            specs1.append(spec)

    if "s_full" in pre:
        add1("s_full", _REP2)
        add1("server_doc_map", _SH3)
    elif "s_pre" in pre:
        add1("s_pre", _SH3)
    else:
        add1("q_server", _REP2)
        add1("w_server", _SH3)
    add1("server_gid", _SH2)
    add1("server_valid", _SH2)
    add1("dead", _SH3)
    arrays1 = [pre.get(n, dyn.get(n)) for n in layout1]
    f1 = _packed(_stage1_stacked, tuple(layout1), sc)
    v_sh, gid_sh = _run_stage(f1, mesh, arrays1, specs1, 2)

    # -- merge 1: the small all-gather + global top-s (Eq. 2) --
    top_s = min(sc.cfg.top_s, sc.n_servers)
    _, pos = jax.lax.top_k(_flatten_shards(v_sh), top_s)
    cand_gids = jnp.take_along_axis(_flatten_shards(gid_sh), pos, axis=-1)

    # -- stage 2: shard-local tool candidates + telemetry terms --
    if compact2:
        # candidate-compacted stage 2: replicated gathers over the ≤
        # top_s * k_slot candidate tools only — no full-tool-axis mask,
        # gather or top-k anywhere (see _stage2_compact for the parity
        # argument).  Runs outside shard_map, like the merges.
        sel, val, qos, load, rtt, dead, aff, gid = _stage2_compact(
            dyn, t_full, v_full, nt, cand_gids, sc, kmesh
        )
    else:
        layout2, specs2 = [], []

        def add2(name, spec):
            val = pre.get(name, dyn.get(name))
            if val is not None:
                layout2.append(name)
                specs2.append(spec)

        if "t_pre" in pre:
            add2("t_pre", _SH3)
        else:
            add2("q_tool", _REP2)
            add2("w_tool", _SH3)
        if sc.terms.rerank and "t_pre" not in pre:
            add2("q_rerank", _REP2)
        if "val_pre" in pre:
            add2("val_pre", _SH3)
        add2("tool_host_global", _SH2)
        add2("tool_host_local", _SH2)
        add2("tool_gid", _SH2)
        add2("tool_valid", _SH2)
        if "qos_pre" in pre:
            add2("qos_pre", _SH3)
        elif "lat" in dyn:
            add2("lat", _SH4 if dyn["lat"].ndim == 4 else _SH3)
        add2("load", _SH3)
        add2("age", _SH3)
        add2("rtt", _SH3)
        add2("rtt_region", _SH3)
        add2("region_idx", _REP1)
        add2("dead", _SH3)
        add2("aff", _SH3)
        arrays2 = [pre.get(n, dyn.get(n)) for n in layout2]

        def f2(*arrs):
            d = dict(zip(tuple(layout2), arrs))
            return _stage2_stacked(d, cand_gids, sc)

        if mesh is not None:
            # candidate set is replicated input to every shard
            layout2_m = tuple(layout2) + ("cand_gids",)
            specs2_m = list(specs2) + [_REP2]

            def f2m(*arrs):
                d = dict(zip(layout2_m, arrs))
                return _stage2_stacked(d, d["cand_gids"], sc)

            outs = _run_stage(f2m, mesh, arrays2 + [cand_gids], specs2_m, 8)
        else:
            outs = f2(*arrays2)
        sel_c, val_c, qos_c, load_c, rtt_c, dead_c, aff_c, gid_c = outs

        # -- merge 2: all-gather candidates before the fused tail --
        sel = _flatten_shards(sel_c)
        val = _flatten_shards(val_c)
        qos = _flatten_shards(qos_c)
        load = _flatten_shards(load_c)
        rtt = _flatten_shards(rtt_c)
        dead = _flatten_shards(dead_c)
        aff = _flatten_shards(aff_c)
        gid = _flatten_shards(gid_c)

    # SONAR-ADAPT: the replicated live weight vector (updated once per
    # route, identically for every shard) replaces the static floats on
    # its active terms; inactive terms keep the structural literals so the
    # reduction identities survive adaptation
    cfg, terms = sc.cfg, sc.terms
    aw = dyn.get("adapt_w")
    if terms.network:
        if aw is not None:
            eff_alpha, eff_beta = aw[0], aw[1]
        else:
            eff_alpha, eff_beta = cfg.alpha, cfg.beta
    else:
        eff_alpha, eff_beta = 1.0, 0.0
    if terms.load:
        eff_gamma = aw[2] if aw is not None else cfg.gamma
    else:
        eff_gamma = 0.0
    if terms.rtt:
        eff_delta = aw[3] if aw is not None else cfg.delta
    else:
        eff_delta = 0.0
    dead_arg = dead if terms.failover else None
    # pass tool_aff=None when the bonus is off so no-affinity configs
    # trace the historical 4-term graph byte-identically
    aff_arg = aff if terms.affinity else None
    eff_eps = cfg.eps if terms.affinity else 0.0

    k_final = min(cfg.top_k, sc.n_tools)
    if sc.use_kernels:
        # static weights stay Python floats (constant-folded kernel);
        # traced ones (SONAR-ADAPT) enter the shard_map as operands
        weights = dict(alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
                       delta=eff_delta)
        traced = {k: v for k, v in weights.items()
                  if isinstance(v, jax.Array)}

        def tail(arrs, w):
            return ops.fused_select(
                arrs["sel"], arrs["val"], arrs["qos"], arrs["load"],
                arrs["dead"], k=k_final, tool_rtt=arrs["rtt"],
                tool_aff=arrs["aff"], eps=eff_eps, temp=cfg.expertise_temp,
                interpret=sc.interpret, **{**weights, **w},
            )

        arrs = dict(sel=sel, val=val, qos=qos, load=load, dead=dead_arg,
                    rtt=rtt, aff=aff_arg)
        pos, c, n, s = _replicated(tail, kmesh)(arrs, traced)
    else:
        pos, c, n, s = kref.fused_select_ref(
            sel, val, qos, load, dead_arg,
            k=k_final, alpha=eff_alpha, beta=eff_beta, gamma=eff_gamma,
            tool_rtt=rtt, delta=eff_delta,
            tool_aff=aff_arg, eps=eff_eps,
            temp=cfg.expertise_temp,
        )
    tool_idx = jnp.take_along_axis(gid, pos[:, None], axis=-1)[:, 0]
    server_idx = jnp.take(dyn["tool_server"], tool_idx)
    return server_idx, tool_idx, c, n, s


# ---------------------------------------------------------------------------
# Engine
# ---------------------------------------------------------------------------

class ShardedRoutingEngine(RoutingEngineBase):
    """Mesh-sharded drop-in for `BatchRoutingEngine` at mega-fleet scale.

    Shares the host layer (`batch_routing.RoutingEngineBase`) and runs
    `_route_sharded`; `route` additionally takes the keyword-only
    ``telemetry_templates=(compact [M, T], template_map [n_servers])``:
    telemetry in template-compact form — QoS is computed per template row
    and gathered per server, identical to densified scoring but without
    materializing [n_servers, T].  For SONAR-GEO the ``(client_region
    [n_q], region_rtt_ms [n_regions, n_servers])`` pair keeps the RTT input
    compact the same way: the matrix is sharded over the server axis once
    and each shard gathers its queries' rows, so a mega fleet never
    materializes a per-query [n_q, n_servers] RTT slab.

    Parameters
    ----------
    servers : Sequence[Server], optional
        The fleet (ignored when `index` is given).
    cfg : RoutingConfig
    algo : str
        One of the nine registered algorithms (``rag`` .. ``sonar_adapt``).
    n_shards : int
        Server-axis partitions.  Clamped to ``n_servers``.
    mesh : Mesh | "auto" | None
        A 1-D device mesh with axis ``"fleet"`` of size `n_shards` runs
        the per-shard stages under ``shard_map``.  ``"auto"`` builds one
        via `launch.mesh.make_fleet_mesh` when enough devices exist, else
        falls back to the (bit-identical) single-device emulation and sets
        ``emulated``.  None always emulates.
    index : ToolIndex | TiledFleetIndex, optional
        Pre-built index; a `TiledFleetIndex` enables template-gathered
        scoring (no fleet-sized weight matrices anywhere).
    registry : MetricsRegistry, optional
        Where `route` times its upload, enqueue and readback phases.
    """

    def __init__(
        self,
        servers: Optional[Sequence[Server]] = None,
        cfg: RoutingConfig = RoutingConfig(),
        algo: str = "sonar",
        n_shards: int = 1,
        mesh=None,
        use_kernels: Optional[bool] = None,
        interpret: Optional[bool] = None,
        index=None,
        compact_stage2: Optional[bool] = None,
        adapt: Optional[_adaptive.AdaptConfig] = None,
        registry=None,
    ):
        super().__init__(index if index is not None else ToolIndex(servers),
                         cfg, algo, use_kernels, interpret, adapt, registry)
        index = self.index
        self.tiled = bool(getattr(index, "is_tiled", False))
        self.n_servers = (
            index.n_servers if self.tiled else len(index.servers)
        )
        self.plan = make_shard_plan(
            np.asarray(index.tool_server), self.n_servers, n_shards
        )
        self.mesh = self._resolve_mesh(mesh)
        # True when several shards run stacked on one device (``mesh=None``,
        # or ``"auto"`` with too few devices): callers that asked for a
        # real mesh check this instead of trusting the shard count
        self.emulated = self.mesh is None and self.plan.n_shards > 1

        # device-resident static arrays
        self._tool_server = jnp.asarray(index.tool_server, jnp.int32)
        self._server_gid = jnp.asarray(self.plan.server_gid)
        self._server_valid = jnp.asarray(self.plan.server_valid)
        self._tool_gid = jnp.asarray(self.plan.tool_gid)
        self._tool_valid = jnp.asarray(self.plan.tool_valid)
        self._tool_host_g = jnp.asarray(self.plan.tool_host_global)
        self._tool_host_l = jnp.asarray(self.plan.tool_host_local)
        self.compact_stage2 = False
        self._stage1_streams = False
        k_slot = 0
        if self.tiled:
            # quantized storage: bf16-rounded template weights live on
            # device in bf16 (half the HBM traffic per route); the
            # pipeline's f32 upcast is exact, so scores are identical to
            # scoring the rounded weights in f32
            w_dtype = (
                jnp.bfloat16
                if getattr(index, "weights_dtype", "float32")
                in ("bfloat16", "bf16")
                else jnp.float32
            )
            self._w_server_t = jnp.asarray(
                index.server_corpus.weights, w_dtype
            )
            self._w_tool_t = jnp.asarray(index.tool_corpus.weights, w_dtype)
            # each shard's replica -> template map, pad replicas -1, held
            # at the width the stage-1 kernel reads
            self._server_doc_sh = ops.stage1_map(np.where(
                self.plan.server_valid,
                index.server_doc_map[self.plan.server_gid], -1,
            ))
            self._stage1_streams = _stage1_streams(
                self.use_kernels, index.server_corpus.weights.shape[0]
            )
            self._tool_doc_sh = jnp.asarray(
                index.tool_doc_map[self.plan.tool_gid]
            )
            # candidate-compacted stage-2 tables: first global tool id,
            # tool count and first tool-doc id per server.  The compacted
            # path needs every server to host >= 1 tool and the candidate
            # set to be free of pad/duplicate gids (n_servers >= top_s) —
            # outside those preconditions fall back to the full stage-2.
            ts = np.asarray(index.tool_server, np.int64)
            counts = np.bincount(ts, minlength=self.n_servers)
            eligible = (
                int(counts.min()) >= 1 and self.n_servers >= cfg.top_s
            )
            if compact_stage2 is None:
                self.compact_stage2 = eligible
            elif compact_stage2:
                assert eligible, (
                    "compact_stage2 requires every server to host >= 1 "
                    "tool and n_servers >= cfg.top_s"
                )
                self.compact_stage2 = True
            if self.compact_stage2:
                starts = np.cumsum(counts) - counts
                self._tool_start_g = jnp.asarray(starts, jnp.int32)
                self._tool_count_g = jnp.asarray(counts, jnp.int32)
                self._tool_doc0_g = jnp.asarray(
                    np.asarray(index.tool_doc_map)[starts], jnp.int32
                )
                k_slot = int(counts.max())
        else:
            assert not compact_stage2, (
                "compact_stage2 requires a TiledFleetIndex"
            )
            ws = np.asarray(index.server_corpus.weights)
            wt = np.asarray(index.tool_corpus.weights)
            self._w_server_sh = jnp.asarray(ws[self.plan.server_gid])
            self._w_tool_sh = jnp.asarray(wt[self.plan.tool_gid])

        # replica scores stage 1 streamed in the last call (0: lax.top_k)
        self._m_stage1 = self.registry.gauge(
            "engine_stage1_streamed_per_call", "scores"
        )
        # the engine's own statics; each call adds its live terms
        self._sc = _StaticCfg(
            cfg=cfg, terms=self.terms,
            n_shards=self.plan.n_shards,
            n_servers=self.n_servers, n_tools=int(index.n_tools),
            s_keep=min(cfg.top_s, self.plan.s_pad),
            k_keep=min(cfg.top_k, self.plan.t_pad),
            use_kernels=self.use_kernels, interpret=interpret,
            compact2=self.compact_stage2, k_slot=k_slot,
            row_blocks=self._row_blocks,
        )

    def _resolve_mesh(self, mesh):
        if mesh is None:
            return None
        if mesh == "auto":
            from repro.launch.mesh import make_fleet_mesh

            if (
                self.plan.n_shards > 1
                and len(jax.devices()) >= self.plan.n_shards
            ):
                return make_fleet_mesh(self.plan.n_shards)
            return None
        assert mesh.devices.size == self.plan.n_shards, (
            f"mesh has {mesh.devices.size} devices, plan has "
            f"{self.plan.n_shards} shards"
        )
        return mesh

    # -- sharding helpers ---------------------------------------------------
    def _shard_vec(self, x) -> jax.Array:
        """[n_servers] or [n_q, n_servers] -> [J, 1|n_q, s_pad]."""
        x = jnp.asarray(x, jnp.float32)
        if x.ndim == 1:
            x = x[None]
        return jnp.transpose(jnp.take(x, self._server_gid, axis=1), (1, 0, 2))

    def _shard_hist(self, lat) -> jax.Array:
        """[n_servers, T] -> [J, s_pad, T]; [n_q, n_servers, T] ->
        [J, n_q, s_pad, T]."""
        lat = jnp.asarray(lat, jnp.float32)
        if lat.ndim == 2:
            return jnp.take(lat, self._server_gid, axis=0)
        return jnp.transpose(
            jnp.take(lat, self._server_gid, axis=1), (1, 0, 2, 3)
        )

    # -- device side --------------------------------------------------------
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
        telemetry_templates=None,
    ) -> tuple:
        """`_route_sharded` and its operands for one call: the `dyn` key
        set follows the call's live terms."""
        terms = self._live(latency_hist, server_load, telemetry_age_s,
                           failed_mask, client_rtt_ms, client_region,
                           region_rtt_ms, affinity,
                           templates=telemetry_templates is not None)
        streamed = float(batch.n * self.n_servers
                         if self._stage1_streams else 0)
        self._m_stage1.set(streamed)
        obs_trace.note(engine_stage1_streamed_per_call=streamed)
        dyn: dict = {
            "tool_server": self._tool_server,
            "server_gid": self._server_gid,
            "server_valid": self._server_valid,
            "tool_gid": self._tool_gid,
            "tool_valid": self._tool_valid,
            "tool_host_global": self._tool_host_g,
            "tool_host_local": self._tool_host_l,
            "q_server": jnp.asarray(batch.q_server),
            "q_tool": jnp.asarray(batch.q_tool),
        }
        if terms.rerank:
            dyn["q_rerank"] = jnp.asarray(batch.q_rerank)
        if self.tiled:
            dyn["w_server_t"] = self._w_server_t
            dyn["w_tool_t"] = self._w_tool_t
            dyn["server_doc_map"] = self._server_doc_sh
            dyn["tool_doc_map"] = self._tool_doc_sh
            if self.compact_stage2:
                dyn["tool_start_g"] = self._tool_start_g
                dyn["tool_count_g"] = self._tool_count_g
                dyn["tool_doc0_g"] = self._tool_doc0_g
        else:
            dyn["w_server"] = self._w_server_sh
            dyn["w_tool"] = self._w_tool_sh
        if terms.network and telemetry_templates is not None:
            compact, tmap = telemetry_templates
            dyn["lat_t"] = jnp.asarray(compact, jnp.float32)
            dyn["tel_map"] = jnp.asarray(
                np.asarray(tmap, np.int32)[self.plan.server_gid]
            )
        elif terms.network:
            dyn["lat"] = self._shard_hist(latency_hist)
        if terms.load:
            dyn["load"] = self._shard_vec(server_load)
        if terms.staleness:
            dyn["age"] = self._shard_vec(telemetry_age_s)
        if terms.rtt and client_rtt_ms is not None:
            dyn["rtt"] = self._shard_vec(client_rtt_ms)
        elif terms.rtt:
            rr = jnp.asarray(region_rtt_ms, jnp.float32)
            dyn["rtt_region"] = jnp.transpose(
                jnp.take(rr, self._server_gid, axis=1), (1, 0, 2)
            )                                             # [J, R, s_pad]
            dyn["region_idx"] = jnp.asarray(client_region, jnp.int32)
        if terms.failover:
            dyn["dead"] = self._shard_vec(
                np.asarray(failed_mask, np.float32)
            )
        if terms.affinity:
            dyn["aff"] = self._shard_vec(affinity)
        if adapt_w is not None:
            # replicated into the sharded program: every shard fuses with
            # bitwise-identical weights
            dyn["adapt_w"] = adapt_w
        return (_route_sharded, (dyn,),
                dict(mesh=self.mesh, sc=self._sc._replace(terms=terms)))
