"""Public jit'd entry points for the Pallas kernels.

These wrappers own all padding/alignment bookkeeping so callers (the SONAR
router, the serving attention layers) use natural shapes.  A caller that
keeps a constant corpus may store it once at the shape the kernel reads
(`bm25_corpus`, `score_fuse_corpus`), and the per-call pad then vanishes.
On CPU the kernels execute in interpret mode; on TPU they compile to
Mosaic.  `interpret=None` auto-selects by backend.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qos import DEFAULT_QOS, QosParams
from repro.kernels import bm25_score as _bm25
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import qos_score as _qos
from repro.kernels import score_fuse as _scf
from repro.kernels import select_fuse as _sel
from repro.kernels import stage1_select as _s1


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _pad_to(x: np.ndarray | jax.Array, axis: int, mult: int, value=0.0):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


def _row_arg(x, n_t: int):
    """(row operand, per_query): a per-tool row as [1, n_t] (zeros when
    absent) or a per-query matrix [n_q, n_t] as is, in f32."""
    if x is None:
        return jnp.zeros((1, n_t), jnp.float32), False
    x = jnp.asarray(x, jnp.float32)
    per_query = x.ndim == 2
    return (x if per_query else x[None, :]), per_query


def _pad_rows(x, per_query: bool, lanes: int, q_tile: int, value=0.0):
    """Pad the tool axis to ``lanes`` and, for per-query rows, the query
    axis to ``q_tile``."""
    x = _pad_to(x, 1, lanes, value=value)
    return _pad_to(x, 0, q_tile, value=value) if per_query else x


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# ---------------------------------------------------------------------------
# Corpus matrices stored at the shapes the kernels read
# ---------------------------------------------------------------------------

def bm25_corpus_shape(n_docs: int, V: int) -> tuple:
    """The shape `bm25_scores` pads a [n_docs, V] corpus to."""
    return _round_up(n_docs, _bm25.BD), _round_up(V, _bm25.BV)


def score_fuse_corpus_shape(n_tools: int, V: int) -> tuple:
    """The shape `fused_score_select` pads a [n_tools, V] corpus to."""
    return _round_up(n_tools, _scf.STRIPE), _round_up(V, 128)


def corpus_pad_bytes(server_shape, tool_shape) -> int:
    """Bytes the kernel wrappers pad per call for f32 server and tool
    corpora stored at these shapes: each padded corpus's aligned size, 0
    for one stored aligned."""
    out = 0
    for shape, aligned in ((server_shape, bm25_corpus_shape(*server_shape)),
                           (tool_shape, score_fuse_corpus_shape(*tool_shape))):
        if tuple(shape) != aligned:
            out += 4 * aligned[0] * aligned[1]
    return out


@functools.partial(jax.jit, donate_argnums=0)
def _write_rows(dst: jax.Array, rows: jax.Array, lo: jax.Array) -> jax.Array:
    return jax.lax.dynamic_update_slice(dst, rows, (lo, 0))


def _place_aligned(w: np.ndarray, shape: tuple, chunk_rows: int) -> jax.Array:
    """``w`` zero-padded to ``shape`` on the default device, written in
    blocks of ``chunk_rows`` rows (a divisor of ``shape[0]``): no padded
    host copy and no unpadded device copy of the whole matrix exists."""
    w = np.asarray(w)
    n, V = w.shape
    dst = jnp.zeros(shape, w.dtype)
    for lo in range(0, n, chunk_rows):
        hi = min(lo + chunk_rows, n)
        block = np.zeros((chunk_rows, shape[1]), w.dtype)
        block[: hi - lo, :V] = w[lo:hi]
        dst = _write_rows(dst, block, np.int32(lo))
    return dst


def bm25_corpus(w: np.ndarray) -> jax.Array:
    """A [n_docs, V] host corpus on the device at `bm25_corpus_shape`,
    which `bm25_scores` reads with no pad."""
    return _place_aligned(w, bm25_corpus_shape(*w.shape), _bm25.BD)


def score_fuse_corpus(w: np.ndarray) -> jax.Array:
    """A [n_tools, V] host corpus on the device at
    `score_fuse_corpus_shape`, which `fused_score_select` reads with no
    pad."""
    return _place_aligned(w, score_fuse_corpus_shape(*w.shape), _scf.STRIPE)


# ---------------------------------------------------------------------------
# QoS
# ---------------------------------------------------------------------------

def qos_scores(
    lat: jax.Array,                    # [n_servers, T] ms
    params: QosParams = DEFAULT_QOS,
    *,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Fleet QoS scores N [n_servers]; exact match of core.qos.network_score."""
    n, T = lat.shape
    lat = jnp.asarray(lat, jnp.float32)
    # left-pad time to the 128-lane boundary with copies of the oldest sample
    T_pad = int(np.ceil(T / 128) * 128)
    if T_pad != T:
        lat = jnp.concatenate(
            [jnp.repeat(lat[:, :1], T_pad - T, axis=1), lat], axis=1
        )
    # pad servers to the tile boundary (pad rows score garbage; sliced off)
    lat = _pad_to(lat, 0, _qos.SERVER_TILE, value=30.0)
    out = _qos.qos_score_pallas(
        lat, p=params, T=T, interpret=_auto_interpret(interpret)
    )
    return out[:n]


# ---------------------------------------------------------------------------
# BM25
# ---------------------------------------------------------------------------

def bm25_scores(
    qcounts: jax.Array,  # [n_q, V]
    weights: jax.Array,  # [n_docs, V], or stored at `bm25_corpus_shape`
    *,
    n_docs: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """scores [n_q, n_docs]; exact match of core.bm25.bm25_scores.
    Zero padding is exact for BM25 (absent terms contribute zero), so the
    corpus may arrive with zero rows and columns past the real ``n_docs``
    (default ``weights.shape[0]``) documents and V terms."""
    n_q, V = qcounts.shape
    n_d = weights.shape[0] if n_docs is None else n_docs
    q = _pad_to(_pad_to(jnp.asarray(qcounts, jnp.float32), 1, _bm25.BV), 0, _bm25.BQ)
    w = _pad_to(_pad_to(jnp.asarray(weights, jnp.float32), 1, _bm25.BV), 0, _bm25.BD)
    out = _bm25.bm25_scores_pallas(q, w, interpret=_auto_interpret(interpret))
    return out[:n_q, :n_d]


# ---------------------------------------------------------------------------
# Stage 1 over a template-tiled shard (see kernels/stage1_select)
# ---------------------------------------------------------------------------

def stage1_map(doc_map: np.ndarray) -> jax.Array:
    """A shard-stacked replica -> template map [J, n] (-1 = pad replica)
    on the device as `stage1_select` reads it: [J, 1, R], the replica axis
    -1-padded to whole stripes (`stage1_select.map_width`), so no call
    pads it."""
    doc_map = np.asarray(doc_map, np.int32)
    J, n = doc_map.shape
    out = np.full((J, 1, _s1.map_width(n)), -1, np.int32)
    out[:, 0, :n] = doc_map
    return jnp.asarray(out)


def stage1_select(
    s_full: jax.Array,          # [n_q, M] f32 template scores
    tpl_map: jax.Array,         # [J, 1, R] i32 from `stage1_map`
    dead: Optional[jax.Array] = None,  # [J, 1 or n_q, n] f32, > 0 = dead
    *,
    k: int,
    interpret: Optional[bool] = None,
):
    """Each shard's top-``k`` replicas per query: values [J, n_q, k] and
    in-shard replica indices [J, n_q, k] i32, exactly ``lax.top_k`` over
    the template scores gathered to every replica, with dead replicas at
    NEG and pad replicas (-1) at ``2 * NEG`` — the same floats, ties to
    the lower replica — without building or sorting that row."""
    n_q = s_full.shape[0]
    s = _pad_to(jnp.asarray(s_full, jnp.float32), 0, _s1.QUERY_TILE)
    if dead is not None and dead.shape[1] > 1:
        dead = _pad_to(dead, 1, _s1.QUERY_TILE)
    v, idx = _s1.stage1_select_pallas(
        s, tpl_map, dead, k=k, interpret=_auto_interpret(interpret)
    )
    return v[:, :n_q], idx[:, :n_q]


# ---------------------------------------------------------------------------
# Fused selection (stage-2 top-k + Eq. 5 softmax + Eq. 8 fusion + argmax)
# ---------------------------------------------------------------------------

def _weights_operand(alpha, beta, gamma, delta):
    """(wrow, dyn) — when any fusion weight arrives as a jax.Array (e.g. the
    live SONAR-ADAPT weight vector threaded through a jit trace), pack all
    four into one (1, 128) f32 row that rides into VMEM as a regular
    operand.  The kernel then reads weights as data — one compilation
    serves every adaptation step instead of a recompile per weight change.
    Static Python floats keep the constant-folded specialization."""
    if not any(isinstance(x, jax.Array) for x in (alpha, beta, gamma, delta)):
        return None, False
    wrow = jnp.zeros((1, 128), jnp.float32)
    for i, v in enumerate((alpha, beta, gamma, delta)):
        wrow = wrow.at[0, i].set(jnp.asarray(v, jnp.float32))
    return wrow, True

def fused_select(
    sel_scores: jax.Array,   # [n_q, n_tools] stage-2 scores, invalid = -inf/NEG
    val_scores: jax.Array,   # [n_q, n_tools] softmax-value scores (== sel
                             # except under rerank)
    tool_qos: jax.Array,     # [n_q, n_tools] or [n_tools] per-tool N (Eq. 7)
    tool_load: Optional[jax.Array] = None,  # [n_q, n_tools] or [n_tools]
                                            # per-tool load penalty U
    tool_dead: Optional[jax.Array] = None,  # [n_q, n_tools] or [n_tools]
                                            # >0 = failed server (SONAR-FT)
    *,
    k: int,
    alpha: float,
    beta: float,
    gamma: float = 0.0,
    temp: float = 1.0,
    tool_rtt: Optional[jax.Array] = None,   # [n_q, n_tools] or [n_tools]
                                            # per-tool RTT penalty R
    delta: float = 0.0,
    tool_aff: Optional[jax.Array] = None,   # [n_q, n_tools] or [n_tools]
                                            # per-tool warm-affinity bonus W
    eps: float = 0.0,
    interpret: Optional[bool] = None,
):
    """Winning (tool_idx, C, N, S) per query; exact match of the scalar
    candidate->softmax->fuse->argmax tail of `Router.select` (with the
    SONAR-LB load term when tool_load/gamma are given, the SONAR-GEO
    locality term when tool_rtt/delta are given, the SONAR-SESSION
    warm-affinity bonus when tool_aff/eps are given, and the SONAR-FT
    failed-server argmax exclusion when tool_dead is given)."""
    n_q, n_t = sel_scores.shape
    k = min(k, n_t)
    sel = jnp.maximum(jnp.asarray(sel_scores, jnp.float32), _sel.NEG)
    val = jnp.asarray(val_scores, jnp.float32)
    # tool axis: one lane-aligned stripe up to STRIPE tools, else padded
    # to a multiple of STRIPE (the kernel streams one stripe at a time)
    stripe = min(_sel.STRIPE, -(-n_t // 128) * 128)
    pad = functools.partial(_pad_rows, lanes=stripe, q_tile=_sel.QUERY_TILE)
    qos, per_query_qos = _row_arg(tool_qos, n_t)
    load, per_query_load = _row_arg(tool_load, n_t)
    rtt, per_query_rtt = _row_arg(tool_rtt, n_t)
    dead, per_query_dead = _row_arg(tool_dead, n_t)
    use_aff = tool_aff is not None
    if use_aff:
        aff, per_query_aff = _row_arg(tool_aff, n_t)
        aff = pad(aff, per_query_aff)
    else:
        aff, per_query_aff = None, False

    sel = pad(sel, True, value=_sel.NEG)
    val = pad(val, True, value=_sel.NEG)
    qos = pad(qos, per_query_qos)
    load = pad(load, per_query_load)
    rtt = pad(rtt, per_query_rtt)
    dead = pad(dead, per_query_dead)
    wrow, dyn_w = _weights_operand(alpha, beta, gamma, delta)
    aff_kw = dict(
        aff=aff, use_aff=use_aff, per_query_aff=per_query_aff,
        eps=float(eps) if use_aff else 0.0,
    )
    if dyn_w:
        idx, c, n, s = _sel.fused_select_pallas(
            sel, val, qos, load, rtt, dead, w=wrow,
            k=k, alpha=0.0, beta=0.0, gamma=0.0, delta=0.0,
            temp=float(temp), dyn_weights=True,
            per_query_qos=per_query_qos, per_query_load=per_query_load,
            per_query_rtt=per_query_rtt, per_query_dead=per_query_dead,
            interpret=_auto_interpret(interpret), **aff_kw,
        )
    else:
        idx, c, n, s = _sel.fused_select_pallas(
            sel, val, qos, load, rtt, dead,
            k=k, alpha=float(alpha), beta=float(beta), gamma=float(gamma),
            delta=float(delta), temp=float(temp),
            per_query_qos=per_query_qos, per_query_load=per_query_load,
            per_query_rtt=per_query_rtt, per_query_dead=per_query_dead,
            interpret=_auto_interpret(interpret), **aff_kw,
        )
    return idx[:n_q], c[:n_q], n[:n_q], s[:n_q]


# ---------------------------------------------------------------------------
# Single-pass fused scoring (stage-2 BM25 matmul + candidate mask + top-k +
# softmax + QoS fusion + argmax — see kernels/score_fuse)
# ---------------------------------------------------------------------------

def fused_score_select(
    q_tool: jax.Array,        # [n_q, V] stage-2 query term counts (f32/bf16)
    w_tool: jax.Array,        # [n_tools, V] tool corpus weights (f32/bf16),
                              # or stored at `score_fuse_corpus_shape`
    tool_server: jax.Array,   # [n_tools] i32 host server per tool
    cand_servers: jax.Array,  # [n_q, top_s] i32 stage-1 candidates
    tool_qos: jax.Array,      # [n_q, n_tools] or [n_tools] per-tool N
    tool_load: Optional[jax.Array] = None,
    tool_dead: Optional[jax.Array] = None,
    q_rerank: Optional[jax.Array] = None,   # [n_q, V] (RerankRAG)
    *,
    k: int,
    alpha: float,
    beta: float,
    gamma: float = 0.0,
    temp: float = 1.0,
    tool_rtt: Optional[jax.Array] = None,
    delta: float = 0.0,
    tool_aff: Optional[jax.Array] = None,
    eps: float = 0.0,
    interpret: Optional[bool] = None,
):
    """Winning (tool_idx, C, N, S) per query, never materializing the
    [n_q, n_tools] stage-2 score matrix: the BM25 matmul, candidate-server
    mask, streaming top-k, softmax, QoS/load/RTT fusion and argmax run as
    ONE Pallas pass over tool stripes (with ragged stripe-skipping for
    stripes hosting no candidate tools).  Decision parity with
    `bm25_scores` + `fused_select` / `kernels.ref.fused_select_ref`; bf16
    operands are upcast to f32 exactly at block load (the quantized
    carve-out in docs/benchmarks.md).  The real tool count is
    ``tool_server``'s length; rows of ``w_tool`` past it are zero padding."""
    n_q, V = q_tool.shape
    n_t, top_s = tool_server.shape[0], cand_servers.shape[1]
    k = min(k, n_t)
    assert k <= _scf.K_MAX and top_s <= 128

    q = _pad_to(_pad_to(jnp.asarray(q_tool), 1, 128), 0, _scf.QUERY_TILE)
    qr = q if q_rerank is None else _pad_to(
        _pad_to(jnp.asarray(q_rerank), 1, 128), 0, _scf.QUERY_TILE
    )
    w = _pad_to(_pad_to(jnp.asarray(w_tool), 1, 128), 0, _scf.STRIPE)
    T_pad = w.shape[0]
    # gids (and their retire/sentinel offsets) ride in f32 lanes: exact
    # only below the 24-bit integer horizon
    assert T_pad + _scf.K_MAX + _scf.STRIPE < 2 ** 24
    host = _pad_to(
        jnp.asarray(tool_server, jnp.int32)[None, :], 1, _scf.STRIPE, value=-1
    )
    cand = _pad_to(
        jnp.asarray(cand_servers, jnp.int32), 0, _scf.QUERY_TILE, value=-1
    )

    pad = functools.partial(_pad_rows, lanes=_scf.STRIPE,
                            q_tile=_scf.QUERY_TILE)
    qos, per_query_qos = _row_arg(tool_qos, n_t)
    load, per_query_load = _row_arg(tool_load, n_t)
    rtt, per_query_rtt = _row_arg(tool_rtt, n_t)
    dead, per_query_dead = _row_arg(tool_dead, n_t)
    qos = pad(qos, per_query_qos)
    load = pad(load, per_query_load)
    rtt = pad(rtt, per_query_rtt)
    dead = pad(dead, per_query_dead)
    use_aff = tool_aff is not None
    if use_aff:
        aff, per_query_aff = _row_arg(tool_aff, n_t)
        aff = pad(aff, per_query_aff)
    else:
        aff, per_query_aff = None, False

    # stripe-liveness flags [n_q_tiles, n_stripes]: does any query in the
    # tile have a candidate server hosting a tool in the stripe?
    n_st = T_pad // _scf.STRIPE
    hp = host.reshape(1, n_st, _scf.STRIPE, 1)
    live = jnp.any(hp == cand[:, None, None, :], axis=(2, 3))
    flags = jnp.any(
        live.reshape(-1, _scf.QUERY_TILE, n_st), axis=1
    ).astype(jnp.int32)

    wrow, dyn_w = _weights_operand(alpha, beta, gamma, delta)
    aff_kw = dict(
        aff=aff, use_aff=use_aff, per_query_aff=per_query_aff,
        eps=float(eps) if use_aff else 0.0,
    )
    if dyn_w:
        idx, c, n, s = _scf.fused_score_select_pallas(
            q, qr, w, host, cand, qos, load, rtt, dead, flags, wvec=wrow,
            k=k, top_s=top_s, alpha=0.0, beta=0.0, gamma=0.0, delta=0.0,
            temp=float(temp), rerank=q_rerank is not None, dyn_weights=True,
            per_query_qos=per_query_qos, per_query_load=per_query_load,
            per_query_rtt=per_query_rtt, per_query_dead=per_query_dead,
            interpret=_auto_interpret(interpret), **aff_kw,
        )
    else:
        idx, c, n, s = _scf.fused_score_select_pallas(
            q, qr, w, host, cand, qos, load, rtt, dead, flags,
            k=k, top_s=top_s, alpha=float(alpha), beta=float(beta),
            gamma=float(gamma), delta=float(delta), temp=float(temp),
            rerank=q_rerank is not None,
            per_query_qos=per_query_qos, per_query_load=per_query_load,
            per_query_rtt=per_query_rtt, per_query_dead=per_query_dead,
            interpret=_auto_interpret(interpret), **aff_kw,
        )
    return idx[:n_q], c[:n_q], n[:n_q], s[:n_q]


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def flash_attention(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,
    *,
    sm_scale: Optional[float] = None,
    causal: bool = True,
    bq: int = _fa.DEFAULT_BQ,
    bk: int = _fa.DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Hq, S, D = q.shape
    Sk = k.shape[2]
    sm_scale = sm_scale if sm_scale is not None else 1.0 / float(np.sqrt(D))
    bq = min(bq, int(np.ceil(S / 8) * 8))
    bk = min(bk, int(np.ceil(Sk / 8) * 8))
    qp = _pad_to(q, 2, bq)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = _fa.flash_attention_pallas(
        qp, kp, vp,
        sm_scale=sm_scale, causal=causal, bq=bq, bk=bk, seq_len=Sk,
        interpret=_auto_interpret(interpret),
    )
    return out[:, :, :S]


def decode_attention(
    q: jax.Array,        # [B, Hq, D] — one new token per sequence
    k: jax.Array,        # [B, Hkv, S, D]
    v: jax.Array,
    lengths: jax.Array,  # [B] int32 valid cache lengths
    *,
    sm_scale: Optional[float] = None,
    bk: int = _dec.DEFAULT_BK,
    interpret: Optional[bool] = None,
) -> jax.Array:
    B, Hq, D = q.shape
    Hkv, S = k.shape[1], k.shape[2]
    G = Hq // Hkv
    sm_scale = sm_scale if sm_scale is not None else 1.0 / float(np.sqrt(D))
    bk = min(bk, int(np.ceil(S / 8) * 8))
    qg = q.reshape(B, Hkv, G, D)
    kp = _pad_to(k, 2, bk)
    vp = _pad_to(v, 2, bk)
    out = _dec.decode_attention_pallas(
        qg, kp, vp, lengths.reshape(B, 1).astype(jnp.int32),
        sm_scale=sm_scale, bk=bk, interpret=_auto_interpret(interpret),
    )
    return out.reshape(B, Hq, D)
