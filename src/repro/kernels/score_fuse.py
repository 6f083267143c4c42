"""Single-pass fused SONAR scoring Pallas kernel (TPU target).

`select_fuse` fuses the *tail* of the routing decision but still consumes a
pre-materialized [n_q, n_tools] score matrix from a separate BM25 kernel
pass plus a separately materialized candidate mask.  This kernel fuses the
whole stage-2 chain into ONE pass over tool stripes:

    BM25 matmul (Eq. 3)  ->  candidate-server mask (Eq. 2/4)
      ->  streaming top-k  ->  softmax expertise (Eq. 5)
      ->  QoS / load / RTT fusion (Eq. 8)  ->  argmax (Eq. 9)

so the [n_q, n_tools] score matrix never exists in HBM: each
(query-tile, tool-stripe) block of scores is produced by the MXU, masked,
and folded into a running per-query top-k held in VMEM scratch, carried
across the stripe grid axis.  Operands may arrive quantized (bf16 query /
weight / telemetry-derived rows); they are upcast to f32 *exactly* at
block load and every accumulation (dot products, softmax, fusion) runs in
f32 — the quantization carve-out documented in docs/benchmarks.md.

Ragged tile-skipping: a host-computed [n_query_tiles, n_stripes] flag
array marks stripes that contain no candidate-server tools for any query
in the tile (at top_s candidates per query, almost all stripes at fleet
scale).  Skipped stripes cost one flag load and zero MXU/VPU work —
mostly-dead or all-NEG shards are free.

Selection semantics replicate `kernels.ref.fused_select_ref` (and hence
the scalar `Router.select`): the running top-k orders candidates by
(score desc, global tool id asc) — exactly ``lax.top_k``'s tie rule over
the full tool axis — because each stripe merge re-peels the combined
(scratch ∪ stripe) pool with a min-global-id tie-break; scratch entries
from earlier stripes always carry lower gids than the current stripe, so
stability is preserved.  The running top-k (`topk_init`, `topk_merge`,
`topk_finale`) is shared with `select_fuse`, which streams materialized
score stripes through the same merge and finale, and its init and merge
with `stage1_select`, which keeps replica ids alone and empties slots to
a floor below its pad sentinel.  Every index (lane, gid)
comes from an int32 iota cast to f32, exact below 2**24; the stripe flags
ride in SMEM, one scalar per grid step.  One caveat: a query whose
candidate servers host zero tools (every stripe skipped) returns tool 0
with neutral (zero) metadata — reachable only on degenerate pools where
stage-1 candidates have no tools at all.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

QUERY_TILE = 8      # f32 sublane granularity
STRIPE = 512        # tool-axis stripe width (lanes)
K_MAX = 128         # running top-k scratch width (one lane register row)
NEG = -1e30         # finite -inf stand-in


def lane_index(shape, dim: int = 1) -> jax.Array:
    """Index iota along ``dim`` as f32.  Mosaic builds integer iotas only;
    every index here stays below 2**24, where f32 holds integers exactly,
    so the cast loses nothing."""
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim).astype(jnp.float32)


def weight_lanes(w_ref) -> tuple:
    """Live (alpha, beta, gamma, delta) from lanes 0..3 of a (1, 128) f32
    row, each as a (1, 1) value: one-hot lane reductions keep this pure
    VPU work (no scalar-memory gathers)."""
    wrow = w_ref[...].astype(jnp.float32)
    wl = lane_index(wrow.shape)
    return tuple(
        jnp.sum(jnp.where(wl == float(i), wrow, 0.0), axis=-1, keepdims=True)
        for i in range(4)
    )


def _pick(x, onehot):
    """The one lane of ``x`` that ``onehot`` marks, per row ([QT, 1]).  A
    select, not a multiply: -inf * 0 would poison the sum with NaN."""
    return jnp.sum(jnp.where(onehot, x, 0.0), axis=-1, keepdims=True)


def topk_scratch(use_aff: bool) -> list:
    """VMEM scratch of the running top-k, one (QUERY_TILE, K_MAX) f32 row
    block each: selection score, softmax value, the per-tool rows (QoS,
    load, RTT, dead, [affinity]) and the global tool id, in that order."""
    n = 8 if use_aff else 7
    return [pltpu.VMEM((QUERY_TILE, K_MAX), jnp.float32)] * n


def _empty(col: int, floor: float) -> float:
    """Value of an empty scratch slot in column ``col``: ``floor`` for the
    selection score, NEG for the softmax value, 0 for per-tool rows."""
    return floor if col == 0 else NEG if col == 1 else 0.0


def topk_init(scr, t_total: int, floor: float = NEG) -> None:
    """Empty running top-k: ``floor`` scores (below every score the caller
    folds in), sentinel gids above every real id so they lose every
    min-gid tie-break.  ``scr`` is [score, payload..., gid]."""
    shape = (QUERY_TILE, K_MAX)
    for col, ref in enumerate(scr[:-1]):
        ref[...] = jnp.full(shape, _empty(col, floor), jnp.float32)
    scr[-1][...] = float(t_total) + lane_index(shape)


def topk_merge(
    scr, stripe_sel, payload, stripe_gid, *,
    k: int, t_total: int, stripe: int, floor: float = NEG,
) -> None:
    """Fold one (QUERY_TILE, TS) stripe into the running top-k.

    ``payload`` holds the stripe's rows that ride with each entry
    ([QT or 1, TS], in the order of ``scr[1:-1]``: the softmax value, then
    the per-tool rows) and ``stripe_gid`` its global ids as f32.  The
    combined pool (scratch + stripe) is peeled k times in (score desc, gid
    asc) order; a peeled entry retires to ``floor``, which must lie below
    every score folded in (`topk_init`'s).  Gids are unique across the pool
    (stripes are disjoint ranges; scratch holds earlier stripes' gids or
    sentinels), so the min-gid one-hot selects exactly one entry per
    step."""
    QT, TS = stripe_sel.shape
    lane = lane_index((QT, K_MAX))

    def comb(ref, x):
        return jnp.concatenate([ref[...], jnp.broadcast_to(x, (QT, TS))], axis=1)

    comb_sel = comb(scr[0], stripe_sel)
    comb_pay = [comb(ref, x) for ref, x in zip(scr[1:-1], payload)]
    comb_gid = comb(scr[-1], stripe_gid)
    big = float(t_total + K_MAX + stripe)

    news = []
    for _ in range(k):
        m = jnp.max(comb_sel, axis=-1, keepdims=True)        # [QT, 1]
        is_max = comb_sel >= m
        g = jnp.min(jnp.where(is_max, comb_gid, big), axis=-1, keepdims=True)
        onehot = comb_gid == g                               # [QT, C]
        news.append([m] + [_pick(x, onehot) for x in comb_pay] + [g])
        # retire the peeled entry from BOTH pools: score AND gid — leaving
        # the gid live would let a later all-floor tie re-pick it,
        # duplicating gids in scratch and double-counting the gid-keyed
        # one-hot sums on the next merge
        comb_sel = jnp.where(onehot, floor, comb_sel)
        comb_gid = jnp.where(onehot, big, comb_gid)

    # write the re-sorted top-k back into scratch lanes [0, k)
    def pack(vals, fill):
        acc = jnp.where(lane >= float(k), fill, 0.0)
        for slot, v in enumerate(vals):
            acc = acc + jnp.where(lane == float(slot), v, 0.0)
        return acc

    for col, ref in enumerate(scr[:-1]):
        ref[...] = pack([e[col] for e in news], _empty(col, floor))
    scr[-1][...] = pack([e[-1] for e in news], float(t_total)) + jnp.where(
        lane >= float(k), lane, 0.0
    )


def topk_finale(
    scr, out_refs, weights: tuple, *,
    k: int, t_total: int, temp: float, eps: float, use_aff: bool,
) -> None:
    """Softmax (Eq. 5) + fusion (Eq. 8) + argmax (Eq. 9) over the k
    running candidates.  ``weights`` is (alpha, beta, gamma, delta) as
    Python floats or (1, 1) values.  The argmax is seeded with candidate 0
    at score NEG, so an all-excluded row returns the top-selection
    candidate, like np.argmax over an all--inf vector."""
    QT = QUERY_TILE
    lane = lane_index((QT, K_MAX))
    cands = []                               # per slot: [m, v, rows.., gid]
    for slot in range(k):
        onehot = lane == float(slot)
        cands.append([_pick(ref[...], onehot) for ref in scr])
    cand_val = [jnp.where(c[0] > NEG / 2.0, c[1], NEG) for c in cands]

    vmax = cand_val[0]                       # extraction is value-sorted only
    for v in cand_val[1:]:                   # when val == sel; reduce
        vmax = jnp.maximum(vmax, v)          # explicitly
    exps = [jnp.exp((v - vmax) / temp) for v in cand_val]
    denom = exps[0]
    for e in exps[1:]:
        denom = denom + e
    denom = jnp.maximum(denom, 1e-30)

    alpha, beta, gamma, delta = weights
    best_s = jnp.full((QT, 1), NEG, jnp.float32)
    best_c = exps[0] / denom
    best_n = cands[0][2]
    best_i = cands[0][-1]
    for v, e, c_ in zip(cand_val, exps, cands):
        n, u, r, d = c_[2:6]
        c = e / denom
        s = alpha * c + beta * n - gamma * u - delta * r
        if use_aff:
            s = s + eps * c_[6]
        s = jnp.where(v > NEG / 2.0, s, NEG)
        s = jnp.where(d > 0.0, NEG, s)
        take = s > best_s                    # strict: earliest winner
        best_c = jnp.where(take, c, best_c)
        best_n = jnp.where(take, n, best_n)
        best_i = jnp.where(take, c_[-1], best_i)
        best_s = jnp.where(take, s, best_s)

    # rows whose every stripe was skipped still hold the sentinel gid:
    # clamp to tool 0, matching np.argmax over an all--inf vector
    best_i = jnp.where(best_i >= float(t_total), 0.0, best_i)
    idx_ref, c_ref, n_ref, s_ref = out_refs
    idx_ref[...] = best_i.astype(jnp.int32)
    c_ref[...] = best_c
    n_ref[...] = best_n
    s_ref[...] = best_s


def _dot_t(a, b):
    """[QT, V] x [TS, V]^T in f32.  HIGHEST keeps f32 operands exact on
    the MXU (the default is one bf16 pass)."""
    return jax.lax.dot_general(
        a, b, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32,
    )


def _score_kernel(
    *refs,
    k: int, n_stripes: int, t_total: int, top_s: int,
    alpha: float, beta: float, gamma: float, delta: float, temp: float,
    rerank: bool, eps: float = 0.0, use_aff: bool = False,
    dyn_weights: bool = False,
):
    # operands: q, qr, w, host, cand, rows (qos, load, rtt, dead, [aff]),
    # flags, [wvec]; then the 4 outputs and the top-k scratch
    refs = list(refs)
    q_ref, qr_ref, w_ref, host_ref, cand_ref = refs[:5]
    pos = 5 + (5 if use_aff else 4)
    row_refs = refs[5:pos]
    flag_ref = refs[pos]                     # [1, n_stripes] i32 in SMEM
    wvec_ref = refs[pos + 1] if dyn_weights else None
    pos += 2 if dyn_weights else 1
    out_refs, scr = refs[pos:pos + 4], refs[pos + 4:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        topk_init(scr, t_total)

    # --- stripe merge: only when the stripe hosts candidate tools ---
    @pl.when(flag_ref[0, j] > 0)
    def _merge():
        q = q_ref[...].astype(jnp.float32)                   # [QT, V]
        w = w_ref[...].astype(jnp.float32)                   # [TS, V]
        scores = _dot_t(q, w)                                # [QT, TS]
        QT, TS = scores.shape
        host = host_ref[...].astype(jnp.int32)               # [1, TS]
        cand = cand_ref[...].astype(jnp.int32)               # [QT, top_s]
        member = jnp.zeros((QT, TS), jnp.bool_)
        for s_i in range(top_s):
            member = member | (host == cand[:, s_i:s_i + 1])
        stripe_sel = jnp.where(member, scores, NEG)
        if rerank:
            stripe_val = _dot_t(qr_ref[...].astype(jnp.float32), w)
        else:
            stripe_val = stripe_sel
        gid = STRIPE * j + jax.lax.broadcasted_iota(jnp.int32, (QT, TS), 1)
        topk_merge(
            scr, stripe_sel,
            [stripe_val] + [ref[...].astype(jnp.float32) for ref in row_refs],
            gid.astype(jnp.float32), k=k, t_total=t_total, stripe=STRIPE,
        )

    @pl.when(j == n_stripes - 1)
    def _finale():
        weights = (
            weight_lanes(wvec_ref) if dyn_weights
            else (alpha, beta, gamma, delta)
        )
        topk_finale(scr, out_refs, weights, k=k, t_total=t_total,
                    temp=temp, eps=eps, use_aff=use_aff)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "top_s", "alpha", "beta", "gamma", "delta", "temp", "eps",
        "rerank", "dyn_weights", "per_query_qos", "per_query_load",
        "per_query_rtt", "per_query_dead", "use_aff", "per_query_aff",
        "interpret",
    ),
)
def fused_score_select_pallas(
    q: jax.Array,      # [n_q_pad, V_pad] f32/bf16 stage-2 query counts
    qr: jax.Array,     # [n_q_pad, V_pad] rerank counts (== q when unused)
    w: jax.Array,      # [T_pad, V_pad] f32/bf16 tool weights
    host: jax.Array,   # [1, T_pad] i32 host server per tool (-1 = pad)
    cand: jax.Array,   # [n_q_pad, top_s] i32 candidate servers (-1 = pad)
    qos: jax.Array,    # [n_q_pad or 1, T_pad] f32 per-tool N
    load: jax.Array,   # [n_q_pad or 1, T_pad] f32 per-tool U
    rtt: jax.Array,    # [n_q_pad or 1, T_pad] f32 per-tool R
    dead: jax.Array,   # [n_q_pad or 1, T_pad] f32 failover mask
    flags: jax.Array,  # [n_q_pad // QUERY_TILE, n_stripes] i32 stripe-live
    aff: jax.Array | None = None,   # [n_q_pad or 1, T_pad] f32 per-tool
                                    # warm-affinity bonus W when use_aff
    wvec: jax.Array | None = None,  # (1, 128) f32 — live [alpha, beta,
                                    # gamma, delta] in lanes 0..3
    *,
    k: int,
    top_s: int,
    alpha: float,
    beta: float,
    gamma: float,
    delta: float,
    temp: float,
    rerank: bool,
    per_query_qos: bool,
    per_query_load: bool,
    per_query_rtt: bool,
    per_query_dead: bool,
    eps: float = 0.0,
    use_aff: bool = False,
    per_query_aff: bool = False,
    dyn_weights: bool = False,
    interpret: bool = False,
):
    n_q, V_pad = q.shape
    T_pad = w.shape[0]
    assert n_q % QUERY_TILE == 0 and T_pad % STRIPE == 0
    assert V_pad % 128 == 0 and 0 < k <= K_MAX
    n_stripes = T_pad // STRIPE
    grid = (n_q // QUERY_TILE, n_stripes)

    def _row_spec(per_query: bool) -> pl.BlockSpec:
        return (
            pl.BlockSpec((QUERY_TILE, STRIPE), lambda i, j: (i, j))
            if per_query
            else pl.BlockSpec((1, STRIPE), lambda i, j: (0, j))
        )

    out_spec = pl.BlockSpec((QUERY_TILE, 1), lambda i, j: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_q, 1), jnp.float32)
    assert (wvec is not None) == dyn_weights
    assert (aff is not None) == use_aff
    in_specs = [
        pl.BlockSpec((QUERY_TILE, V_pad), lambda i, j: (i, 0)),
        pl.BlockSpec((QUERY_TILE, V_pad), lambda i, j: (i, 0)),
        pl.BlockSpec((STRIPE, V_pad), lambda i, j: (j, 0)),
        pl.BlockSpec((1, STRIPE), lambda i, j: (0, j)),
        pl.BlockSpec((QUERY_TILE, cand.shape[1]), lambda i, j: (i, 0)),
        _row_spec(per_query_qos),
        _row_spec(per_query_load),
        _row_spec(per_query_rtt),
        _row_spec(per_query_dead),
    ]
    operands = [q, qr, w, host, cand, qos, load, rtt, dead]
    if use_aff:
        in_specs.append(_row_spec(per_query_aff))
        operands.append(aff)
    # the query tile's row of flags rides in SMEM (read as scalars for the
    # pl.when guard): [n_q_tiles, 1, n_stripes] with the tile axis squeezed
    # keeps the block's last two dims whole, and the footprint is one row
    # whatever the batch size (SMEM is 1 MiB on v5e)
    in_specs.append(pl.BlockSpec(
        (None, 1, n_stripes), lambda i, j: (i, 0, 0),
        memory_space=pltpu.SMEM,
    ))
    operands.append(flags[:, None, :])
    if dyn_weights:
        in_specs.append(pl.BlockSpec((1, 128), lambda i, j: (0, 0)))
        operands.append(wvec)
    idx, c, n, s = pl.pallas_call(
        functools.partial(
            _score_kernel, k=k, n_stripes=n_stripes, t_total=T_pad,
            top_s=top_s, alpha=alpha, beta=beta, gamma=gamma, delta=delta,
            temp=temp, rerank=rerank, eps=eps, use_aff=use_aff,
            dyn_weights=dyn_weights,
        ),
        grid=grid,
        in_specs=in_specs,
        out_specs=[out_spec, out_spec, out_spec, out_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n_q, 1), jnp.int32),
            out_shape, out_shape, out_shape,
        ],
        scratch_shapes=topk_scratch(use_aff),
        interpret=interpret,
    )(*operands)
    return idx[:, 0], c[:, 0], n[:, 0], s[:, 0]
