"""Fused SONAR selection Pallas kernel (TPU target).

Collapses the per-query tail of Algorithm 1 — stage-2 top-k over the masked
tool scores (Eq. 4), softmax expertise over the candidate set (Eq. 5), QoS
fusion S = alpha*C + beta*N (Eq. 8) and the final argmax (Eq. 9) — into one
pass over a materialized [n_q, n_tools] score matrix.

Why fuse: the unfused pipeline materializes the [n_q, k] candidate tensors
(indices, scores, gathered QoS) in HBM between five separate ops; here each
score stripe is streamed once and the k-step extraction, softmax and fusion
happen in-register.

Inputs per query row
  sel  [n_tools]  — stage-2 scores, already masked to NEG outside the
                    stage-1 candidate servers (Eq. 2 mask).
  val  [n_tools]  — scores used for the expertise softmax.  Equal to `sel`
                    for RAG/PRAG/SONAR; the rerank re-scoring for RerankRAG
                    (candidates are *chosen* by `sel` but *valued* by `val`).
  qos  [n_tools]  — per-tool network score N (Eq. 7), broadcast from the
                    host server; zeros when the algorithm is semantic-only.
  load [n_tools]  — per-tool utilization penalty U (SONAR-LB); zeros off.
  rtt  [n_tools]  — per-tool propagation-RTT penalty R (SONAR-GEO),
                    broadcast from the host server's client-region RTT;
                    zeros off.
  dead [n_tools]  — >0 marks tools on known-failed servers (SONAR-FT
                    failover mask); they keep softmax mass but are excluded
                    from the final argmax.  Zeros off.

Outputs per query row: winning global tool index + (C, N, S) at the winner.

Tiling: grid (query tiles, tool stripes).  Each (QUERY_TILE, stripe) block
is folded into the running per-query top-k that `kernels/score_fuse` keeps
in VMEM scratch (`topk_merge`), and the last stripe runs the shared
softmax / fusion / argmax finale (`topk_finale`).  VMEM holds one stripe
per operand, so the tool axis has no size limit below the 2**24 f32 gid
horizon (a 500k-tool mesh shard streams like a 500-tool one).

Selection semantics replicate the scalar `Router.select` exactly:
top-k ties break toward the lower tool index (stable argsort), the softmax
normalizes over the valid candidate set only, candidates whose selection
score is NEG (fewer than k valid tools) or whose server is dead are
excluded from the argmax, the final argmax tie-breaks toward the earlier
(higher-ranked) candidate, and when *every* candidate is excluded the
top-selection candidate is returned (np.argmax over all -inf picks 0).

Quantized operands: inputs may arrive physically stored as bf16 (upcast
with `.astype(jnp.float32)` at block load, exact for every bf16 value) and
all in-kernel arithmetic is f32, so this kernel sits inside the
quantized-scoring parity contract (docs/benchmarks.md "Quantized scoring
carve-out").
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.score_fuse import (
    K_MAX,
    NEG,
    QUERY_TILE,
    STRIPE,
    topk_finale,
    topk_init,
    topk_merge,
    topk_scratch,
    weight_lanes,
)

__all__ = ["K_MAX", "NEG", "QUERY_TILE", "STRIPE", "fused_select_pallas"]


def _select_kernel(
    *refs,
    k: int, n_stripes: int, t_total: int, stripe: int,
    alpha: float, beta: float, gamma: float, delta: float,
    temp: float, eps: float = 0.0, use_aff: bool = False,
    dyn_weights: bool = False,
):
    # operands: sel, val, rows (qos, load, rtt, dead, [aff]), [w]; then
    # the 4 outputs and the top-k scratch
    refs = list(refs)
    sel_ref, val_ref = refs[:2]
    pos = 2 + (5 if use_aff else 4)
    row_refs = refs[2:pos]
    w_ref = refs[pos] if dyn_weights else None
    pos += 1 if dyn_weights else 0
    out_refs, scr = refs[pos:pos + 4], refs[pos + 4:]
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        topk_init(scr, t_total)

    QT, TS = sel_ref.shape
    gid = stripe * j + jax.lax.broadcasted_iota(jnp.int32, (QT, TS), 1)
    topk_merge(
        scr, sel_ref[...].astype(jnp.float32),
        [ref[...].astype(jnp.float32) for ref in [val_ref] + row_refs],
        gid.astype(jnp.float32), k=k, t_total=t_total, stripe=stripe,
    )

    @pl.when(j == n_stripes - 1)
    def _finale():
        weights = (
            weight_lanes(w_ref) if dyn_weights
            else (alpha, beta, gamma, delta)
        )
        topk_finale(scr, out_refs, weights, k=k, t_total=t_total,
                    temp=temp, eps=eps, use_aff=use_aff)


@functools.partial(
    jax.jit,
    static_argnames=(
        "k", "alpha", "beta", "gamma", "delta", "temp", "eps", "dyn_weights",
        "per_query_qos", "per_query_load", "per_query_rtt", "per_query_dead",
        "use_aff", "per_query_aff", "interpret",
    ),
)
def fused_select_pallas(
    sel: jax.Array,   # [n_q_pad, T_pad] f32, NEG-padded to a stripe multiple
    val: jax.Array,   # [n_q_pad, T_pad] f32
    qos: jax.Array,   # [n_q_pad or 1, T_pad] f32
    load: jax.Array,  # [n_q_pad or 1, T_pad] f32 — per-tool U penalty
    rtt: jax.Array,   # [n_q_pad or 1, T_pad] f32 — per-tool R penalty
    dead: jax.Array,  # [n_q_pad or 1, T_pad] f32 — >0 excludes from argmax
    aff: jax.Array | None = None,  # [n_q_pad or 1, T_pad] f32 — per-tool
                                   # warm-affinity bonus W when use_aff
    w: jax.Array | None = None,  # (1, 128) f32 — live [alpha, beta, gamma,
                                 # delta] in lanes 0..3 when dyn_weights
    *,
    k: int,
    alpha: float,
    beta: float,
    gamma: float,
    delta: float,
    temp: float,
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
    n_q, T_pad = sel.shape
    stripe = min(STRIPE, T_pad)
    assert n_q % QUERY_TILE == 0 and T_pad % stripe == 0 and stripe % 128 == 0
    assert 0 < k <= K_MAX and T_pad + K_MAX + stripe < 2 ** 24
    assert (w is not None) == dyn_weights
    assert (aff is not None) == use_aff
    n_stripes = T_pad // stripe
    grid = (n_q // QUERY_TILE, n_stripes)

    def _row_spec(per_query: bool) -> pl.BlockSpec:
        return (
            pl.BlockSpec((QUERY_TILE, stripe), lambda i, j: (i, j))
            if per_query
            else pl.BlockSpec((1, stripe), lambda i, j: (0, j))
        )

    in_specs = [
        pl.BlockSpec((QUERY_TILE, stripe), lambda i, j: (i, j)),
        pl.BlockSpec((QUERY_TILE, stripe), lambda i, j: (i, j)),
        _row_spec(per_query_qos),
        _row_spec(per_query_load),
        _row_spec(per_query_rtt),
        _row_spec(per_query_dead),
    ]
    operands = [sel, val, qos, load, rtt, dead]
    if use_aff:
        in_specs.append(_row_spec(per_query_aff))
        operands.append(aff)
    if dyn_weights:
        in_specs.append(pl.BlockSpec((1, 128), lambda i, j: (0, 0)))
        operands.append(w)

    out_spec = pl.BlockSpec((QUERY_TILE, 1), lambda i, j: (i, 0))
    out_shape = jax.ShapeDtypeStruct((n_q, 1), jnp.float32)
    idx, c, n, s = pl.pallas_call(
        functools.partial(
            _select_kernel, k=k, n_stripes=n_stripes, t_total=T_pad,
            stripe=stripe, alpha=alpha, beta=beta, gamma=gamma,
            delta=delta, temp=temp, eps=eps, use_aff=use_aff,
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
