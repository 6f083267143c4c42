"""Tiled BM25 scoring Pallas kernel (TPU target).

Stage-1/2 semantic retrieval (paper Eq. 1-4) reduces to an IDF-weighted
TF matmul (see repro.core.bm25):

    scores [n_q, n_docs] = qcounts [n_q, V] @ weights[n_docs, V]^T

At fleet scale (10^3-10^4 virtual servers x 10^4-vocab hashed term space,
scored per request batch) this is MXU work: we tile (BQ x BV) query and
(BD x BV) doc blocks through VMEM with an f32 VMEM accumulator carried
across the sequential vocab grid axis.

Block shapes are MXU-aligned (multiples of 128 lanes / 8 sublanes); padding
to tile boundaries happens in ops.py (zero-padding is exact for BM25 since
absent terms contribute zero mass).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BQ = 128   # query-block rows
BD = 128   # doc-block rows
BV = 512   # vocab (contraction) block


def _bm25_kernel(q_ref, w_ref, out_ref, acc_ref, *, n_v_blocks: int):
    """grid = (n_q_blocks, n_d_blocks, n_v_blocks); the last axis is
    sequential on TPU so acc_ref (VMEM scratch) carries the partial sum."""
    kv = pl.program_id(2)

    @pl.when(kv == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[...].astype(jnp.float32)      # [BQ, BV]
    w = w_ref[...].astype(jnp.float32)      # [BD, BV]
    acc_ref[...] += jax.lax.dot_general(
        q, w, (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,     # f32 operands stay exact
        preferred_element_type=jnp.float32,
    )

    @pl.when(kv == n_v_blocks - 1)
    def _store():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def bm25_scores_pallas(
    qcounts: jax.Array,   # [n_q_pad, V_pad] f32 (zero-padded)
    weights: jax.Array,   # [n_d_pad, V_pad] f32 (zero-padded)
    *,
    interpret: bool = False,
) -> jax.Array:
    n_q, V = qcounts.shape
    n_d, V2 = weights.shape
    assert V == V2 and n_q % BQ == 0 and n_d % BD == 0 and V % BV == 0
    grid = (n_q // BQ, n_d // BD, V // BV)
    return pl.pallas_call(
        functools.partial(_bm25_kernel, n_v_blocks=grid[2]),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BQ, BV), lambda i, j, k: (i, k)),
            pl.BlockSpec((BD, BV), lambda i, j, k: (j, k)),
        ],
        out_specs=pl.BlockSpec((BQ, BD), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((n_q, n_d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((BQ, BD), jnp.float32)],
        interpret=interpret,
    )(qcounts, weights)
