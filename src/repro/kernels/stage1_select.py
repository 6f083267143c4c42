"""Stage-1 server selection over a template-tiled shard (Pallas, TPU target).

A tiled fleet gives each of its replicas the BM25 score of one of M
template servers.  Stage 1 keeps each shard's top-s replicas per query,
dead replicas below every live one and pad replicas below those.  The jnp
path writes the template scores out to every replica and sorts the row
(``lax.top_k`` over the shard lowers to a full sort on the TPU); this
kernel never builds that row.  Its grid streams the shard's replica axis
in stripes, and for each (query tile, stripe) block it

* forms each replica's score in registers from its template id: M static
  selects from the query tile's [QUERY_TILE, M] template scores;
* sets dead replicas (dead row > 0) to ``NEG`` and pad replicas (template
  id -1) to ``PAD_NEG``, in the jnp path's order, so the values are the
  same floats;
* folds the stripe into the running top-s of `score_fuse.topk_merge` in
  (score desc, replica asc) order — ``lax.top_k``'s tie rule — with
  empty and retired slots at ``FLOOR``, below ``PAD_NEG``.

A stripe whose best score in every row of the tile is not above the
running s-th score cannot enter the top-s (its replicas come after every
kept one), so it skips the merge.  The scores repeat by template, so after
the first stripes nearly every stripe does.  Replica ids ride f32 lanes,
exact below 2**24.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.score_fuse import (
    K_MAX,
    NEG,
    QUERY_TILE,
    _pick,
    lane_index,
    topk_init,
    topk_merge,
)

STRIPE = 8192          # replica-axis stripe (lanes); 123 steps at 10^6
MAX_TEMPLATES = 128    # template scores held as one lane row per query
PAD_NEG = 2.0 * NEG    # pad replicas: below every dead one
FLOOR = 4.0 * NEG      # empty and retired top-s slots: below every pad


def stripe_for(n_replicas: int) -> int:
    """The stripe over a shard of ``n_replicas``: `STRIPE`, or the shard
    rounded up to whole lane tiles where it is smaller."""
    return min(STRIPE, -(-n_replicas // 128) * 128)


def map_width(n_replicas: int) -> int:
    """The replica axis of a shard's template map, a whole number of
    stripes."""
    st = stripe_for(n_replicas)
    return -(-n_replicas // st) * st


def _kernel(s_ref, map_ref, *refs, k: int, m: int, n_total: int,
            stripe: int, n_stripes: int, has_dead: bool):
    # operands: template scores, map, [dead]; outputs: values, ids;
    # scratch: running score and id
    dead_ref = refs[0] if has_dead else None
    v_ref, i_ref, *scr = refs[1:] if has_dead else refs
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        topk_init(scr, n_total, floor=FLOOR)

    s = s_ref[...]                                           # [QT, M]
    QT, TS = s.shape[0], map_ref.shape[-1]
    tpl = jnp.broadcast_to(map_ref[...], (QT, TS))           # i32
    s_lane = lane_index(s.shape)
    score = jnp.zeros((QT, TS), jnp.float32)
    for t in range(m):
        score = jnp.where(tpl == t, _pick(s, s_lane == float(t)), score)
    if has_dead:
        dead = jnp.broadcast_to(dead_ref[...], (QT, TS))
        score = jnp.where(dead > 0.0, NEG, score)
    score = jnp.where(tpl >= 0, score, PAD_NEG)

    # the running s-th score; a stripe not above it in any row is skipped
    lane = lane_index((QT, K_MAX))
    kth = _pick(scr[0][...], lane == float(k - 1))          # [QT, 1]
    best = jnp.max(score, axis=-1, keepdims=True)
    enter = jnp.max(jnp.where(best > kth, 1.0, 0.0)) > 0.0

    @pl.when(enter)
    def _merge():
        gid = stripe * j + jax.lax.broadcasted_iota(jnp.int32, (QT, TS), 1)
        topk_merge(scr, score, [], gid.astype(jnp.float32), k=k,
                   t_total=n_total, stripe=stripe, floor=FLOOR)

    @pl.when(j == n_stripes - 1)
    def _out():
        v_ref[...] = scr[0][...]
        i_ref[...] = scr[-1][...].astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def stage1_select_pallas(
    s_full: jax.Array,     # [n_q_pad, M] f32 template scores
    tpl_map: jax.Array,    # [J, 1, R] i32 replica -> template (-1 = pad)
    dead: jax.Array | None = None,  # [J, n_q_pad or 1, <= R] f32, > 0 dead
    *,
    k: int,
    interpret: bool = False,
):
    n_q, m = s_full.shape
    J, _, R = tpl_map.shape
    stripe = stripe_for(R)
    assert n_q % QUERY_TILE == 0 and R % stripe == 0
    assert 0 < k <= K_MAX and m <= MAX_TEMPLATES
    # replica ids and their retire sentinels ride f32 lanes
    assert R + K_MAX + stripe < 2 ** 24
    n_stripes = R // stripe
    in_specs = [
        pl.BlockSpec((QUERY_TILE, m), lambda jb, i, j: (i, 0)),
        pl.BlockSpec((None, 1, stripe), lambda jb, i, j: (jb, 0, j)),
    ]
    operands = [s_full, tpl_map]
    if dead is not None:
        # the last stripe may run past the row: those lanes are pads
        if dead.shape[1] == 1:
            in_specs.append(pl.BlockSpec((None, 1, stripe),
                                         lambda jb, i, j: (jb, 0, j)))
        else:
            assert dead.shape[1] == n_q
            in_specs.append(pl.BlockSpec((None, QUERY_TILE, stripe),
                                         lambda jb, i, j: (jb, i, j)))
        operands.append(dead)
    out_spec = pl.BlockSpec((None, QUERY_TILE, K_MAX),
                            lambda jb, i, j: (jb, i, 0))
    v, idx = pl.pallas_call(
        functools.partial(_kernel, k=k, m=m, n_total=R, stripe=stripe,
                          n_stripes=n_stripes, has_dead=dead is not None),
        grid=(J, n_q // QUERY_TILE, n_stripes),
        in_specs=in_specs,
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((J, n_q, K_MAX), jnp.float32),
                   jax.ShapeDtypeStruct((J, n_q, K_MAX), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((QUERY_TILE, K_MAX), jnp.float32)] * 2,
        interpret=interpret,
        name="stage1_select",
    )(*operands)
    return v[:, :, :k], idx[:, :, :k]
