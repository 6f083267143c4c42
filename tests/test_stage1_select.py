"""The streaming stage-1 selection over a tiled shard (`kernels/stage1_select`,
interpret mode) against the jnp stage 1 it replaces on the kernel path:
template scores gathered to every replica, dead replicas at NEG, pad
replicas at PAD_NEG, ``lax.top_k``.  Values and replica ids must be
bit-identical, ties to the lower replica, in every case below.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.mesh_routing import NEG, PAD_NEG, _stage1_stacked, _StaticCfg
from repro.core.routing import RoutingConfig, Terms
from repro.kernels import ops
from repro.kernels.stage1_select import STRIPE

M, K = 15, 8

# name -> (n_replicas, n_shards, n_q, dead-row kind, overrides)
CASES = {
    # zero-overlap queries: every template scores the same, so the top-s
    # is the lowest live replicas, templates interleaved
    "equal-scores": (3000, 1, 8, "query", "equal"),
    "down-rack": (3000, 1, 8, "rack", None),
    "template-dead": (3000, 1, 5, "template", None),
    "few-live": (3000, 1, 8, "few-live", None),
    # 4 shards of 3 replicas: the last holds 1 real replica and 2 pads,
    # so PAD_NEG entries come out
    "few-real": (10, 4, 3, "query", None),
    # more than two stripes, the last one ragged
    "ragged": (2 * STRIPE + 77, 1, 8, "rack", None),
    "shards-shared-row": (5003, 4, 8, "shared", None),
    "no-dead-row": (3001, 4, 6, None, None),
}


def _case(name):
    n, J, n_q, dead_kind, override = CASES[name]
    rng = np.random.default_rng(len(name))
    tmap = np.arange(n) % M
    s_full = rng.integers(0, 5, (n_q, M)).astype(np.float32) * 0.75
    if override == "equal":
        s_full[:] = 0.0
    dead = None
    if dead_kind == "query":
        dead = rng.random((n_q, n)) < 0.3
    elif dead_kind == "rack":
        dead = np.zeros((n_q, n), bool)
        dead[:, :40] = True
        dead[1, 7] = False                       # a probe re-admits one
    elif dead_kind == "template":
        top = int(np.argmax(s_full[0]))
        dead = np.broadcast_to(tmap == top, (n_q, n)).copy()
    elif dead_kind == "few-live":
        dead = np.zeros((n_q, n), bool)
        dead[0, :] = True
        dead[0, [5, 600, 2999]] = False          # 3 live, s_keep is 8
    elif dead_kind == "shared":
        dead = (rng.random(n) < 0.2)[None, :]
    return n, J, n_q, tmap, s_full, dead


def _shards(x, J, s_pad, fill):
    """[rows, n] -> [J, rows, s_pad], shard slices padded with ``fill``."""
    rows, n = x.shape
    out = np.full((rows, J * s_pad), fill, x.dtype)
    out[:, :n] = x
    return out.reshape(rows, J, s_pad).transpose(1, 0, 2)


@pytest.mark.parametrize("name", list(CASES))
def test_stage1_select_matches_top_k(name):
    n, J, n_q, tmap, s_full, dead = _case(name)
    s_pad = -(-n // J)
    gid = np.arange(J * s_pad).reshape(J, s_pad)
    valid = gid < n
    sc = _StaticCfg(
        cfg=RoutingConfig(top_s=K), terms=Terms(failover=dead is not None),
        n_shards=J, n_servers=n, n_tools=2 * n, s_keep=min(K, s_pad),
        k_keep=K, use_kernels=False, interpret=True,
    )
    d = {"server_gid": jnp.asarray(np.minimum(gid, n - 1), jnp.int32),
         "server_valid": jnp.asarray(valid)}
    if dead is not None:
        d["dead"] = jnp.asarray(_shards(dead.astype(np.float32), J, s_pad,
                                        0.0))
    doc_map = _shards(tmap[None, :], J, s_pad, -1)[:, 0]     # [J, s_pad]
    # today's stage 1: template scores gathered to every replica, top_k
    s_pre = jnp.take(jnp.asarray(s_full), jnp.asarray(np.maximum(doc_map, 0)),
                     axis=1).transpose(1, 0, 2)
    v0, g0 = _stage1_stacked({**d, "s_pre": s_pre}, sc)
    v1, g1 = _stage1_stacked(
        {**d, "s_full": jnp.asarray(s_full),
         "server_doc_map": ops.stage1_map(doc_map)},
        sc._replace(use_kernels=True),
    )
    np.testing.assert_array_equal(np.asarray(v1).view(np.int32),
                                  np.asarray(v0).view(np.int32))
    np.testing.assert_array_equal(np.asarray(g1), np.asarray(g0))
    if name == "few-real":                 # the pads did come out
        assert (np.asarray(v1) == PAD_NEG).any()
    if name == "few-live":                 # and dead replicas filled the row
        assert (np.asarray(v1)[0, 0] == NEG).sum() == K - 3
