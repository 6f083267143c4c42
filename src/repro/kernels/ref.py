"""Pure-jnp oracles for every Pallas kernel in this package.

Each function is the semantic ground truth; kernel tests sweep shapes and
dtypes and assert allclose against these (see tests/test_kernels.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.bm25 import bm25_scores as bm25_ref          # noqa: F401
from repro.core.qos import QosParams, network_score as qos_ref  # noqa: F401
from repro.kernels.select_fuse import NEG  # kernel & oracle must agree


def fused_select_ref(
    sel_scores: jax.Array,   # [n_q, n_tools], invalid = -inf/NEG
    val_scores: jax.Array,   # [n_q, n_tools]
    tool_qos: jax.Array,     # [n_q, n_tools] or [n_tools]
    tool_load: jax.Array | None = None,  # [n_q, n_tools] or [n_tools] — U
    tool_dead: jax.Array | None = None,  # [n_q, n_tools] or [n_tools] — >0
                                         # excludes the tool from the argmax
    *,
    k: int,
    alpha: float,
    beta: float,
    gamma: float = 0.0,
    temp: float = 1.0,
    tool_rtt: jax.Array | None = None,   # [n_q, n_tools] or [n_tools] — R
    delta: float = 0.0,
    tool_aff: jax.Array | None = None,   # [n_q, n_tools] or [n_tools] — W
    eps: float = 0.0,
):
    """Pure-jnp oracle for kernels/select_fuse: stage-2 top-k (ties -> lower
    index), Eq. 5 softmax over the valid candidates, Eq. 8 fusion (plus the
    SONAR-LB load term -gamma*U, the SONAR-GEO locality term -delta*R, the
    SONAR-SESSION warm-affinity bonus +eps*W and the SONAR-FT failed-server
    mask), argmax.
    Dead candidates keep their softmax mass (they are excluded from the
    *argmax* only), matching the scalar router's post-fusion masking; if
    every candidate is masked/invalid the top-selection candidate wins."""
    sel = jnp.maximum(sel_scores.astype(jnp.float32), NEG)
    k = min(k, sel.shape[-1])
    top_v, top_i = jax.lax.top_k(sel, k)                     # [n_q, k]
    valid = top_v > NEG / 2.0
    val = jnp.take_along_axis(val_scores.astype(jnp.float32), top_i, axis=-1)
    val = jnp.where(valid, val, NEG)

    def _gather(per_tool):
        per_tool = per_tool.astype(jnp.float32)
        if per_tool.ndim == 1:
            return per_tool[top_i]
        return jnp.take_along_axis(per_tool, top_i, axis=-1)

    n = _gather(tool_qos)
    u = _gather(tool_load) if tool_load is not None else jnp.zeros_like(n)
    r = _gather(tool_rtt) if tool_rtt is not None else jnp.zeros_like(n)
    z = (val - jnp.max(val, axis=-1, keepdims=True)) / temp
    e = jnp.exp(z)
    c = e / jnp.maximum(e.sum(axis=-1, keepdims=True), 1e-30)
    # NB: with delta != 0 XLA may FMA-contract this 4-term expression
    # differently across independently-compiled pipelines (batched vs
    # sharded), so SONAR-GEO's fused *score* is only reproduced to ~1 ulp
    # between them; decisions stay argmax-identical because candidates
    # with bit-identical inputs contract identically (exact ties still
    # tie).  With delta == 0 the term folds away and the historical
    # bit-identity of all other algorithms is preserved.
    fused = alpha * c + beta * n - gamma * u - delta * r
    if tool_aff is not None:
        # appended only when an affinity operand is supplied, so zero-
        # affinity callers keep today's 4-term graph byte-identically
        fused = fused + eps * _gather(tool_aff)
    s = jnp.where(valid, fused, NEG)
    if tool_dead is not None:
        s = jnp.where(_gather(tool_dead) > 0.0, NEG, s)
    best = jnp.argmax(s, axis=-1)                            # first max wins
    take = lambda a: jnp.take_along_axis(a, best[:, None], axis=-1)[:, 0]
    return take(top_i), take(c), take(n), take(s)


def fused_score_select_ref(
    q_tool: jax.Array,        # [n_q, V]
    w_tool: jax.Array,        # [n_tools, V]
    tool_server: jax.Array,   # [n_tools] i32
    cand_servers: jax.Array,  # [n_q, top_s] i32
    tool_qos: jax.Array,
    tool_load: jax.Array | None = None,
    tool_dead: jax.Array | None = None,
    q_rerank: jax.Array | None = None,
    *,
    k: int,
    alpha: float,
    beta: float,
    gamma: float = 0.0,
    temp: float = 1.0,
    tool_rtt: jax.Array | None = None,
    delta: float = 0.0,
    tool_aff: jax.Array | None = None,
    eps: float = 0.0,
):
    """Pure-jnp oracle for kernels/score_fuse: materialize the full
    stage-2 score matrix (BM25 matmul + candidate-server mask) and feed
    it to `fused_select_ref` — exactly the unfused two-pass pipeline the
    single-pass kernel replaces."""
    t = bm25_ref(w_tool, q_tool)
    in_cand = jnp.any(
        tool_server[None, None, :] == cand_servers[:, :, None], axis=1
    )                                                        # [n_q, n_tools]
    sel = jnp.where(in_cand, t, NEG)
    if q_rerank is not None:
        val = bm25_ref(w_tool, q_rerank)
    else:
        val = sel
    return fused_select_ref(
        sel, val, tool_qos, tool_load, tool_dead,
        k=k, alpha=alpha, beta=beta, gamma=gamma, temp=temp,
        tool_rtt=tool_rtt, delta=delta, tool_aff=tool_aff, eps=eps,
    )


def repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, Hkv, S, D] -> [B, Hkv*n_rep, S, D] (GQA expansion)."""
    if n_rep == 1:
        return k
    B, H, S, D = k.shape
    return jnp.broadcast_to(k[:, :, None], (B, H, n_rep, S, D)).reshape(
        B, H * n_rep, S, D
    )


def mha_ref(
    q: jax.Array,  # [B, Hq, S, D]
    k: jax.Array,  # [B, Hkv, Sk, D]
    v: jax.Array,  # [B, Hkv, Sk, D]
    *,
    sm_scale: float,
    causal: bool = True,
    seq_len: int | None = None,
) -> jax.Array:
    """Naive full-softmax GQA attention (f32 math)."""
    B, Hq, S, D = q.shape
    Hkv, Sk = k.shape[1], k.shape[2]
    k = repeat_kv(k, Hq // Hkv)
    v = repeat_kv(v, Hq // Hkv)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    mask = jnp.ones((S, Sk), dtype=bool)
    if causal:
        mask &= jnp.tril(jnp.ones((S, Sk), dtype=bool), k=Sk - S)
    if seq_len is not None:
        mask &= (jnp.arange(Sk) < seq_len)[None, :]
    s = jnp.where(mask[None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def decode_ref(
    q: jax.Array,        # [B, Hkv, G, D]
    k: jax.Array,        # [B, Hkv, S, D]
    v: jax.Array,        # [B, Hkv, S, D]
    lengths: jax.Array,  # [B, 1] int32
    *,
    sm_scale: float,
) -> jax.Array:
    """Naive single-token GQA attention over a variable-length cache."""
    s = jnp.einsum(
        "bhgd,bhkd->bhgk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * sm_scale
    S = k.shape[2]
    mask = jnp.arange(S)[None, :] < lengths  # [B, S]
    s = jnp.where(mask[:, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bhkd->bhgd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)
