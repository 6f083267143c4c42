"""Fused SONAR QoS scoring Pallas kernel (TPU target).

Computes the paper's Eq. 7 network score for a fleet of servers in one pass
over the telemetry matrix:

    lat [n_servers, T] f32  ->  N [n_servers] f32 in [-1, 1]

Fusion rationale (DESIGN.md §7): at fleet scale (thousands of replicas x
O(100)-sample windows, re-scored on every routing decision) the reference
implementation materializes five separate reductions over the telemetry
matrix; the kernel streams each (SERVER_TILE x T) stripe through VMEM once
and produces all penalty terms in-register.  T is padded to the 128-lane
boundary with NaN-free left-padding handled in ops.py.

Tiling: grid over server tiles; block = (SERVER_TILE, T_pad) resident in
VMEM.  For T<=2048 and SERVER_TILE=256 the working set is <= 2 MB, well
inside the ~16 MB v5e VMEM budget, and reductions are lane-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.qos import QosParams

SERVER_TILE = 256


def _qos_kernel(lat_ref, out_ref, *, p: QosParams, T: int, T_pad: int):
    """One (SERVER_TILE, T_pad) stripe.  Columns [0, T_pad-T) are left-pad
    copies of the first real sample (ops.py guarantees this), so EWMA /
    window math below treats the stripe as age-ordered with the newest
    sample in the last column."""
    lat = lat_ref[...].astype(jnp.float32)  # [S_TILE, T_pad]

    # ages: newest sample (last col) has age 0 (in-kernel iota; Pallas
    # kernels may not capture trace-time array constants, and Mosaic builds
    # integer iotas only — the cast is exact for any window length)
    pos = jax.lax.broadcasted_iota(jnp.int32, (1, T_pad), 1)
    k = (T_pad - 1.0) - pos.astype(jnp.float32)

    # --- EWMA (closed form; initial-state mass on the oldest real sample).
    # Pad columns (age k >= T) carry zero weight; the (1-a)^T carry mass is
    # assigned to the oldest *real* column (age k == T-1), exactly matching
    # repro.core.qos.ewma on the unpadded array. ---
    a = p.ewma_alpha
    w = a * (1.0 - a) ** k                                    # [1, T_pad]
    carry = (1.0 - a) ** T
    w = jnp.where(k > T - 1, 0.0, jnp.where(k == T - 1, w + carry, w))
    ew = jnp.sum(lat * w, axis=-1)                            # [S_TILE]

    # --- base score: 1 inside [lo, hi], smooth decay outside ---
    over = jnp.maximum(ew - p.ideal_high_ms, 0.0)
    under = jnp.maximum(p.ideal_low_ms - ew, 0.0)
    base = 1.0 / (1.0 + (over + under) / p.base_scale_ms)

    # --- P_high ---
    p_high = jnp.clip((ew - p.ideal_high_ms) / (4.0 * p.ideal_high_ms), 0.0, 1.0)

    # --- window mask over the *real* trailing `window` samples ---
    m = (k < float(min(p.window, T))).astype(jnp.float32)     # [1, T_pad]
    n_w = float(min(p.window, T))

    # --- P_trend: closed-form LS slope over the window ---
    x = (-k + (n_w - 1) / 2.0) * m                            # centered pos
    sum_x2 = jnp.sum(x * x)
    slope = jnp.sum(lat * x, axis=-1) / jnp.maximum(sum_x2, 1e-6)
    p_trend = jnp.clip(slope * n_w / p.trend_scale_ms, 0.0, 1.0)

    # --- P_outage ---
    risky = (lat > p.outage_risk_ms).astype(jnp.float32) * m
    p_outage = jnp.clip(2.0 * jnp.sum(risky, axis=-1) / n_w, 0.0, 1.0)

    # --- P_instab: coefficient of variation over the window ---
    mean_w = jnp.sum(lat * m, axis=-1) / n_w
    var_w = jnp.sum((lat - mean_w[:, None]) ** 2 * m, axis=-1) / n_w
    cv = jnp.sqrt(jnp.maximum(var_w, 0.0)) / jnp.maximum(mean_w, 1e-6)
    p_instab = jnp.clip((cv - p.cv_low) / p.cv_scale, 0.0, 1.0)

    score = (
        base
        * (1.0 - p.w_high * p_high)
        * (1.0 - p.w_trend * p_trend)
        * (1.0 - p.w_outage * p_outage)
        * (1.0 - p.w_instab * p_instab)
    )
    # newest sample (age 0) by a masked lane max: Mosaic has no dynamic
    # slice for a single unaligned column, and the max over one live lane
    # returns that sample exactly
    newest = jnp.max(jnp.where(k == 0.0, lat, -jnp.inf), axis=-1)
    out_ref[...] = jnp.where(newest >= p.offline_ms, -1.0, score)[:, None]


@functools.partial(jax.jit, static_argnames=("p", "T", "interpret"))
def qos_score_pallas(
    lat_padded: jax.Array,  # [n_pad, T_pad] f32, server- and time-padded
    *,
    p: QosParams,
    T: int,                 # number of real (rightmost) time samples
    interpret: bool = False,
) -> jax.Array:
    n_pad, T_pad = lat_padded.shape
    assert n_pad % SERVER_TILE == 0
    grid = (n_pad // SERVER_TILE,)
    return pl.pallas_call(
        functools.partial(_qos_kernel, p=p, T=T, T_pad=T_pad),
        grid=grid,
        in_specs=[pl.BlockSpec((SERVER_TILE, T_pad), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((SERVER_TILE, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_pad, 1), jnp.float32),
        interpret=interpret,
    )(lat_padded)[:, 0]
