"""Adaptive fusion-weight control (paper Sec. VI "Advanced joint optimization").

The paper fixes alpha/beta per deployment mode and names adaptive trade-off
learning as future work.  Two implementations live here:

1. `AdaptiveSonarRouter` — the minimal scalar feedback controller: a single
   beta in [beta_min, beta_max] nudged by the outcome stream (failures push
   it up multiplicatively, SLO soft-misses at half that pressure, healthy
   stretches recover it monotonically toward the configured target).

2. **SONAR-ADAPT** — the production version: the full weight vector
   w = [alpha, beta, gamma, delta] held in a pure-functional `AdaptState`
   pytree and updated by exponentiated-gradient (EG) REINFORCE steps on the
   shaped reward the serving/traffic layers already emit.  The update is a
   handful of FLOPs over a fixed-size feedback bucket, run by one donated
   jit program (`adapt_update`) for the scalar router and both routing
   engines; the engines pass the live weights to their routed programs as
   data, so a weight update never recompiles them.

Update rule (doctested in docs/algorithms.md):

    r      = 0                      if the call failed
           = min(slo_ms / lat, 1)   otherwise (1 inside the SLO)
    g      = mean_valid[(r - baseline) * f]          f = [C, N, -U, -R]
    w     <- clip(w * exp(lr * g), w_min, w_max)
    baseline <- rho * baseline + (1 - rho) * mean_valid[r]

With lr = 0 the update is the bitwise identity (x * exp(0) = x and the
clip is a no-op for in-range weights), which is what the zero-knob
byte-identity tests in tests/test_parity_prop.py pin across all four
routing paths.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.qos import load_penalty, rtt_penalty
from repro.core.routing import (
    Decision,
    RoutingConfig,
    SonarGeoRouter,
    SonarRouter,
)

# Fixed feedback-batch width: outcomes are padded (valid-masked) to this
# bucket so an engine's update compiles ONCE instead of once per feedback
# count (the same bucketing trick as the serving pad_to path).
FEEDBACK_BUCKET = 64


# ---------------------------------------------------------------------------
# Scalar feedback controller (the seed design, kept + hardened)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class AdaptiveConfig:
    target_alpha: float = 0.5        # semantic weight the controller relaxes to
    beta_min: float = 0.2
    beta_max: float = 0.9
    failure_gain: float = 1.5        # multiplicative beta bump on a failure
    soft_gain: Optional[float] = None  # on an SLO miss; None = half pressure,
                                       # i.e. 1 + (failure_gain - 1) / 2
    recovery: float = 0.02           # additive beta step per healthy pick
    latency_slo_ms: float = 200.0

    @property
    def effective_soft_gain(self) -> float:
        if self.soft_gain is not None:
            return self.soft_gain
        return 1.0 + 0.5 * (self.failure_gain - 1.0)

    @property
    def target_beta(self) -> float:
        """The recovery target, clamped into the controller's range."""
        return float(
            np.clip(1.0 - self.target_alpha, self.beta_min, self.beta_max)
        )


class AdaptiveSonarRouter:
    """SONAR with outcome-feedback weight adaptation."""

    def __init__(self, servers: Sequence, cfg: RoutingConfig = RoutingConfig(),
                 adapt: AdaptiveConfig = AdaptiveConfig()):
        self.adapt = adapt
        self.base_cfg = cfg
        # start at the recovery target so beta never begins out of range
        self.beta = adapt.target_beta
        self._router = SonarRouter(servers, cfg)
        self.name = "AdaptiveSONAR"
        self.history: list = []

    # Router protocol -------------------------------------------------------
    @property
    def cfg(self) -> RoutingConfig:
        return dataclasses.replace(
            self.base_cfg, alpha=1.0 - self.beta, beta=self.beta
        )

    @property
    def index(self):
        return self._router.index

    def select(
        self,
        query: str,
        latency_hist: Optional[np.ndarray] = None,
        server_load: Optional[np.ndarray] = None,
        telemetry_age_s: Optional[np.ndarray] = None,
        failed_mask: Optional[np.ndarray] = None,
        client_rtt_ms: Optional[np.ndarray] = None,
        audit=None,
    ) -> Decision:
        self._router.cfg = self.cfg
        return self._router.select(
            query, latency_hist, server_load,
            telemetry_age_s=telemetry_age_s, failed_mask=failed_mask,
            client_rtt_ms=client_rtt_ms, audit=audit,
        )

    # Feedback --------------------------------------------------------------
    def observe(self, latency_ms: float, online: bool):
        a = self.adapt
        if not online:
            self.beta = min(self.beta * a.failure_gain, a.beta_max)
        elif latency_ms > a.latency_slo_ms:
            # soft miss: half the failure pressure by default
            self.beta = min(self.beta * a.effective_soft_gain, a.beta_max)
        else:
            # monotone one-step approach toward the clamped target: never
            # overshoots and never leaves [beta_min, beta_max]
            target = a.target_beta
            if self.beta > target:
                self.beta = max(self.beta - a.recovery, target)
            elif self.beta < target:
                self.beta = min(self.beta + a.recovery, target)
        self.history.append(self.beta)


# ---------------------------------------------------------------------------
# SONAR-ADAPT: pure-functional exponentiated-gradient weight adaptation
# ---------------------------------------------------------------------------

class AdaptConfig(NamedTuple):
    """Hashable knobs of the EG update (static under jit)."""

    lr: float = 0.05                 # EG step size; 0 freezes the weights
    baseline_rho: float = 0.9        # reward-EMA smoothing
    w_min: float = 0.05              # multiplicative-update floor
    w_max: float = 2.0               # and ceiling
    slo_ms: float = 500.0            # reward-shaping latency target


class AdaptState(NamedTuple):
    """The learner state — a pytree threaded through (and donated by)
    the jit update."""

    weights: jax.Array               # f32 [4] = [alpha, beta, gamma, delta]
    baseline: jax.Array              # f32 []  reward EMA (advantage baseline)
    step: jax.Array                  # i32 []  applied non-empty updates


def init_state(
    cfg: RoutingConfig = RoutingConfig(),
    acfg: AdaptConfig = AdaptConfig(),
) -> AdaptState:
    """Start from the hand-tuned weights of ``cfg`` — with lr = 0 the
    learner therefore *is* the hand-tuned variant, forever."""
    w = np.asarray(
        [cfg.alpha, cfg.beta, cfg.gamma, cfg.delta], np.float32
    )
    assert np.all(w >= acfg.w_min) and np.all(w <= acfg.w_max), (
        "initial weights must sit inside [w_min, w_max] so the zero-lr "
        "update is the bitwise identity"
    )
    return AdaptState(
        weights=jnp.asarray(w),
        baseline=jnp.zeros((), jnp.float32),
        step=jnp.zeros((), jnp.int32),
    )


def shape_reward(latency_ms: float, ok: bool, slo_ms: float) -> float:
    """Scalar reward: 0 on failure, 1 inside the SLO, soft partial credit
    ``slo / latency`` beyond it (host-side; the shaped values enter the
    jit update as a plain f32 vector)."""
    if not ok:
        return 0.0
    lat = max(float(latency_ms), 1e-6)
    return min(slo_ms / lat, 1.0)


def decision_feats(
    expertise: float,
    network: float,
    load_pen: float = 0.0,
    rtt_pen: float = 0.0,
) -> np.ndarray:
    """Feature vector f = [C, N, -U, -R] at the winning candidate — the
    per-weight sensitivities of the fused score S = w . f."""
    return np.asarray(
        [expertise, network, -load_pen, -rtt_pen], np.float32
    )


def pad_feedback(
    rewards: Sequence[float],
    feats: Sequence[np.ndarray],
    bucket: int = FEEDBACK_BUCKET,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad a variable-length outcome list to the fixed bucket with a
    validity mask, so the compiled update never re-specializes on count."""
    n = min(len(rewards), bucket)
    r = np.zeros((bucket,), np.float32)
    f = np.zeros((bucket, 4), np.float32)
    v = np.zeros((bucket,), np.float32)
    if n:
        r[:n] = np.asarray(rewards[:n], np.float32)
        f[:n] = np.asarray(feats[:n], np.float32).reshape(n, 4)
        v[:n] = 1.0
    return r, f, v


def _adapt_step(
    state: AdaptState,
    rewards: jax.Array,              # f32 [B] shaped rewards
    feats: jax.Array,                # f32 [B, 4] = [C, N, -U, -R] at winner
    valid: jax.Array,                # f32 [B] 1 = real outcome, 0 = pad
    acfg: AdaptConfig,
) -> AdaptState:
    """One masked-mean EG step.  An all-pad bucket returns the state
    bitwise unchanged; with lr = 0 so does any bucket (x * exp(0) = x and
    the clip is a no-op for in-range weights)."""
    r = jnp.asarray(rewards, jnp.float32)
    f = jnp.asarray(feats, jnp.float32)
    v = jnp.asarray(valid, jnp.float32)
    n = jnp.sum(v)
    has = n > 0.0
    denom = jnp.maximum(n, 1.0)
    adv = (r - state.baseline) * v                       # [B]
    g = jnp.sum(adv[:, None] * f, axis=0) / denom        # [4]
    w = jnp.clip(
        state.weights * jnp.exp(acfg.lr * g), acfg.w_min, acfg.w_max
    )
    mean_r = jnp.sum(r * v) / denom
    baseline = (
        acfg.baseline_rho * state.baseline
        + (1.0 - acfg.baseline_rho) * mean_r
    )
    return AdaptState(
        weights=jnp.where(has, w, state.weights),
        baseline=jnp.where(has, baseline, state.baseline),
        step=state.step + has.astype(jnp.int32),
    )


@functools.partial(
    jax.jit, static_argnames=("acfg",), donate_argnums=(0,)
)
def _adapt_update_jit(state, rewards, feats, valid, *, acfg):
    # trace-time module-global lookup: monkeypatching `_adapt_step` (plus
    # jax.clear_caches()) swaps the math, which the adaptation-mutation
    # tests rely on
    return _adapt_step(state, rewards, feats, valid, acfg)


def adapt_update(
    state: AdaptState,
    rewards: np.ndarray,
    feats: np.ndarray,
    valid: np.ndarray,
    acfg: AdaptConfig,
) -> AdaptState:
    """Jit'd update (state donated): the scalar router's per-outcome
    step, and the routing engines' fold of their queued feedback, one
    ``FEEDBACK_BUCKET`` a step, before each route."""
    return _adapt_update_jit(state, rewards, feats, valid, acfg=acfg)


def weights_cfg(cfg: RoutingConfig, state: AdaptState) -> RoutingConfig:
    """Re-derive a RoutingConfig carrying the live learned weights."""
    w = np.asarray(state.weights, np.float32)
    return dataclasses.replace(
        cfg, alpha=float(w[0]), beta=float(w[1]),
        gamma=float(w[2]), delta=float(w[3]),
    )


class SonarAdaptRouter(SonarGeoRouter):
    """SONAR-ADAPT: every fusion extension on, weights learned online.

    Structurally this is SONAR-GEO + staleness + failover, so fed exactly
    the inputs of any hand-tuned variant (and with matching weights) it
    computes the identical fusion — the reduction the zero-lr
    byte-identity tests pin.  The weight vector lives in an `AdaptState`
    updated by `_adapt_step` on each observed outcome.
    """

    name = "SONAR-ADAPT"
    uses_staleness = True
    uses_failover = True

    def __init__(
        self,
        servers: Sequence,
        cfg: RoutingConfig = RoutingConfig(),
        adapt: AdaptConfig = AdaptConfig(),
        index=None,
    ):
        super().__init__(servers, cfg, index=index)
        self.base_cfg = cfg
        self.adapt_cfg = adapt
        self.state = init_state(cfg, adapt)
        self.last_feats: Optional[np.ndarray] = None
        self.weight_history: list = []

    @property
    def weights(self) -> np.ndarray:
        return np.asarray(self.state.weights, np.float32)

    def select(
        self,
        query: str,
        latency_hist: Optional[np.ndarray] = None,
        server_load: Optional[np.ndarray] = None,
        telemetry_age_s: Optional[np.ndarray] = None,
        failed_mask: Optional[np.ndarray] = None,
        client_rtt_ms: Optional[np.ndarray] = None,
        audit=None,
    ) -> Decision:
        if self.adapt_cfg.lr != 0.0:
            self.cfg = weights_cfg(self.base_cfg, self.state)
        d = super().select(
            query, latency_hist, server_load,
            telemetry_age_s=telemetry_age_s, failed_mask=failed_mask,
            client_rtt_ms=client_rtt_ms, audit=audit,
        )
        # stash f = [C, N, -U, -R] at the winner for the next observe()
        u = 0.0
        if (
            self.uses_load and server_load is not None
            and self.cfg.gamma != 0.0
        ):
            rho = np.asarray(server_load, np.float32)[d.server_idx]
            u = float(load_penalty(rho, self.cfg.load_knee,
                                   self.cfg.load_sharp))
        r = 0.0
        if (
            self.uses_rtt and client_rtt_ms is not None
            and self.cfg.delta != 0.0
        ):
            rtt = np.asarray(client_rtt_ms, np.float32)[d.server_idx]
            r = float(rtt_penalty(rtt, self.cfg.rtt_scale_ms))
        self.last_feats = decision_feats(d.expertise, d.network, u, r)
        return d

    # Feedback --------------------------------------------------------------
    def observe_outcome(
        self,
        latency_ms: float,
        ok: bool = True,
        feats: Optional[np.ndarray] = None,
    ) -> None:
        """Apply one EG step from a completed call's outcome."""
        if feats is None:
            feats = self.last_feats
        if feats is None or self.adapt_cfg.lr == 0.0:
            return
        reward = shape_reward(latency_ms, ok, self.adapt_cfg.slo_ms)
        r, f, v = pad_feedback([reward], [np.asarray(feats)], 1)
        self.state = adapt_update(self.state, r, f, v, self.adapt_cfg)
        self.weight_history.append(self.weights)

    def observe(self, latency_ms: float, online: bool) -> None:
        """Agent-loop feedback protocol (duck-typed by `repro.agent`)."""
        self.observe_outcome(latency_ms, ok=online)


# scalar-path registration (routing.make_router lazily imports this module
# to resolve the name, so `make_router("sonar_adapt", ...)` always works)
from repro.core import routing as _routing  # noqa: E402

_routing.ALGORITHMS.setdefault("sonar_adapt", SonarAdaptRouter)
