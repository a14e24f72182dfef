"""The integrated CaaS control plane (paper §II-§IV, one fused step).

Per monitoring instant the platform:
  1. absorbs CUS measurements into the configured predictor (Kalman §II.A,
     or the ad-hoc / ARMA baselines of §V.B),
  2. computes r_w = Σ_k m b̂ (eq. 1), detects t_init and confirms TTCs,
  3. allocates proportional-fair service rates (eqs. 11-14),
  4. updates the CU target with the configured scaling policy (AIMD Fig. 1,
     or Reactive / MWA / LR of §V.C, or utilization-driven Autoscale),
  5. starts/terminates instances (termination = smallest a_{i,j} first).

The step is pure and fixed-shape: the surrounding environment (simulator or
the elastic TPU runtime in ``repro.ft``) drives it under ``lax.scan``.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple

import jax
import jax.numpy as jnp

from . import aimd as aimd_lib
from . import billing as billing_lib
from . import fairshare, kalman, predictors
from .types import (AimdState, ArmaState, BillingParams, ClusterState,
                    ControlParams, KalmanState, PolicyParams, PolicyState,
                    WorkloadState, required_cus)

PREDICTORS = ("kalman", "adhoc", "arma")
POLICIES = ("aimd", "reactive", "mwa", "lr", "autoscale")


@dataclasses.dataclass(frozen=True)
class ControllerConfig:
    predictor: str = "kalman"
    policy: str = "aimd"
    params: ControlParams = ControlParams()
    billing: BillingParams = BillingParams()
    # Pre-confirmation probe rate: the platform runs one task at a time per
    # unconfirmed workload to build the initial CUS estimate — a fraction of
    # one CU on average, not a dedicated instance.
    bootstrap_rate: float = 0.3
    # Autoscale baseline (§V.C): step instances on mean-CPU threshold.
    as_threshold: float = 0.20
    as_step: float = 1.0
    # AIMD base: 'committed' (booting+active; avoids double-request during
    # boot) or 'active' (paper-literal eq. 2).
    aimd_base: str = "committed"
    # Route the Kalman bank's fused eqs. 6-9 update through the Pallas
    # kernel (``repro.kernels.kalman_update``): compiled on TPU,
    # interpreter-emulated elsewhere, bit-comparable to ``kalman.step``.
    # Off by default — vmapped sweeps keep the plain jnp path.
    kalman_kernel: bool = False

    def __post_init__(self):
        # ValueError (not assert) so a misconfigured controller fails
        # identically under ``python -O`` — same path as SpotConfig.
        if self.predictor not in PREDICTORS:
            raise ValueError(f"unknown predictor {self.predictor!r}; "
                             f"choose one of {PREDICTORS}")
        if self.policy not in POLICIES:
            raise ValueError(f"unknown policy {self.policy!r}; "
                             f"choose one of {POLICIES}")
        if self.aimd_base not in ("committed", "active"):
            raise ValueError(f"unknown aimd_base {self.aimd_base!r}; "
                             "choose 'committed' or 'active'")


class ControllerState(NamedTuple):
    kf: KalmanState          # Kalman or ad-hoc filter bank (shape-shared)
    arma: ArmaState
    pol: PolicyState
    aimd: AimdState


class ControlProbe(NamedTuple):
    """Per-tick control-plane diagnostics (``repro.obs`` emission hook).

    Populated only when the caller passes an ``ObsSpec`` (each field
    further gated by its probe family — ``None`` when unwanted), so a
    probe-free controller step carries a leafless ``None`` here and
    compiles unchanged.
    """

    aimd_incr: jnp.ndarray | None = None    # () bool Fig. 1 branch taken
    water_scale: jnp.ndarray | None = None  # () f32 eqs. 13-14 rescale
    kalman: "kalman.KalmanProbe | None" = None  # innovation diagnostics


class ControlDecision(NamedTuple):
    s: jnp.ndarray           # (W,) service rates for [t, t+1)
    n_star: jnp.ndarray      # ()   N*_tot (eq. 12)
    n_target: jnp.ndarray    # ()   CU count requested for t+1
    b_hat: jnp.ndarray       # (W, K) current predictions
    reliable: jnp.ndarray    # (W, K) predictor reliability flags
    probe: ControlProbe | None = None  # obs diagnostics (None = off)


def init(w: int, k: int, cfg: ControllerConfig) -> ControllerState:
    return ControllerState(
        kf=kalman.init(w, k),
        arma=predictors.arma_init(w, k),
        pol=aimd_lib.policy_init(),
        aimd=aimd_lib.aimd_init(cfg.params.n_min),
    )


def reset_rows(state: ControllerState, rows: jnp.ndarray) -> ControllerState:
    """Clear predictor state for newly (re)submitted workload rows."""
    return state._replace(
        kf=kalman.reset_rows(state.kf, rows),
        arma=predictors.arma_reset_rows(state.arma, rows),
    )


def step(state: ControllerState,
         work: WorkloadState,
         cluster: ClusterState,
         b_meas: jnp.ndarray,        # (W, K) fresh CUS measurements
         meas_mask: jnp.ndarray,     # (W, K) bool
         exec_time: jnp.ndarray,     # (W, K) CU-seconds consumed in window
         items_done: jnp.ndarray,    # (W, K) completions in window
         cfg: ControllerConfig,
         cores: jnp.ndarray | float | None = None,  # CUs per instance/slot
         pp: PolicyParams | None = None,  # traced policy gains (tuning)
         tenants: tuple | None = None,    # (tenant_id (W,), n, base_w (N,))
         meas_dropped: jnp.ndarray | None = None,  # (W, K) lost telemetry
         obs=None,  # static ObsSpec (repro.obs): emit ControlDecision.probe
         ) -> tuple[ControllerState, WorkloadState, ControlDecision]:
    p = cfg.params
    # CUs per instance — a traced scalar when the spot fleet's granularity
    # is a sweep axis (sim.sweep vmaps over it), or a per-slot (I,) vector
    # for mixed-granularity fleets; the caller owns keeping it consistent
    # with the execution and scaling planes.  All control arithmetic below
    # is in CU space, so a preemption that knocks out one m4.10xlarge is
    # seen as a 40-CU capacity loss and AIMD re-grows the fleet additively,
    # exactly as it reacts to any shortfall — possibly with instances of a
    # *different* type, if that is what the market now sells cheapest.
    if cores is None:
        cores = 1.0

    # -- 1. predictor update ------------------------------------------------
    # ``meas_dropped`` marks filters whose fresh measurement was lost to a
    # telemetry dropout (chaos engine, hardened mode): the Kalman bank coasts
    # there with inflated covariance instead of silently standing still.
    k_probe = None
    if cfg.predictor == "kalman":
        with jax.named_scope("kalman"):
            if obs is not None and obs.want_kalman:
                # Innovation/NIS from the *pre-update* bank — the residual
                # eq. 8 is about to correct with (trace-time gated:
                # probe-free configs compile the exact historical update).
                k_probe = kalman.probe(state.kf, meas_mask, p)
            kf = kalman.step(state.kf, b_meas, meas_mask, p,
                             use_kernel=cfg.kalman_kernel,
                             dropped=meas_dropped)
        arma = state.arma
        b_hat, reliable = kf.b_hat, kf.reliable
    elif cfg.predictor == "adhoc":
        kf = predictors.adhoc_step(state.kf, b_meas, meas_mask, p)
        arma = state.arma
        b_hat, reliable = kf.b_hat, kf.reliable
    else:  # arma
        kf = state.kf
        arma = predictors.arma_step(state.arma, exec_time, items_done,
                                    work.m0, p)
        b_hat, reliable = arma.b_hat, arma.reliable

    # -- 2. demand + TTC confirmation (§II.B) --------------------------------
    r = required_cus(work.m, b_hat)                        # eq. 1
    w_reliable = jnp.all(reliable | (work.m0 == 0), axis=-1) & jnp.any(
        work.m0 > 0, axis=-1)
    newly_conf = work.active & w_reliable & ~work.confirmed
    d_conf = fairshare.confirm_ttc(r, work.d, newly_conf, p)
    d = jnp.where(newly_conf, d_conf, work.d)
    confirmed = work.confirmed | newly_conf
    work = work._replace(d=d, confirmed=confirmed)

    # -- 3. proportional-fair service rates (eqs. 11-14) ---------------------
    n_usable = billing_lib.usable(cluster, cores)
    sched = work.active & confirmed
    if tenants is None:
        alloc = fairshare.allocate(r, d, sched, n_usable, p, pp=pp)
    else:
        # Multi-tenant shared fleet: the allocation is hierarchical (fleet
        # → tenant weight → per-task eqs. 13-14).  A single tenant routes
        # back through ``allocate`` inside, bit-identically.
        tid, n_tenants, base_w = tenants
        alloc = fairshare.allocate_tenants(r, d, sched, n_usable, p,
                                           tid, n_tenants, base_w, pp=pp)
    # Pre-confirmation bootstrap: run a trickle so measurements arrive.
    boot = work.active & ~confirmed
    s = jnp.where(boot, cfg.bootstrap_rate, alloc.s)
    # Demand seen by the scaler includes the bootstrap trickle.
    n_star = alloc.n_star + jnp.sum(jnp.where(boot, cfg.bootstrap_rate, 0.0))

    # -- 4. scaling policy ---------------------------------------------------
    pol = aimd_lib.policy_push(state.pol, n_star)
    n_base = (billing_lib.committed(cluster, cores)
              if cfg.aimd_base == "committed" else n_usable)
    aimd_state = aimd_lib.aimd_step(state.aimd, n_base, n_star, p, pp=pp)
    if cfg.policy == "aimd":
        n_target = aimd_state.n_target
    elif cfg.policy == "reactive":
        n_target = aimd_lib.reactive_target(pol, p)
    elif cfg.policy == "mwa":
        n_target = aimd_lib.mwa_target(pol, p)
    elif cfg.policy == "lr":
        n_target = aimd_lib.lr_target(pol, p)
    else:  # autoscale: ±step instances on mean CPU utilization (§V.C)
        active_mask = (cluster.phase == billing_lib.ACTIVE)
        n_act = jnp.maximum(jnp.sum(active_mask.astype(jnp.float32)), 1.0)
        util = jnp.sum(cluster.busy_frac * active_mask) / n_act
        n_now = billing_lib.committed(cluster, cores)
        any_work = jnp.any(work.active)
        n_target = jnp.where(util > cfg.as_threshold,
                             n_now + cfg.as_step, n_now - cfg.as_step)
        n_target = jnp.where(any_work, n_target, n_now - cfg.as_step)
        n_target = jnp.clip(n_target, 1.0, p.n_max)

    # -- 5. observability probe (repro.obs) ----------------------------------
    # Assembled only under an ObsSpec; each field further gated by its
    # family so an enabled probe subset compiles exactly its own ops.
    probe = None
    if obs is not None:
        probe = ControlProbe(
            aimd_incr=(aimd_lib.increase_branch(n_base, n_star)
                       if obs.want_aimd else None),
            water_scale=(alloc.scale if obs.want_fairshare else None),
            kalman=k_probe)

    new_state = ControllerState(kf=kf, arma=arma, pol=pol, aimd=aimd_state)
    return new_state, work, ControlDecision(
        s=s, n_star=n_star, n_target=n_target, b_hat=b_hat,
        reliable=reliable, probe=probe)
