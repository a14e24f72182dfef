"""Discrete-time cloud simulator reproducing the paper's §V testbed.

One `lax.scan` step = one monitoring instant:

  arrivals → spot-price step → wall-clock advance (boot/billing at the
  current price) → market preemption of outbid slots → task execution with
  the rates decided last instant → workload/SLA bookkeeping → controller
  step (predict, confirm, allocate, scale) → instance start/terminate
  (spot requests go unfulfilled while the fleet is outbid).

Everything is fixed-shape and jitted; a full 30-workload × 300-tick
experiment runs in milliseconds, so the benchmark suite sweeps predictors,
policies and monitoring intervals cheaply — and ``sim.sweep`` vmaps the
*whole* run over seeds × bid levels × bid policies × fleet mixes ×
workload scenarios in one call.  The schedule is a traced
``workloads.JaxSchedule`` pytree input (padded rows masked by ``valid``),
so ``sim.scenarios`` generators can hand every grid point its own sampled
workload world without recompiling.  With the spot market live, all
Table-V instance types evolve as one
correlated price system and the fleet may be mixed-granularity: each slot
is billed/preempted at its own type's price, and every acquisition picks
the cheapest-per-CU type currently available under the bid policy.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from ..core import aimd as aimd_lib
from ..core import billing as billing_lib
from ..core import controller as ctrl
from ..core.types import (ClusterState, ControlParams, PolicyParams,
                          TenantConfig, WorkloadState, make_policy_params)
from ..obs import probes as obs_lib
from . import faults as faults_lib
from . import spot as spot_lib
from . import workloads as wl


@dataclasses.dataclass(frozen=True)
class SimConfig:
    ctrl: ctrl.ControllerConfig = ctrl.ControllerConfig()
    ticks: int = 400
    pool: int = 160               # instance slots (> N_max)
    # CUS accounting is *occupancy* (download + compute), as in the paper:
    # the per-item b_true already includes the non-compute share, so a
    # granted CU-second is consumed one-for-one.
    efficiency: float = 1.0
    exec_noise: float = 0.08      # window-level execution-time noise
    seed: int = 0
    # Appendix-A spot market; disabled by default (static list price,
    # nothing is ever preempted) so the paper's §V experiments are
    # untouched.  Enable to bill at the live spot price and lose slots
    # whose bid the market clears above.
    spot: spot_lib.SpotConfig = spot_lib.SpotConfig()
    # Multi-tenant shared fleet (``sim.tenants``): the schedule's workload
    # axis becomes ``n`` concatenated per-tenant blocks of ``max_w`` rows,
    # the allocator arbitrates hierarchically across tenants, arrivals pass
    # an admission gate, and billing is attributed per tenant in the scan
    # carry.  None (default) is the single-owner path, byte-identical to
    # every pre-tenant simulation.
    tenants: TenantConfig | None = None
    # Chaos engine (``sim.faults``): outages, storms, slot hard-kills,
    # telemetry dropouts/delays, stragglers, driven by a traced
    # ``FaultSpec`` input.  None (default) compiles the exact fault-free
    # step — zero-fault runs stay bit-identical to every pre-chaos
    # baseline.  ``FaultConfig(hardened=False)`` suffers the same faults
    # with the graceful-degradation responses switched off.
    faults: "faults_lib.FaultConfig | None" = None
    # Observability (``repro.obs``): in-scan metric probes, the decision
    # ledger, per-family counters/gauges/histograms accumulated in the
    # scan carry.  Static (hashable, part of every jit cache key, probes
    # selected per family).  None (default) compiles the exact probe-free
    # step — runs stay bit-identical to every committed baseline, the
    # same contract as ``faults=None``.
    obs: "obs_lib.ObsSpec | None" = None

    @property
    def dt(self) -> float:
        return self.ctrl.params.monitor_dt


def default_params(cfg: SimConfig) -> PolicyParams:
    """The config's hand-set policy coefficients as a ``PolicyParams``
    pytree — what every run uses when no tuner supplies candidates.
    ``bid_mult`` is the *relative* multiplier (1.0 = keep the configured /
    swept bid multiple untouched)."""
    return make_policy_params(alpha=cfg.ctrl.params.alpha,
                              beta=cfg.ctrl.params.beta,
                              bid_mult=1.0,
                              ttc_gain=cfg.spot.ttc_gain,
                              ema_alpha=cfg.spot.ema_alpha)


# The tuned-leaf defaults strip_tuned resets cache keys to.
_PARAMS0 = ControlParams()
_SPOT0 = spot_lib.SpotConfig()


def strip_tuned(cfg: SimConfig) -> SimConfig:
    """``cfg`` with the ``PolicyParams``-traced leaves struck out.

    Compilation caches key on this: the tuned coefficients (AIMD α/β, TTC
    escalation gain, EMA weight) flow through the compiled scan as traced
    inputs, so two configs that differ only there must share one compile —
    which is what lets a tuner population evaluate under one ``vmap``
    without retracing.  ``SpotConfig.bid_mult`` stays in the key: like
    ``instance``/``fleet`` it seeds the *static* runtime construction, and
    the traced counterpart is the relative ``PolicyParams.bid_mult``
    (applied on top of the runtime/axis multiple inside the scan).
    """
    params = dataclasses.replace(cfg.ctrl.params, alpha=_PARAMS0.alpha,
                                 beta=_PARAMS0.beta)
    spot = dataclasses.replace(cfg.spot, ttc_gain=_SPOT0.ttc_gain,
                               ema_alpha=_SPOT0.ema_alpha)
    return dataclasses.replace(cfg, ctrl=dataclasses.replace(
        cfg.ctrl, params=params), spot=spot)


class SummaryCarry(NamedTuple):
    """Per-run summary registers, accumulated *inside* the scan carry.

    These are the scalars ``sim.sweep.summarize`` reads out, maintained
    online so a sweep never has to materialize the O(T·W·K) per-tick trace:
    a B-point grid moves O(B) floats instead of O(B·T·W·K).  ``cum_cost``
    and ``n_preempt`` already live in ``ClusterState``; everything else the
    old trace-mode summary recomputed from ``ys`` is registered here.
    """

    max_committed: jnp.ndarray  # () running max of control-plane CUs
    price_sum: jnp.ndarray      # () Σ_t spot price of the primary type
    price_max: jnp.ndarray      # () running max of that price
    cost_at_done: jnp.ndarray   # () cum_cost registered on the tick *after*
                                #    any completion — the latest write is
                                #    exactly ``cum_cost[t_end + 1]`` of the
                                #    trace
    fire: jnp.ndarray           # () bool: a completion happened this tick,
                                #    so next tick's cum_cost is a completion
                                #    endpoint (cheap re-use of the step's
                                #    own ``done_now`` predicate instead of a
                                #    per-tick W-wide max over ``t_done``)
    # Per-tenant attribution registers (``SimConfig.tenants``); None in
    # single-owner mode, so the carry — and the compiled scan — of every
    # existing run is untouched.
    tenant: "TenantCarry | None" = None


class TenantCarry(NamedTuple):
    """Per-tenant billing-attribution registers (O(N) per run).

    Costs are integers in ``_COST_UNIT``-ths of a dollar so the conservation
    invariant — per-tick attributed cost sums *exactly* to the fleet's
    billed cost — holds in integer arithmetic, immune to float rounding.
    """

    cost_u: jnp.ndarray   # (N,) int32 attributed cost, units of 1/_COST_UNIT $
    service: jnp.ndarray  # (N,) f32 delivered CU-seconds
    q_prev: jnp.ndarray   # ()  int32 fleet cum_cost already attributed, units


# Attribution cost quantum: 0.1 milli-dollar.  f32 dollars convert to exact
# int32 units up to ~$200k cumulative — far beyond any simulated bill.
_COST_UNIT = 1e4


def summary_init(n_tenants: int | None = None) -> SummaryCarry:
    z = jnp.asarray(0.0, jnp.float32)
    tenant = None
    if n_tenants is not None:
        tenant = TenantCarry(
            cost_u=jnp.zeros((n_tenants,), jnp.int32),
            service=jnp.zeros((n_tenants,), jnp.float32),
            q_prev=jnp.asarray(0, jnp.int32))
    return SummaryCarry(max_committed=z, price_sum=z, price_max=z,
                        cost_at_done=z, fire=jnp.asarray(False),
                        tenant=tenant)


def _attribute(tc: TenantCarry, cum_cost: jnp.ndarray,
               exec_time: jnp.ndarray, valid: jnp.ndarray,
               tid: jnp.ndarray, base_w: jnp.ndarray,
               n: int) -> TenantCarry:
    """One tick of exact cost attribution (tentpole billing invariant).

    The tick's newly billed fleet cost — quantized to ``_COST_UNIT`` integer
    units — is split across tenants in proportion to delivered service
    (CU-seconds executed this tick).  On idle ticks (no service anywhere:
    warm-up, drain-out) the cost is shared base-fleet overhead, split by
    contracted weight over tenants that have any valid workload rows — so a
    tenant whose rows are all padding can never be billed.  Integer units
    are apportioned by largest-remainder rounding, which sums to the tick's
    delta exactly: Σ_i cost_u_i telescopes to the quantized fleet bill.
    """
    serv = jax.ops.segment_sum(jnp.sum(exec_time, -1), tid, num_segments=n)
    tot = jnp.sum(serv)
    elig = jax.ops.segment_sum(valid.astype(jnp.float32), tid,
                               num_segments=n) > 0.0
    w_fall = base_w * elig
    w_tot = jnp.sum(w_fall)
    fallback = jnp.where(w_tot > 0.0, w_fall / jnp.maximum(w_tot, 1e-9),
                         1.0 / n)
    share = jnp.where(tot > 0.0, serv / jnp.maximum(tot, 1e-9), fallback)

    q_now = jnp.round(cum_cost * _COST_UNIT).astype(jnp.int32)
    delta_q = q_now - tc.q_prev
    raw = delta_q.astype(jnp.float32) * share
    base = jnp.floor(raw).astype(jnp.int32)
    rem = delta_q - jnp.sum(base)
    # rem = q·n + r with 0 ≤ r < n: every tenant absorbs q units and the
    # r leftover units go to the largest fractional shares — exact for any
    # rem, including the (f32 round-up) case where Σ base overshoots.
    frac = raw - base.astype(jnp.float32)
    order = jnp.argsort(-frac)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    adj = (rem // n) + (rank < (rem % n)).astype(jnp.int32)
    return TenantCarry(cost_u=tc.cost_u + base + adj,
                       service=tc.service + serv,
                       q_prev=q_now)


class SimState(NamedTuple):
    c: ctrl.ControllerState
    work: WorkloadState
    cluster: ClusterState
    s: jnp.ndarray          # (W,) service rates decided last instant
    done_acc: jnp.ndarray   # (W,) cumulative (fractional) completions
    key: jax.Array
    t: jnp.ndarray          # () tick counter
    spot: spot_lib.SpotState
    summ: SummaryCarry
    # Chaos-engine registers; None whenever ``SimConfig.faults`` is None,
    # so the carry — and the compiled scan — of a fault-free run is
    # untouched.
    faults: "faults_lib.FaultState | None" = None
    # Observability registers (``repro.obs``); None whenever
    # ``SimConfig.obs`` is None — the same leafless-carry contract.
    obs: "obs_lib.ObsCarry | None" = None


class SimTrace(NamedTuple):
    cum_cost: jnp.ndarray    # (T,)
    n_usable: jnp.ndarray    # (T,)
    n_committed: jnp.ndarray # (T,)
    n_star: jnp.ndarray      # (T,)
    n_target: jnp.ndarray    # (T,)
    util: jnp.ndarray        # (T,) fleet CPU utilization
    b_hat: jnp.ndarray       # (T, W, K)
    b_meas: jnp.ndarray      # (T, W, K)
    reliable: jnp.ndarray    # (T, W, K)
    confirmed: jnp.ndarray   # (T, W)
    active: jnp.ndarray      # (T, W)
    remaining: jnp.ndarray   # (T, W)  Σ_k m
    spot_price: jnp.ndarray  # (T,)  $/quantum of the primary instance type
    spot_bid: jnp.ndarray    # (T,)  $/quantum new requests bid this tick
                             #       (primary type; +inf off the market)
    n_preempted: jnp.ndarray # (T,)  cumulative instances lost to the market
    t_done: jnp.ndarray      # (W,)  completion tick (final)
    work_final: WorkloadState
    violations: jnp.ndarray  # ()  TTC violations (final)


def _execute(work: WorkloadState, sched: wl.JaxSchedule, s: jnp.ndarray,
             cluster: ClusterState, done_acc: jnp.ndarray,
             cfg: SimConfig, key: jax.Array, cores):
    """Consume CUS on the fleet for one interval; emit measurements."""
    dt = cfg.dt
    n_act = billing_lib.capacity(cluster, cores)  # paid CUs incl. draining
    # Grants beyond physical capacity are scaled back proportionally.
    want = jnp.sum(s)
    cap = n_act * 1.0
    scale = jnp.where(want > cap, cap / jnp.maximum(want, 1e-9), 1.0)
    granted = s * scale * dt * cfg.efficiency           # CUS per workload

    m = work.m[:, 0]
    m0 = jnp.maximum(work.m0[:, 0], 1.0)
    p = 1.0 - m / m0                                     # completed fraction
    bias = wl.ramp(p, sched.c0, sched.p_r, sched.overshoot)
    k_exec, k_meas = jax.random.split(key)
    noise = jnp.exp(cfg.exec_noise * jax.random.normal(k_exec, m.shape))
    b_exec = work.b_true[:, 0] * bias * noise            # cost of *current* items

    possible = granted / jnp.maximum(b_exec, 1e-9)
    items_done = jnp.minimum(m, possible)
    items_done = jnp.where(work.active, items_done, 0.0)
    exec_time = items_done * b_exec

    # Window measurement: mean CUS of completed tasks.  Tasks are atomic —
    # a measurement only exists once at least one task *finished* in the
    # window, i.e. when the cumulative completion count crosses an integer.
    # Item costs are heavy-tailed (video lengths, image sizes), so the
    # window average concentrates far slower than 1/sqrt(n): we cap the
    # averaging benefit at 4 effective samples.
    done_acc_new = done_acc + items_done
    meas_mask = jnp.floor(done_acc_new) > jnp.floor(done_acc)
    meas_sigma = sched.sigma / jnp.sqrt(jnp.clip(items_done, 1.0, 4.0))
    b_meas = b_exec * jnp.exp(meas_sigma * jax.random.normal(k_meas, m.shape))

    new_m = jnp.maximum(m - items_done, 0.0)
    # Utilization: executed CUS over paid capacity this window.
    util = jnp.sum(exec_time) / jnp.maximum(n_act * dt, 1e-9)
    return (new_m[:, None], b_meas[:, None], meas_mask[:, None],
            exec_time[:, None], items_done[:, None], util, done_acc_new)


def make_step(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
              trace: bool = True,
              params: PolicyParams | None = None,
              fspec: "faults_lib.FaultSpec | None" = None) -> Callable:
    """One monitoring instant as a ``lax.scan`` step.

    ``schedule`` may be a *traced* ``JaxSchedule`` pytree — the simulator no
    longer closes over static numpy arrays, so one compiled scan serves
    every schedule of the same shape and ``sim.sweep`` can feed a different
    generated scenario to every grid point.  Padded rows (``valid=False``)
    never arrive, so they execute nothing, bill nothing and violate nothing.

    ``params`` are the tunable policy coefficients, likewise a (possibly
    traced) pytree input rather than trace-time constants: AIMD gains reach
    ``controller.step``, the TTC-escalation gain scales the urgency signal,
    and the EMA weight reaches ``spot.step`` — so ``repro.opt`` evaluates a
    whole candidate population through one compile.  ``None`` means the
    config's own values (``default_params``).

    ``trace=True`` emits the full per-tick ``ys`` dict (six (T,) series plus
    three (T, W, K) arrays once stacked) — what ``run`` and the plotting
    helpers consume.  ``trace=False`` emits nothing: the summary statistics
    accumulate in ``SimState.summ`` and the scan is ``ys``-free, which is
    what lets ``sim.sweep`` batch 10⁴–10⁵-point grids without streaming
    O(B·T·W·K) floats through memory.

    ``fspec`` carries the traced fault intensities when the config enables
    the chaos engine (``cfg.faults``); it defaults to the fault-free spec.
    Every fault branch below is a *trace-time* conditional on
    ``cfg.faults``, so a ``faults=None`` config compiles a step
    structurally identical to the pre-chaos simulator.
    """
    sched = wl.as_jax_schedule(schedule)
    use_spot = cfg.spot.enabled
    pp = default_params(cfg) if params is None else params
    tcfg = cfg.tenants
    fcfg = cfg.faults
    ocfg = cfg.obs
    hardened = fcfg is not None and fcfg.hardened
    if fcfg is not None and fspec is None:
        fspec = faults_lib.make_fault_spec()
    if tcfg is not None:
        w_rows = sched.t_arrive.shape[0]
        if w_rows != tcfg.w_total:
            raise ValueError(
                f"schedule has {w_rows} workload rows but TenantConfig "
                f"(n={tcfg.n}, max_w={tcfg.max_w}) expects {tcfg.w_total} — "
                "build the schedule with sim.tenants")
        tid = tcfg.tenant_ids()
        base_w = tcfg.weight_vec()

    def step(state: SimState, _):
        t = state.t
        with jax.named_scope("execute"):
            key, k_exec = jax.random.split(state.key)
        # Observability signal slots — assigned below where the matching
        # plane exists under this config (tenant gate, spot market, chaos
        # engine), None otherwise.  All trace-time.
        obs_rej = obs_pre = obs_kill = None

        # --- arrivals ------------------------------------------------------
        with jax.named_scope("arrivals"):
            arrive = (sched.t_arrive == t) & sched.valid
            n_shed_now = 0.0
            if hardened:
                # Deadline-aware shedding: during a sustained outage (the
                # acquisition fail-streak from last tick), refuse arrivals whose
                # requested deadline is tighter than ``shed_slack`` monitoring
                # intervals per streak tick — the platform cannot finish them
                # and admitting them would only convert them into violations.
                streak_prev = state.faults.fail_streak
                tight = sched.d_requested < fcfg.shed_slack * streak_prev * cfg.dt
                shed = (streak_prev >= fcfg.shed_after) & tight
                n_shed_now = jnp.sum((arrive & shed).astype(jnp.float32))
                arrive = arrive & ~shed
            if tcfg is not None:
                # Admission gate: a tenant already occupying ≥ adm_frac of its
                # row budget has new arrivals rejected outright (they never
                # submit, so they neither execute nor count as violations).
                # The default adm_frac = 1.0 admits everything: an arriving row
                # is itself inactive, so occupancy is at most max_w - 1.
                occ = jax.ops.segment_sum(state.work.active.astype(jnp.float32),
                                          tid, num_segments=tcfg.n)
                admit = occ < pp.adm_frac * tcfg.max_w
                # Budget cap: a tenant whose attributed bill has reached its
                # contracted cap stops admitting work (default: uncapped).
                spent = state.summ.tenant.cost_u.astype(jnp.float32) / _COST_UNIT
                admit = admit & (spent < tcfg.budget_vec())
                if ocfg is not None and (ocfg.fairshare or ocfg.ledger > 0):
                    # Rejected arrivals per tenant, read off the gate before it
                    # filters them (observability: fairshare family + ledger).
                    obs_rej = jax.ops.segment_sum(
                        (arrive & ~admit[tid]).astype(jnp.float32), tid,
                        num_segments=tcfg.n)
                arrive = arrive & admit[tid]
            work = state.work._replace(
                active=state.work.active | arrive,
                m=jnp.where(arrive[:, None], sched.m0, state.work.m),
                d=jnp.where(arrive, sched.d_requested, state.work.d),
                t_submit=jnp.where(arrive, t, state.work.t_submit),
            )
            c_state = ctrl.reset_rows(state.c, arrive)

        # --- spot market: new clearing prices for [t, t+1) ------------------
        # All Table-V types advance together (correlated log-AR(1)); slots
        # are billed and preempted at *their own type's* price, so one run
        # can hold a mixed-granularity fleet.
        with jax.named_scope("market"):
            cluster = state.cluster
            if use_spot:
                spot_state = spot_lib.step(state.spot, cfg.spot, cfg.dt,
                                           ema_alpha=pp.ema_alpha)
                slot_price = spot_state.prices[cluster.itype]   # (I,)
                cores = spot_lib.CORES_TABLE[cluster.itype]     # (I,) CUs/slot
            else:
                spot_state = state.spot
                slot_price = None
                cores = 1.0

        # --- chaos engine: this tick's fault draws --------------------------
        # One call on a dedicated PRNG chain, so enabling faults never
        # perturbs the workload, market or execution-noise streams.
        with jax.named_scope("faults"):
            if fcfg is not None:
                ftick, fstate = faults_lib.tick(state.faults, fspec, cfg.dt, t)
                # Stragglers: the slot stays billed at full price but delivers a
                # fraction of its nominal CU capacity while the episode lasts.
                exec_cores = cores * ftick.slow
            else:
                exec_cores = cores

        # --- market preemption: outbid slots are taken the instant the new
        # price clears above their bid — *before* billing advances, so a
        # reclaimed slot never renews a quantum at the very price that
        # killed it ---------------------------------------------------------
        with jax.named_scope("billing"):
            if use_spot:
                if ocfg is not None and ocfg.want_preempt:
                    # Per-type preemption counts: the same hit mask
                    # ``billing.preempt`` is about to apply (phase >= BOOTING
                    # and the clearing price strictly above the slot's bid),
                    # bucketed by instance type before the phases are wiped.
                    pb = jnp.broadcast_to(
                        jnp.asarray(slot_price, jnp.float32), cluster.bid.shape)
                    p_hit = (cluster.phase >= billing_lib.BOOTING) & (
                        pb > cluster.bid)
                    obs_pre = jax.ops.segment_sum(
                        p_hit.astype(jnp.float32), cluster.itype,
                        num_segments=spot_lib.N_TYPES)
                cluster, _ = billing_lib.preempt(cluster, slot_price)
            # --- wall clock: boots complete, billing quanta renew -----------
            cluster = billing_lib.advance(cluster, cfg.dt, cfg.ctrl.billing,
                                          price=slot_price)

        # --- execute with last instant's rates ------------------------------
        with jax.named_scope("execute"):
            (new_m, b_meas, meas_mask, exec_time, items_done, util,
             done_acc) = _execute(
                work, sched, state.s, cluster, state.done_acc, cfg, k_exec,
                exec_cores)
        with jax.named_scope("faults"):
            if fcfg is not None:
                # Slot hard-kills (storms + Poisson failures) land mid-window:
                # the killed slots were billed at the last quantum renewal and
                # burned capacity this window — exactly mid-quantum preemption
                # billing — but their in-flight work is lost.  The lost items
                # re-enter the queue exactly once: the rollback is capped at
                # this window's completions by construction (lost ≤ items_done).
                act = cluster.phase == billing_lib.ACTIVE
                slot_cu = act.astype(jnp.float32) * exec_cores
                tot_cu = jnp.sum(slot_cu)
                lost_cu = jnp.sum(jnp.where(ftick.kill, slot_cu, 0.0))
                lost_frac = jnp.where(tot_cu > 0.0,
                                      lost_cu / jnp.maximum(tot_cu, 1e-9), 0.0)
                lost = items_done * lost_frac
                new_m = new_m + lost
                done_acc = done_acc - jnp.sum(lost, -1)
                if ocfg is not None and ocfg.want_preempt:
                    # Chaos hard-kills per type, mirroring kill_slots' hit mask.
                    k_hit = (cluster.phase >= billing_lib.BOOTING) & ftick.kill
                    obs_kill = jax.ops.segment_sum(
                        k_hit.astype(jnp.float32), cluster.itype,
                        num_segments=spot_lib.N_TYPES)
                cluster, n_hit = faults_lib.kill_slots(cluster, ftick.kill)
                fstate = fstate._replace(n_killed=fstate.n_killed + n_hit)
        with jax.named_scope("execute"):
            done_acc = jnp.where(arrive, 0.0, done_acc)
            work = work._replace(m=new_m)
            busy = jnp.where(cluster.phase == billing_lib.ACTIVE, util, 0.0)
            cluster = cluster._replace(busy_frac=busy)

            # --- completions + SLA clock ------------------------------------
            done_now = work.active & (jnp.sum(work.m, -1) <= 0.0)
            work = work._replace(
                active=work.active & ~done_now,
                t_done=jnp.where(done_now, t, work.t_done),
                d=jnp.where(work.active & ~done_now,
                            work.d - cfg.dt, work.d),
            )

        # --- telemetry faults: dropouts lose fresh measurements, delays hold
        # them one instant and deliver them stale (eq. 8's lagged form makes
        # a one-tick-stale value well-formed) ---------------------------------
        with jax.named_scope("faults"):
            meas_dropped = None
            if fcfg is not None:
                b_meas, meas_mask, dropped, fstate = faults_lib.filter_telemetry(
                    fstate, ftick, fspec, b_meas, meas_mask, arrive)
                if hardened:
                    meas_dropped = dropped

        # --- control --------------------------------------------------------
        with jax.named_scope("control"):
            c_state, work, dec = ctrl.step(
                c_state, work, cluster, b_meas, meas_mask, exec_time, items_done,
                cfg.ctrl, cores=cores, pp=pp,
                tenants=(None if tcfg is None else (tid, tcfg.n, base_w)),
                meas_dropped=meas_dropped, obs=ocfg)
        with jax.named_scope("acquire"):
            if use_spot:
                rt = spot_state.rt
                # Dynamic bid policy: the TTC-aware signal is how far the most
                # behind-schedule active workload has fallen — time fraction of
                # its deadline used minus work fraction done.  On-track runs
                # keep the cheap floor bid; runs knocked behind (preemptions,
                # outages) escalate toward the on-demand cap.
                frac_time = 1.0 - work.d / jnp.maximum(work.d_requested, 1e-9)
                frac_done = 1.0 - (jnp.sum(work.m, -1)
                                   / jnp.maximum(jnp.sum(work.m0, -1), 1e-9))
                behind = jnp.where(work.active, frac_time - frac_done, -jnp.inf)
                urgency = jnp.clip(pp.ttc_gain * jnp.max(behind), 0.0, 1.0)
                bids = spot_lib.current_bids(cfg.spot, rt, spot_state, urgency)
                # Acquisitions pick the cheapest-per-CU currently-available
                # type of the fleet mix; requests are only fulfilled while the
                # market clears at or below our bid for that type.  Under the
                # chaos engine a dried-up type has no capacity at any bid: the
                # hardened controller hedges by selecting around it, the
                # unhardened one picks blind and simply fails to start.
                if fcfg is None:
                    itype_new, can_start = spot_lib.select_type(
                        spot_state.prices, bids, rt.mix)
                elif hardened:
                    itype_new, can_start = spot_lib.select_type(
                        spot_state.prices, bids, rt.mix, avail=ftick.avail)
                else:
                    itype_new, can_start = spot_lib.select_type(
                        spot_state.prices, bids, rt.mix)
                    can_start = can_start & ftick.avail[itype_new]
                allow = can_start
                if hardened:
                    # Bounded-backoff gate: after repeated failed acquisitions
                    # the controller waits out a jittered exponential delay
                    # before retrying instead of hammering the market.
                    trying = state.faults.backoff_left <= 0.0
                    allow = can_start & trying
                scale_cores = jnp.where(cluster.phase == billing_lib.OFF,
                                        spot_lib.CORES_TABLE[itype_new], cores)
                cluster = billing_lib.scale_to(
                    cluster, dec.n_target, cfg.ctrl.billing,
                    price=spot_state.prices[itype_new], bid=bids[itype_new],
                    itype=itype_new, allow_start=allow, cores=scale_cores)
            else:
                cluster = billing_lib.scale_to(cluster, dec.n_target,
                                               cfg.ctrl.billing)

        with jax.named_scope("summary"):
            # Slots started this tick carry their new type; refresh the CU
            # weights before reporting control-plane sizes.
            out_cores = (spot_lib.CORES_TABLE[cluster.itype] if use_spot
                         else cores)
            n_committed = billing_lib.committed(cluster, out_cores)
            if fcfg is not None and use_spot:
                # Fail-streak / backoff bookkeeping.  The streak counts
                # *consecutive ticks of unmet demand* — the controller wants to
                # grow the committed fleet and the market (outbid or dried up)
                # cannot fulfil it — independent of whether the backoff gate let
                # this tick's request out.  Counting ticks rather than attempts
                # matters: the shed gate and the anti-windup clamp key on the
                # streak as an outage-duration signal, and a streak that only
                # grew on try-ticks would let the backoff suppress its own
                # outage detector.
                fs_prev = state.faults
                want_grow = dec.n_target > n_committed + 0.5
                unmet = want_grow & ~can_start
                streak = jnp.where(unmet, fs_prev.fail_streak + 1.0, 0.0)
                if hardened:
                    tried = fs_prev.backoff_left <= 0.0
                    delay = aimd_lib.backoff_delay(streak, fcfg.backoff_cap,
                                                   ftick.jitter_u)
                    # A new delay starts only when a request actually went out
                    # and failed; the moment the market observably clears
                    # (``can_start`` — published prices and availability are
                    # free to read) the residual wait is void, so recovery is
                    # never stalled by a backoff scheduled during the outage.
                    backoff_left = jnp.where(
                        unmet & tried, delay,
                        jnp.where(can_start, 0.0,
                                  jnp.maximum(fs_prev.backoff_left - 1.0, 0.0)))
                    # Anti-windup: while acquisition keeps failing, hold the
                    # stored AIMD target within one additive step of what is
                    # actually committed, so recovery ramps at the normal AIMD
                    # pace instead of thundering-herd to the windup peak.
                    c_state = c_state._replace(aimd=aimd_lib.anti_windup(
                        c_state.aimd, n_committed + pp.alpha, streak > 0.0))
                else:
                    backoff_left = fs_prev.backoff_left
                fstate = fstate._replace(
                    fail_streak=streak, backoff_left=backoff_left,
                    n_shed=fstate.n_shed + n_shed_now)
            elif fcfg is not None:
                fstate = fstate._replace(n_shed=fstate.n_shed + n_shed_now)
            else:
                fstate = None
            spot_price = (spot_state.price if use_spot
                          else jnp.asarray(cfg.ctrl.billing.price_per_quantum,
                                           jnp.float32))

            # Summary registers (see SummaryCarry).  The cost register fires on
            # the tick *after* a completion — the trace index
            # ``cost_at_completion`` reads — and is overwritten whenever a later
            # completion moves that endpoint, so its final value is
            # ``cum_cost[max(t_done) + 1]``.  The fire flag re-uses this tick's
            # ``done_now`` instead of re-deriving the endpoint from a W-wide
            # ``max(t_done)`` every tick (summary-mode hot-loop cost).
            summ = SummaryCarry(
                max_committed=jnp.maximum(state.summ.max_committed, n_committed),
                price_sum=state.summ.price_sum + spot_price,
                price_max=jnp.maximum(state.summ.price_max, spot_price),
                cost_at_done=jnp.where(state.summ.fire, cluster.cum_cost,
                                       state.summ.cost_at_done),
                fire=jnp.any(done_now),
                tenant=(None if tcfg is None else _attribute(
                    state.summ.tenant, cluster.cum_cost, exec_time, sched.valid,
                    tid, base_w, tcfg.n)),
            )

        # --- observability: accumulate this tick's probe registers ----------
        # Strictly read-only — every signal is a value computed above, no
        # PRNG is consumed, nothing flows back into the simulation, so an
        # obs=None config compiles this block away entirely.
        with jax.named_scope("obs"):
            obs_c = state.obs
            if ocfg is not None:
                pr = dec.probe
                obs_viol = obs_cost = None
                if ocfg.detect is not None:
                    # Detector inputs, still read-only: TTC violations judged
                    # at completion time (the same lateness rule as
                    # ``violation_rows``; never-finished work is only judged
                    # at the horizon) and this tick's billed spend.
                    ticks_allowed = jnp.ceil(sched.d_requested / cfg.dt)
                    late = (t - work.t_submit) - ticks_allowed
                    obs_viol = jnp.sum(
                        (done_now & sched.valid & (late > 1))
                        .astype(jnp.float32))
                    obs_cost = cluster.cum_cost - state.cluster.cum_cost
                sig = obs_lib.TickSignals(
                    aimd_incr=pr.aimd_incr,
                    water_scale=pr.water_scale,
                    kalman=pr.kalman,
                    n_target=dec.n_target,
                    preempt_by_type=obs_pre,
                    kill_by_type=obs_kill,
                    adm_rejects=obs_rej,
                    queue_depth=jnp.sum(work.active.astype(jnp.float32)),
                    fail_streak=(fstate.fail_streak
                                 if (fcfg is not None and use_spot) else None),
                    n_shed=(n_shed_now if hardened else None),
                    spot_price=spot_price,
                    viol_now=obs_viol,
                    n_committed=n_committed,
                    n_unavail=(jnp.sum((~ftick.avail).astype(jnp.float32))
                               if fcfg is not None else None),
                    cost_delta=obs_cost)
                obs_c = obs_lib.update(state.obs, ocfg, t, sig,
                                       q_cap=sched.t_arrive.shape[0])

        with jax.named_scope("summary"):
            new_state = SimState(c=c_state, work=work, cluster=cluster, s=dec.s,
                                 done_acc=done_acc, key=key, t=t + 1,
                                 spot=spot_state, summ=summ, faults=fstate,
                                 obs=obs_c)
            if not trace:
                return new_state, None
            out = dict(
                cum_cost=cluster.cum_cost,
                n_usable=billing_lib.usable(cluster, out_cores),
                n_committed=n_committed,
                n_star=dec.n_star,
                n_target=dec.n_target,
                util=util,
                b_hat=dec.b_hat,
                b_meas=b_meas,
                reliable=dec.reliable,
                confirmed=work.confirmed,
                active=work.active,
                remaining=jnp.sum(work.m, -1),
                spot_price=spot_price,
                spot_bid=(bids[spot_state.rt.itype] if use_spot
                          else jnp.asarray(jnp.inf, jnp.float32)),
                n_preempted=cluster.n_preempt,
            )
            return new_state, out

    return step


def init_state(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
               seed: jnp.ndarray | int | None = None,
               spot_rt: spot_lib.SpotRuntime | None = None) -> SimState:
    """Build the t=0 state.  ``seed``, ``spot_rt`` and the schedule itself
    may be traced values — the axes ``sim.sweep`` vmaps the whole
    simulation over."""
    if seed is None:
        seed = cfg.seed
    sched = wl.as_jax_schedule(schedule)
    w, k = sched.m0.shape
    work = WorkloadState(
        active=jnp.zeros((w,), bool),
        m=jnp.zeros((w, k)),
        m0=sched.m0,
        b_true=sched.b_true,
        d=sched.d_requested,
        d_requested=sched.d_requested,
        confirmed=jnp.zeros((w,), bool),
        t_submit=jnp.full((w,), -1),
        t_done=jnp.full((w,), -1),
    )
    if spot_rt is None:
        spot_rt = spot_lib.make_runtime(cfg.spot)
    # The market gets its own PRNG chain so enabling it never perturbs the
    # execution-noise stream of the workload simulator.
    spot_state = spot_lib.init(
        spot_rt, jax.random.PRNGKey(jnp.asarray(seed) + 7919))

    cluster = billing_lib.init(cfg.pool)
    # The platform idles at N_min pre-warmed CUs (paper: N_min = 10).
    if cfg.spot.enabled:
        # Baseline market (prices = Table-V base, EMA = base, no urgency):
        # acquire the cheapest-per-CU type of the fleet mix.
        bids0 = spot_lib.current_bids(cfg.spot, spot_rt, spot_state, 0.0)
        itype0, can0 = spot_lib.select_type(spot_state.prices, bids0,
                                            spot_rt.mix)
        cluster = billing_lib.scale_to(
            cluster, jnp.asarray(cfg.ctrl.params.n_min), cfg.ctrl.billing,
            price=spot_state.prices[itype0], bid=bids0[itype0],
            itype=itype0, allow_start=can0,
            cores=spot_lib.CORES_TABLE[itype0])
    else:
        cluster = billing_lib.scale_to(
            cluster, jnp.asarray(cfg.ctrl.params.n_min), cfg.ctrl.billing)
    cluster = cluster._replace(
        boot_left=jnp.zeros_like(cluster.boot_left),
        phase=jnp.where(cluster.phase > 0, jnp.int8(billing_lib.ACTIVE),
                        cluster.phase))
    return SimState(
        c=ctrl.init(w, k, cfg.ctrl),
        work=work,
        cluster=cluster,
        s=jnp.zeros((w,)),
        done_acc=jnp.zeros((w,)),
        key=jax.random.PRNGKey(seed),
        t=jnp.asarray(0),
        spot=spot_state,
        summ=summary_init(None if cfg.tenants is None else cfg.tenants.n),
        # Measurement telemetry is (W, 1)-shaped (see ``_execute``), so the
        # pending-delivery registers match that, not the schedule's K.
        faults=(None if cfg.faults is None else faults_lib.init_state(
            seed, spot_lib.N_TYPES, w, 1, cfg.pool)),
        obs=(None if cfg.obs is None else obs_lib.init_carry(
            cfg.obs, w=w, k=sched.m0.shape[1], n_types=spot_lib.N_TYPES,
            n_tenants=(1 if cfg.tenants is None else cfg.tenants.n))),
    )


def scan_run(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
             seed: jnp.ndarray | int | None = None,
             spot_rt: spot_lib.SpotRuntime | None = None,
             trace: bool = True,
             params: PolicyParams | None = None,
             fspec: "faults_lib.FaultSpec | None" = None):
    """The raw jittable simulation: (final state, per-tick outputs).

    No ``jax.jit`` inside — callers decide the compilation boundary, which
    lets ``sim.sweep`` vmap this whole function over batched seeds, bids,
    granularities, schedules *and policy parameters* in a single compile.
    ``params`` (default: the config's values) carries the tunable policy
    coefficients as a traced pytree; its relative ``bid_mult`` scales the
    runtime's bid multiple here, so a tuner candidate bids
    ``params.bid_mult ×`` whatever the config/axis set.  With
    ``trace=False`` the scan emits no per-tick outputs (``ys`` is None):
    the run summary lives in the final state's ``summ`` carry — the
    memory-lean mode sweeps use.
    """
    sched = wl.as_jax_schedule(schedule)
    pp = default_params(cfg) if params is None else params
    if spot_rt is None:
        spot_rt = spot_lib.make_runtime(cfg.spot)
    # ``rt.bid`` (the informational static bid) is left untouched: nothing
    # in the simulation reads it — live bidding goes through current_bids,
    # which uses ``bid_mult``.
    spot_rt = spot_rt._replace(bid_mult=spot_rt.bid_mult * pp.bid_mult)
    step = make_step(sched, cfg, trace=trace, params=pp, fspec=fspec)
    state = init_state(sched, cfg, seed=seed, spot_rt=spot_rt)
    # Summary mode keeps no per-tick outputs, so unrolling pairs of steps
    # costs no memory and buys back the loop overhead that otherwise
    # leaves the register-carry scan slower than the traced one.
    unroll = 1 if trace else 2
    return jax.lax.scan(step, state, None, length=cfg.ticks, unroll=unroll)


# --------------------------------------------------------------------------
# Cached compilation: ``run``/``run_single`` used to build and jit a fresh
# closure per call, recompiling the whole simulation across repeated
# benchmark invocations.  The entry points below key one compiled callable
# on (schedule *shape*, static config, trace mode, runtime structure): the
# schedule itself is a traced input, so every schedule — and every
# generated scenario — of one shape shares a single compile.

_JIT_CACHE: dict = {}
_JIT_CACHE_MAX = 128


def _cache_put(key, fn) -> None:
    """Insert with LRU-ish eviction so a long-lived process iterating over
    many schedules/configs cannot grow the cache without bound."""
    if len(_JIT_CACHE) >= _JIT_CACHE_MAX:
        _JIT_CACHE.pop(next(iter(_JIT_CACHE)))
    _JIT_CACHE[key] = fn


def cached_scan(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
                trace: bool, with_rt: bool) -> Callable:
    """The jitted ``scan_run`` entry point for this (schedule shape, cfg,
    mode).  ``schedule`` is consulted only for its *scenario shape*
    (``workloads.schedule_shape``) — the returned callable takes the
    schedule pytree as its first argument, so same-shape schedules with
    different contents (e.g. generated scenarios) reuse one compile.  The
    cache keys on ``strip_tuned(cfg)``: the tunable policy coefficients
    are the callable's trailing ``PolicyParams`` argument, never part of
    the key, so tuner candidates share one compile too.

    ``with_rt=True`` returns ``f(sched, seed, spot_rt, params)``;
    otherwise ``f(sched, seed, params)`` (the runtime then derives from
    the config — note ``cfg.spot.bid_mult`` stays in the key for exactly
    that reason).  When the chaos engine is on (``cfg.faults`` — itself
    part of the cache key through ``strip_tuned``), the callable takes a
    trailing traced ``FaultSpec`` argument.
    """
    key = (wl.schedule_shape(schedule), strip_tuned(cfg), bool(trace),
           bool(with_rt))
    fn = _JIT_CACHE.get(key)
    if fn is None:
        if cfg.faults is not None:
            if with_rt:
                fn = jax.jit(lambda sched, seed, rt, pp, fs: scan_run(
                    sched, cfg, seed=seed, spot_rt=rt, trace=trace,
                    params=pp, fspec=fs))
            else:
                fn = jax.jit(lambda sched, seed, pp, fs: scan_run(
                    sched, cfg, seed=seed, trace=trace, params=pp, fspec=fs))
        elif with_rt:
            fn = jax.jit(lambda sched, seed, rt, pp: scan_run(
                sched, cfg, seed=seed, spot_rt=rt, trace=trace, params=pp))
        else:
            fn = jax.jit(lambda sched, seed, pp: scan_run(
                sched, cfg, seed=seed, trace=trace, params=pp))
        _cache_put(key, fn)
    return fn


def cost_at_completion(work_final: WorkloadState, cum_cost: jnp.ndarray,
                       valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """$ billed when the last workload completes, jnp-pure (shared by
    ``total_cost`` and ``sim.sweep``).  A run in which submitted work never
    finishes has no such endpoint: it is billed to the full horizon, so an
    incomplete run can never masquerade as a cheap one.  ``valid`` masks
    out padded workload rows (they can neither finish nor stay
    unfinished)."""
    submitted = work_final.t_submit >= 0
    finished = work_final.t_done >= 0
    t_done = work_final.t_done
    if valid is not None:
        submitted = submitted & valid
        t_done = jnp.where(valid, t_done, -1)
    unfinished = jnp.any(submitted & ~finished)
    t_end = jnp.max(t_done)
    idx = jnp.clip(t_end + 1, 0, cum_cost.shape[0] - 1)
    return jnp.where(unfinished | (t_end < 0), cum_cost[-1], cum_cost[idx])


def violation_rows(work_final: WorkloadState,
                   schedule: wl.Schedule | wl.JaxSchedule,
                   cfg: SimConfig,
                   valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """(W,) bool: which workload rows violated their TTC.

    ``valid`` is the explicit workload-valid mask; it defaults to the
    schedule's own mask, so padded rows never count as violations even if a
    caller hands in a hand-built final state with garbage in the padding.
    """
    sched = wl.as_jax_schedule(schedule)
    if valid is None:
        valid = sched.valid
    ticks_allowed = jnp.ceil(sched.d_requested / cfg.dt)
    submitted = (work_final.t_submit >= 0) & valid
    finished = work_final.t_done >= 0
    # Judged against the TTC *requested* at submission (with one tick of
    # grace).  A confirmed-but-extended deadline (infeasible request) is
    # therefore still counted as a violation of the original ask.
    lateness = (work_final.t_done - work_final.t_submit) - ticks_allowed
    return ((submitted & finished & (lateness > 1)) |
            (submitted & ~finished))


def count_violations(work_final: WorkloadState,
                     schedule: wl.Schedule | wl.JaxSchedule,
                     cfg: SimConfig,
                     valid: jnp.ndarray | None = None) -> jnp.ndarray:
    """TTC violations, jnp-pure (shared by ``run`` and ``sim.sweep``)."""
    return jnp.sum(violation_rows(work_final, schedule, cfg, valid=valid))


def run(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
        seed: int | None = None,
        spot_rt: spot_lib.SpotRuntime | None = None,
        params: PolicyParams | None = None,
        fspec: "faults_lib.FaultSpec | None" = None) -> SimTrace:
    s = cfg.seed if seed is None else seed
    sched = wl.as_jax_schedule(schedule)
    pp = default_params(cfg) if params is None else params
    tail: tuple = ()
    if cfg.faults is not None:
        tail = (faults_lib.make_fault_spec() if fspec is None else fspec,)
    if spot_rt is None:
        final, ys = cached_scan(sched, cfg, trace=True,
                                with_rt=False)(sched, s, pp, *tail)
    else:
        final, ys = cached_scan(sched, cfg, trace=True,
                                with_rt=True)(sched, s, spot_rt, pp, *tail)

    violations = count_violations(final.work, sched, cfg)
    return SimTrace(t_done=final.work.t_done, work_final=final.work,
                    violations=violations, **{k: ys[k] for k in ys})


def obs_report(final: SimState, cfg: SimConfig,
               schedule: wl.Schedule | wl.JaxSchedule) -> "obs_lib.ObsReport":
    """Drain a finished run's observability registers into an ObsReport.

    ``final`` is the scan's final carry (``scan_run``/``cached_scan``
    return it as the first element); the schedule supplies the queue-depth
    histogram's static bin span.  Raises if the run was probe-free.
    """
    if cfg.obs is None or final.obs is None:
        raise ValueError("run had no observability enabled — set "
                         "SimConfig.obs to an ObsSpec")
    sched = wl.as_jax_schedule(schedule)
    return obs_lib.drain(final.obs, cfg.obs, q_cap=sched.t_arrive.shape[0])


def run_obs(schedule: wl.Schedule | wl.JaxSchedule, cfg: SimConfig, *,
            seed: int | None = None,
            params: PolicyParams | None = None,
            fspec: "faults_lib.FaultSpec | None" = None,
            ) -> "tuple[SimTrace, obs_lib.ObsReport]":
    """``run`` plus the drained ObsReport, in one cached compile."""
    s = cfg.seed if seed is None else seed
    sched = wl.as_jax_schedule(schedule)
    pp = default_params(cfg) if params is None else params
    tail: tuple = ()
    if cfg.faults is not None:
        tail = (faults_lib.make_fault_spec() if fspec is None else fspec,)
    final, ys = cached_scan(sched, cfg, trace=True,
                            with_rt=False)(sched, s, pp, *tail)
    violations = count_violations(final.work, sched, cfg)
    trace = SimTrace(t_done=final.work.t_done, work_final=final.work,
                     violations=violations, **{k: ys[k] for k in ys})
    return trace, obs_report(final, cfg, sched)


def total_cost(trace: SimTrace) -> float:
    """Cumulative bill at the instant the last workload completes.

    The paper's Figs. 4-5 track cost over the experiment; the experiment
    ends when all workloads are done (the platform then sheds to N_min and
    would otherwise keep renewing idle base instances forever).  Incomplete
    runs bill to the full horizon (see ``cost_at_completion``) — check
    ``trace.violations`` alongside this number.
    """
    return float(cost_at_completion(trace.work_final, trace.cum_cost))
