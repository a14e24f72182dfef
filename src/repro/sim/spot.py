"""JAX-native spot-market dynamics (paper Appendix A, Fig. 6 / Table V).

The paper's empirical findings, as a generative price process:

  * spot price scales ~linearly with the CU count of the instance type;
  * price *volatility* also grows with CU count — the single-CU m3.medium
    never exceeded $0.01 over three months, while m4.10xlarge spiked hard;
  * sparse demand spikes multiply the price several-fold, increasingly
    often for large instances;
  * the Table-V types live in one region and co-move: a demand shock that
    lifts m3.xlarge lifts its neighbours too.

Price model: *all* Table-V types evolve together as one correlated
log-AR(1) system around their base prices.  Each type's log-deviation is
driven by a shared market factor plus idiosyncratic noise,

    eps_i = sqrt(corr) * eps_market + sqrt(1 - corr) * eps_i_own,

so the cross-type correlation of log-price increments is ``corr`` while
every marginal remains exactly the single-type process of the original
model (eps_i is still N(0, 1)).  The AR coefficient and innovation are
rescaled with the step size so the stationary log-price distribution is
invariant to the monitoring interval, and demand spikes are a per-type
two-state process — arriving at ``p_spike`` per hour, lasting one hour in
expectation — so the spiked-time fraction is interval-invariant too.

Everything here is pure jnp on fixed shapes: a full multi-type price path
is one ``lax.scan``, and every function is ``vmap``-able over
``SpotRuntime`` — which is how ``sim.sweep`` batches Monte-Carlo sweeps
over seeds × bid policies × fleet mixes in a single jitted call.

Bid semantics (EC2 2015): while spot price ≤ bid you hold the instance and
pay the *current* spot price per started quantum; the instant price > bid
the instance is reclaimed (``core.billing.preempt``) and new requests at
that bid go unfulfilled until the price falls back.  A request's bid is
fixed at request time — dynamic policies change the bid attached to *new*
requests, never to running instances.

Bid policies (``BID_POLICIES``, evaluated per scan step by
``current_bids``):

  * ``multiple``   — static ``bid_mult`` × base spot price (the paper's
                     fixed-bid setting);
  * ``on_demand``  — bid the on-demand price: the classic
                     never-lose-capacity cap;
  * ``ttc``        — TTC-aware: start at the static bid and raise it
                     toward the on-demand cap as workloads fall behind
                     schedule (urgency = ttc_gain × max over active
                     workloads of time-fraction-used − work-fraction-done,
                     so an on-track fleet keeps bidding the cheap floor);
  * ``ema``        — market-aware: bid ``bid_mult`` × a running price EMA
                     (capped at on-demand), so the fleet tracks the calm
                     price level, sheds during spikes, and re-acquires the
                     moment the market falls back.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp

# Appendix A, Table V (North Virginia, 2015-07-10).
#                  cores  on_demand   spot
INSTANCE_TYPES = {
    "m3.medium":    (1,     0.067,      0.0081),
    "m3.large":     (2,     0.133,      0.0173),
    "m3.xlarge":    (4,     0.266,      0.0333),
    "m3.2xlarge":   (8,     0.532,      0.0660),
    "m4.4xlarge":   (16,    1.008,      0.1097),
    "m4.10xlarge":  (40,    2.520,      0.5655),
}
INSTANCE_NAMES = tuple(INSTANCE_TYPES)
N_TYPES = len(INSTANCE_NAMES)

# Same table as jnp constants, indexable by a *traced* instance-type id —
# the axis sim.sweep vmaps over.
CORES_TABLE = jnp.asarray([v[0] for v in INSTANCE_TYPES.values()],
                          jnp.float32)
ON_DEMAND_TABLE = jnp.asarray([v[1] for v in INSTANCE_TYPES.values()],
                              jnp.float32)
SPOT_BASE_TABLE = jnp.asarray([v[2] for v in INSTANCE_TYPES.values()],
                              jnp.float32)

BID_POLICIES = ("multiple", "on_demand", "ttc", "ema")


def instance_index(instance: str) -> int:
    if instance not in INSTANCE_TYPES:
        raise ValueError(f"unknown instance type {instance!r}; "
                         f"Table V has {INSTANCE_NAMES}")
    return INSTANCE_NAMES.index(instance)


def bid_policy_index(policy: str) -> int:
    if policy not in BID_POLICIES:
        raise ValueError(f"unknown bid policy {policy!r}; "
                         f"choose one of {BID_POLICIES}")
    return BID_POLICIES.index(policy)


def fleet_mask(fleet: Sequence[str | int]) -> jnp.ndarray:
    """(T,) float32 membership mask of a fleet mix over the Table-V types."""
    mask = [0.0] * N_TYPES
    for member in fleet:
        idx = (instance_index(member) if isinstance(member, str)
               else int(member))
        mask[idx] = 1.0
    return jnp.asarray(mask, jnp.float32)


def by_type(table: jnp.ndarray, itype: jnp.ndarray) -> jnp.ndarray:
    """``table[itype]`` for a (T,) per-type table and type ids of any shape.

    A static select chain over the ``N_TYPES`` types, so it lowers to
    elementwise compares and selects: under ``vmap`` with a batched table
    the indexing form becomes a per-row gather, which the TPU runs as a
    scalar loop.  Each result is the table entry itself, bit for bit
    (``inf``, ``-0.0`` and ``nan`` included).  Ids must lie in
    ``[0, N_TYPES)``.
    """
    out = table[0]
    for k in range(1, N_TYPES):
        out = jnp.where(itype == k, table[k], out)
    return out


@dataclasses.dataclass(frozen=True)
class SpotConfig:
    """Static knobs of the market process (closed over at trace time)."""

    enabled: bool = False
    instance: str = "m3.medium"   # primary instance type (granularity axis)
    # Allowed Table-V types of the fleet; None = single-type (``instance``).
    # With more than one member, every acquisition picks the
    # cheapest-per-CU type whose current price is at or below our bid.
    fleet: tuple[str, ...] | None = None
    bid_policy: str = "multiple"  # one of BID_POLICIES
    bid_mult: float = 1.5         # bid = bid_mult × base (or × EMA) price
    rho: float = 0.97             # hourly AR(1) coefficient (market.py legacy)
    vol0: float = 0.01            # hourly log-volatility floor ...
    vol_scale: float = 0.035      # ... + vol_scale · log2(cores + 1)
    p_spike_per_core: float = 0.002   # hourly demand-spike probability / core
    spike_lo: float = 2.0         # spike multiplier ~ U[spike_lo, spike_hi]
    spike_hi: float = 8.0
    spike_hours: float = 1.0      # mean spike duration (hours); >1 makes
                                  # holding through a spike renew several
                                  # quanta at the spiked price, so
                                  # shedding-and-rebuying can pay off
    # Cross-type coupling: correlation of log-price increments between any
    # two Table-V types (0 = independent markets, →1 = one shared market).
    corr: float = 0.6
    # Per-hour weight of the running price EMA the 'ema' policy bids on.
    ema_alpha: float = 0.3
    # TTC-aware escalation gain: urgency = ttc_gain × how far the most
    # behind-schedule active workload has fallen (time fraction used minus
    # work fraction done), clipped to [0, 1].  An on-track fleet keeps the
    # floor bid; one knocked behind by preemptions escalates toward the
    # on-demand cap.
    ttc_gain: float = 4.0

    def __post_init__(self):
        # ValueError (not assert) so misconfigured sweeps fail identically
        # under ``python -O`` — same path as ``instance_index``.
        bid_policy_index(self.bid_policy)
        instance_index(self.instance)
        for member in self.fleet or ():
            instance_index(member)
        if not 0.0 <= self.corr < 1.0:
            raise ValueError(f"corr must be in [0, 1), got {self.corr}")
        if not self.spike_hours > 0.0:
            raise ValueError(
                f"spike_hours must be positive, got {self.spike_hours}")
        if not 0.0 < self.ema_alpha <= 1.0:
            raise ValueError(
                f"ema_alpha must be in (0, 1], got {self.ema_alpha}")


class SpotRuntime(NamedTuple):
    """Per-run fleet constants as traced values (the vmap axes).

    ``itype``/``cores``/``base_price``/``on_demand``/``bid`` describe the
    *primary* type — the single-type view legacy callers and the trace
    outputs use.  ``mix`` is the fleet-membership mask the acquisition
    step chooses from, ``policy`` the BID_POLICIES id, ``bid_mult`` the
    static multiple (also the EMA multiple and the TTC floor).
    """

    itype: jnp.ndarray       # () int32 primary index into the Table-V arrays
    cores: jnp.ndarray       # () CUs per primary instance
    base_price: jnp.ndarray  # () $ / instance-quantum, primary spot baseline
    on_demand: jnp.ndarray   # () $ / instance-quantum, primary on-demand
    bid: jnp.ndarray         # () static $ bid of the primary type (info)
    bid_mult: jnp.ndarray    # () bid as a multiple of base (or EMA) price
    policy: jnp.ndarray      # () int32 index into BID_POLICIES
    mix: jnp.ndarray         # (T,) float32 fleet-membership mask


class SpotState(NamedTuple):
    """Multi-type market state carried through the simulator scan."""

    x: jnp.ndarray           # (T,) log-deviations of the correlated AR(1)
    prices: jnp.ndarray      # (T,) current $ / instance-quantum per type
    spike_mult: jnp.ndarray  # (T,) active demand-spike multiplier (1 = calm)
    ema: jnp.ndarray         # (T,) running price EMA (the 'ema' bid policy)
    key: jax.Array           # market-private PRNG chain (keeps the
                             # simulator's execution-noise stream untouched)
    rt: SpotRuntime

    @property
    def price(self) -> jnp.ndarray:
        """() current price of the run's *primary* instance type."""
        return self.prices[self.rt.itype]


def _vol_table(cfg: SpotConfig) -> jnp.ndarray:
    """(T,) hourly log-volatility per type (CU-proportional, Fig. 6)."""
    return cfg.vol0 + cfg.vol_scale * jnp.log2(CORES_TABLE + 1.0)


def _p_spike_table(cfg: SpotConfig) -> jnp.ndarray:
    """(T,) hourly demand-spike probability per type."""
    return cfg.p_spike_per_core * CORES_TABLE


def make_runtime(cfg: SpotConfig,
                 itype: jnp.ndarray | int | None = None,
                 bid_mult: jnp.ndarray | float | None = None,
                 policy: jnp.ndarray | int | str | None = None,
                 mix: jnp.ndarray | None = None) -> SpotRuntime:
    """Resolve the fleet constants for one run.

    ``itype``, ``bid_mult``, ``policy`` and ``mix`` may be traced — these
    are the hooks ``sim.sweep`` uses to vmap one jitted simulation over
    instance granularities, bid levels, bid policies and fleet mixes.
    """
    if itype is None:
        itype = instance_index(cfg.fleet[0] if cfg.fleet else cfg.instance)
    itype = jnp.asarray(itype, jnp.int32)
    if mix is None:
        if cfg.fleet:
            mix = fleet_mask(cfg.fleet)
        else:
            mix = (jnp.arange(N_TYPES) == itype).astype(jnp.float32)
    mix = jnp.asarray(mix, jnp.float32)
    if policy is None:
        policy = bid_policy_index(cfg.bid_policy)
    elif isinstance(policy, str):
        policy = bid_policy_index(policy)
    policy = jnp.asarray(policy, jnp.int32)
    if bid_mult is None:
        bid_mult = cfg.bid_mult
    bid_mult = jnp.asarray(bid_mult, jnp.float32)

    cores = CORES_TABLE[itype]
    base = SPOT_BASE_TABLE[itype]
    on_demand = ON_DEMAND_TABLE[itype]
    # Informational static bid of the primary type under the *config's*
    # policy (dynamic policies start here at t=0, urgency 0, EMA = base).
    if cfg.bid_policy == "on_demand":
        bid = on_demand * jnp.ones_like(base)
    else:
        bid = bid_mult * base
    return SpotRuntime(itype=itype, cores=cores, base_price=base,
                       on_demand=on_demand, bid=bid, bid_mult=bid_mult,
                       policy=policy, mix=mix)


def init(rt: SpotRuntime, key: jax.Array) -> SpotState:
    """Market at its baseline: zero log-deviations, prices = Table-V base."""
    return SpotState(x=jnp.zeros((N_TYPES,)),
                     prices=SPOT_BASE_TABLE * 1.0,
                     spike_mult=jnp.ones((N_TYPES,)),
                     ema=SPOT_BASE_TABLE * 1.0,
                     key=key, rt=rt)


def step(state: SpotState, cfg: SpotConfig, dt: float,
         ema_alpha: jnp.ndarray | float | None = None) -> SpotState:
    """Advance all Table-V prices one monitoring interval of ``dt`` seconds.

    ``ema_alpha`` optionally overrides ``cfg.ema_alpha`` with a *traced*
    per-hour EMA weight (``core.types.PolicyParams.ema_alpha``) — the hook
    that makes the market-aware bid policy's smoothing coefficient tunable
    inside one compiled sweep.  Either path runs the same f32 arithmetic,
    so the default-valued override is bit-identical to no override.

    The hourly AR(1) (rho, vol) is rescaled so each type's stationary
    log-price variance vol²/(1-rho²) is preserved at any dt.  Innovations
    share a market factor: eps_i = √corr·eps_mkt + √(1−corr)·eps_own, so
    increments correlate at ``corr`` across types while every marginal is
    exactly the single-type process (eps_i ~ N(0,1)).  Demand spikes are a
    per-type two-state process: from calm, one arrives with probability
    p_spike·h; once active it ends with probability h per step (mean
    duration one hour).  Both the spiked-time fraction and the marginal
    price distribution are therefore invariant to dt, and at an hourly
    step with ``spike_hours = 1`` the process reduces exactly to the
    legacy per-hour Bernoulli spike.  A spike ends with probability
    ``h / spike_hours`` per step (mean duration ``spike_hours``).
    """
    key, k_mkt, k_eps, k_enter, k_exit, k_mult = jax.random.split(
        state.key, 6)
    h = dt / 3600.0
    rho_dt = cfg.rho ** h
    vol = _vol_table(cfg)
    vol_dt = vol * jnp.sqrt((1.0 - rho_dt ** 2) / (1.0 - cfg.rho ** 2))
    eps = (jnp.sqrt(cfg.corr) * jax.random.normal(k_mkt)
           + jnp.sqrt(1.0 - cfg.corr) * jax.random.normal(k_eps, (N_TYPES,)))
    x = rho_dt * state.x + vol_dt * eps

    p_spike = _p_spike_table(cfg)
    in_spike = state.spike_mult > 1.0
    ends = (jax.random.uniform(k_exit, (N_TYPES,))
            < jnp.minimum(h / cfg.spike_hours, 1.0))
    arrives = (jax.random.uniform(k_enter, (N_TYPES,))
               < jnp.minimum(p_spike * h, 1.0))
    fresh = jax.random.uniform(k_mult, (N_TYPES,), minval=cfg.spike_lo,
                               maxval=cfg.spike_hi)
    # A step that is calm — or whose spike just ended — may see a fresh
    # arrival, so at h = 1 every hour is an independent Bernoulli(p_spike)
    # draw, exactly the legacy hourly generator.
    calm = ~in_spike | ends
    spike_mult = jnp.where(calm, jnp.where(arrives, fresh, 1.0),
                           state.spike_mult)
    prices = SPOT_BASE_TABLE * jnp.exp(x) * spike_mult
    # Running price EMA for the market-aware bid policy, rescaled so its
    # per-hour weight is ``ema_alpha`` at any monitoring interval.
    a_hr = jnp.asarray(cfg.ema_alpha if ema_alpha is None else ema_alpha,
                       jnp.float32)
    a_dt = 1.0 - (1.0 - a_hr) ** h
    ema = (1.0 - a_dt) * state.ema + a_dt * prices
    return SpotState(x=x, prices=prices, spike_mult=spike_mult, ema=ema,
                     key=key, rt=state.rt)


def current_bids(cfg: SpotConfig, rt: SpotRuntime, state: SpotState,
                 urgency: jnp.ndarray | float = 0.0) -> jnp.ndarray:
    """(T,) $ bid per type attached to *new* requests this instant.

    All BID_POLICIES are evaluated and the runtime's (possibly traced)
    ``policy`` id selects one — which is what lets ``sim.sweep`` vmap the
    bid policy as an experiment axis.  ``urgency`` ∈ [0, 1] is the
    TTC-aware signal: 0 = every active workload on schedule, 1 = some
    deadline is at risk (the fleet fell far enough behind).
    """
    urgency = jnp.clip(jnp.asarray(urgency, jnp.float32), 0.0, 1.0)
    static = rt.bid_mult * SPOT_BASE_TABLE
    on_demand = ON_DEMAND_TABLE * jnp.ones_like(static)
    # TTC-aware: interpolate from the static bid up to the never-lose-
    # capacity cap as deadline slack shrinks.
    cap = jnp.maximum(on_demand, static)
    ttc = static + urgency * (cap - static)
    # Market-aware: track the calm price level, never pay above on-demand.
    ema = jnp.minimum(rt.bid_mult * state.ema, on_demand)
    return jnp.stack([static, on_demand, ttc, ema])[rt.policy]


def select_type(prices: jnp.ndarray, bids: jnp.ndarray, mix: jnp.ndarray,
                avail: jnp.ndarray | None = None,
                ) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Pick the acquisition type: cheapest-per-CU currently-available.

    A type is available when it is in the fleet ``mix`` and the market
    currently clears at or below our bid for it (an EC2 request above the
    clearing price is simply not fulfilled).  Returns ``(itype, any)``;
    when no type is available ``any`` is False and the caller must not
    start instances (``itype`` is then arbitrary).

    ``avail`` optionally supplies a (T,) capacity mask from the chaos
    engine (``sim.faults``): a hardened controller passes it so selection
    hedges across the types that still *have* capacity instead of
    queueing on a dried-up one.  ``None`` compiles the exact historical
    selection.
    """
    ok = (prices <= bids) & (mix > 0.0)
    if avail is not None:
        ok = ok & avail
    per_cu = prices / CORES_TABLE
    score = jnp.where(ok, per_cu, jnp.inf)
    return jnp.argmin(score).astype(jnp.int32), jnp.any(ok)


def price_trace(rt: SpotRuntime, steps: int, key: jax.Array,
                cfg: SpotConfig = SpotConfig(), dt: float = 3600.0
                ) -> jnp.ndarray:
    """A (steps,)-shaped price path of the primary type in one ``lax.scan``.

    vmap over ``rt`` (and/or ``key``) for batched traces.
    """
    return price_traces(rt, steps, key, cfg, dt)[:, rt.itype]


def price_traces(rt: SpotRuntime, steps: int, key: jax.Array,
                 cfg: SpotConfig = SpotConfig(), dt: float = 3600.0
                 ) -> jnp.ndarray:
    """(steps, T) correlated price paths of *all* Table-V types."""
    def body(s, _):
        s = step(s, cfg, dt)
        return s, s.prices

    _, prices = jax.lax.scan(body, init(rt, key), None, length=steps)
    return prices


def preemptions(trace: jnp.ndarray, bid: jnp.ndarray) -> jnp.ndarray:
    """Boolean mask of steps in which a bid at ``bid`` is outbid."""
    return trace > bid
