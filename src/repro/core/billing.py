"""Instance lifecycle + quantized billing (paper §II.C, §IV, Appendix A).

The fleet is a fixed pool of ``I`` potential instances (``I`` ≥ N_max) whose
lifecycle is driven by three pure functions:

  * ``advance``   — one monitoring interval of wall-clock: boot progress and
                    billing-quantum renewal (a_{i,j} countdown, eq. 3).
  * ``scale_to``  — start/drain instances to hit a target count (or, for
                    mixed-granularity spot fleets, a target *CU* capacity:
                    pass per-slot ``cores`` weights and the chosen start
                    type; see the function docstring).
  * ``preempt``   — spot-market reclamation: slots whose recorded bid is
                    below the current spot price are lost immediately.

Billing model (Appendix A): a CU is billed one quantum's price for each
*started* ``quantum`` (EC2 2015: $0.0081/hour for m3.medium spot), beginning
at the start request (boot time is paid, as on EC2).  There are no refunds.
The price may be the static ``BillingParams.price_per_quantum`` or, when the
spot market is live (``sim.spot``), the *current* spot price — pass it as
the ``price`` argument of ``advance``/``scale_to`` (scalar, or per-slot for
heterogeneous fleets).

Termination (§IV): "the prudent action is always to terminate spot instances
with the smallest remaining time before renewal" — i.e. AWS's
``ClosestToNextInstanceHour`` policy.  Scaling down therefore *drains*: the
instance is marked, keeps executing the work it has already been paid for,
and is reclaimed exactly at its quantum boundary instead of renewing.
Scaling up first cancels pending drains (free capacity) before paying for
new starts.  The control plane counts only non-draining instances; the
execution plane happily uses draining ones — they are paid for.

Preemption (Appendix A) is the involuntary counterpart: the market, not the
controller, takes the instance *now*, mid-quantum, and the already-billed
remainder is forfeited.  ``scale_to`` also refuses to start new slots while
``allow_start`` is False — on EC2 a request bidding below the clearing
price is simply not fulfilled.
"""

from __future__ import annotations

import jax.numpy as jnp
from jax import lax

from .types import BillingParams, ClusterState

OFF, BOOTING, ACTIVE = 0, 1, 2


def init(pool: int) -> ClusterState:
    return ClusterState(
        phase=jnp.zeros((pool,), jnp.int8),
        a=jnp.zeros((pool,), jnp.float32),
        boot_left=jnp.zeros((pool,), jnp.float32),
        draining=jnp.zeros((pool,), bool),
        cum_cost=jnp.asarray(0.0, jnp.float32),
        busy_frac=jnp.zeros((pool,), jnp.float32),
        itype=jnp.zeros((pool,), jnp.int32),
        bid=jnp.full((pool,), jnp.inf, jnp.float32),
        n_preempt=jnp.asarray(0.0, jnp.float32),
    )


def committed(cluster: ClusterState, cores: float | jnp.ndarray = 1.0
              ) -> jnp.ndarray:
    """Control-plane fleet size in CUs: paid-for, not marked to drain."""
    on = (cluster.phase >= BOOTING) & ~cluster.draining
    return jnp.sum(on.astype(jnp.float32) * cores)


def usable(cluster: ClusterState, cores: float | jnp.ndarray = 1.0
           ) -> jnp.ndarray:
    """Control-plane usable CUs (paper N_tot, eq. 2): active, not draining."""
    on = (cluster.phase == ACTIVE) & ~cluster.draining
    return jnp.sum(on.astype(jnp.float32) * cores)


def capacity(cluster: ClusterState, cores: float | jnp.ndarray = 1.0
             ) -> jnp.ndarray:
    """Execution capacity in CUs: every booted instance, drained or not, is
    paid for and is given tasks until its quantum expires."""
    return jnp.sum((cluster.phase == ACTIVE).astype(jnp.float32) * cores)


def advance(cluster: ClusterState, dt: float, billing: BillingParams,
            price: jnp.ndarray | None = None) -> ClusterState:
    """Advance wall-clock ``dt`` seconds: boots finish, quanta renew, and
    draining instances are reclaimed at their billing boundary.

    ``price`` is the $/quantum charged for renewals crossed in this window —
    scalar or per-slot; defaults to the static ``billing.price_per_quantum``.
    """
    if price is None:
        price = billing.price_per_quantum
    price = jnp.broadcast_to(jnp.asarray(price, jnp.float32),
                             cluster.a.shape)

    on = cluster.phase >= BOOTING
    boot_left = jnp.where(on, jnp.maximum(cluster.boot_left - dt, 0.0),
                          cluster.boot_left)
    phase = jnp.where(on & (boot_left <= 0.0), jnp.int8(ACTIVE),
                      cluster.phase)

    a = jnp.where(on, cluster.a - dt, cluster.a)
    hit_boundary = on & (a <= 0.0)
    renew = hit_boundary & ~cluster.draining
    reclaim = hit_boundary & cluster.draining

    # A monitoring interval can span several billing quanta (per-second /
    # per-minute billing): charge as many as the clock crossed.
    k = jnp.where(renew, jnp.floor(-a / billing.quantum) + 1.0, 0.0)
    a = a + k * billing.quantum
    cum_cost = cluster.cum_cost + jnp.sum(k * price)

    phase = jnp.where(reclaim, jnp.int8(OFF), phase)
    a = jnp.where(reclaim, 0.0, a)
    draining = cluster.draining & ~reclaim
    bid = jnp.where(reclaim, jnp.inf, cluster.bid)

    return cluster._replace(phase=phase, a=a, boot_left=boot_left,
                            draining=draining, cum_cost=cum_cost, bid=bid)


def preempt(cluster: ClusterState, price: jnp.ndarray
            ) -> tuple[ClusterState, jnp.ndarray]:
    """Spot reclamation: the market takes every slot outbid by ``price``.

    Unlike the controller's polite drain, this is involuntary and immediate:
    the slot goes OFF mid-quantum and the rest of its paid time is forfeited
    (no refunds on EC2).  Returns the new state and the number of instances
    lost — the capacity-loss signal the controller's AIMD loop reacts to on
    its next step, and the event ``ft.elastic`` treats as a node failure.
    """
    price = jnp.broadcast_to(jnp.asarray(price, jnp.float32),
                             cluster.bid.shape)
    on = cluster.phase >= BOOTING
    hit = on & (price > cluster.bid)
    n_hit = jnp.sum(hit.astype(jnp.float32))
    return cluster._replace(
        phase=jnp.where(hit, jnp.int8(OFF), cluster.phase),
        a=jnp.where(hit, 0.0, cluster.a),
        boot_left=jnp.where(hit, 0.0, cluster.boot_left),
        draining=cluster.draining & ~hit,
        bid=jnp.where(hit, jnp.inf, cluster.bid),
        n_preempt=cluster.n_preempt + n_hit,
    ), n_hit


def scale_to(cluster: ClusterState, n_target: jnp.ndarray,
             billing: BillingParams,
             price: jnp.ndarray | None = None,
             bid: jnp.ndarray | None = None,
             itype: jnp.ndarray | None = None,
             allow_start: jnp.ndarray | bool = True,
             cores: jnp.ndarray | None = None) -> ClusterState:
    """Drive the control-plane fleet size toward ``n_target``.

    ``n_target`` is an instance count for homogeneous fleets (``cores``
    omitted).  For heterogeneous spot fleets, pass ``cores`` — per-slot CU
    weights, with OFF slots carrying the CUs of the type a new start would
    use (the caller's ``itype``) — and ``n_target`` becomes a *CU* target:
    growth starts just enough instances of the chosen type to cover the
    missing CUs, shrink sheds only whole instances that fit within the CU
    excess (the fleet stays at or above its target, as under the
    instance-count ``ceil`` semantics — a sub-instance excess never
    forfeits a paid coarse instance).

    Growth: cancel drains first (the capacity is already paid for), then
    start OFF slots, paying a full quantum each at ``price`` ($/quantum;
    defaults to the static list price).  New slots record ``bid`` and
    ``itype`` for the spot market's ``preempt``; ``allow_start=False``
    models an unfulfilled spot request (price above our bid) — growth by
    undraining still works, new money does not enter the market.
    Shrink: mark the instances with the *smallest remaining paid time*
    (§IV) as draining.
    """
    if price is None:
        price = billing.price_per_quantum
    pool = cluster.phase.shape[0]
    slot_cores = (jnp.ones((pool,), jnp.float32) if cores is None
                  else jnp.broadcast_to(jnp.asarray(cores, jnp.float32),
                                        (pool,)))
    n_target = jnp.round(n_target)
    n_live = committed(cluster, slot_cores)
    delta = n_target - n_live

    # ---- grow: undrain cheapest-to-keep first (largest remaining time) ----
    n_grow = jnp.maximum(delta, 0.0)
    drain_key = jnp.where(cluster.draining, -cluster.a, jnp.inf)
    do_undrain = cluster.draining & _take(drain_key, slot_cores, n_grow)
    n_undrained = jnp.sum(jnp.where(do_undrain, slot_cores, 0.0))
    draining = cluster.draining & ~do_undrain

    n_start = jnp.maximum(n_grow - n_undrained, 0.0)
    n_start = jnp.where(jnp.asarray(allow_start), n_start, 0.0)
    off = cluster.phase == OFF
    start_key = jnp.where(off, jnp.arange(pool, dtype=jnp.float32), jnp.inf)
    do_start = off & _take(start_key, slot_cores, n_start)

    phase = jnp.where(do_start, jnp.int8(BOOTING), cluster.phase)
    a = jnp.where(do_start, billing.quantum, cluster.a)
    boot_left = jnp.where(do_start, billing.boot_delay, cluster.boot_left)
    start_price = jnp.broadcast_to(jnp.asarray(price, jnp.float32),
                                   cluster.a.shape)
    cum_cost = cluster.cum_cost + jnp.sum(
        jnp.where(do_start, start_price, 0.0))
    new_bid = (jnp.full_like(cluster.bid, jnp.inf) if bid is None
               else jnp.broadcast_to(jnp.asarray(bid, jnp.float32),
                                     cluster.bid.shape))
    bid_arr = jnp.where(do_start, new_bid, cluster.bid)
    itype_arr = cluster.itype
    if itype is not None:
        itype_arr = jnp.where(
            do_start,
            jnp.broadcast_to(jnp.asarray(itype, jnp.int32),
                             cluster.itype.shape),
            cluster.itype)

    # ---- shrink: smallest-remaining-time instances first (§IV) -----------
    n_shrink = jnp.maximum(-delta, 0.0)
    live = (phase >= BOOTING) & ~draining
    # Active instances by remaining paid time ascending; booting ones last.
    shrink_key = jnp.where(live & (phase == ACTIVE), a,
                           jnp.where(live, a + 2.0 * billing.quantum,
                                     jnp.inf))
    do_shed = live & _take(shrink_key, slot_cores, n_shrink, cover=False)

    if billing.terminate == "immediate":
        # Paper semantics: release now, forfeit the rest of the quantum.
        phase = jnp.where(do_shed, jnp.int8(OFF), phase)
        a = jnp.where(do_shed, 0.0, a)
        boot_left = jnp.where(do_shed, 0.0, boot_left)
        bid_arr = jnp.where(do_shed, jnp.inf, bid_arr)
    else:
        # Beyond-paper: drain and reclaim at the billing boundary.
        draining = draining | do_shed

    return cluster._replace(phase=phase, a=a, boot_left=boot_left,
                            draining=draining, cum_cost=cum_cost,
                            bid=bid_arr, itype=itype_arr)


def _take(key: jnp.ndarray, weights: jnp.ndarray, budget: jnp.ndarray,
          cover: bool = True) -> jnp.ndarray:
    """Mark slots in ascending-``key`` order against a weight ``budget``.

    ``cover=True`` (growth): take while the weight marked *before* each
    slot stays below the budget — just enough slots to cover it,
    overshooting by at most one (the CU analogue of ``ceil``).
    ``cover=False`` (shrink): take only slots that fit *entirely* within
    the budget, so the fleet never dips below its target — a sub-instance
    CU excess must not shed (and forfeit) a whole coarse instance.
    With unit weights and an integer budget both modes are exactly
    ``rank ≤ budget``.  Callers mask the result: slots keyed ``inf`` sort
    last but can still be marked once the budget exceeds the eligible
    weight.

    The order comes from one multi-operand ``lax.sort`` keyed on
    ``(key, slot index)``, with the weights carried along: the comparator
    ``jnp.argsort`` uses (``-0.0`` ties ``0.0``), ties broken by slot as
    a stable sort does. A second sort keyed on the permutation puts the
    marks back in slot order. No index gathers or scatters: under
    ``vmap`` the TPU runs per-row-indexed ones as scalar loops.
    """
    iota = lax.iota(jnp.int32, key.shape[0])
    _, perm, w_sorted = lax.sort((key, iota, weights), num_keys=2)
    incl = jnp.cumsum(w_sorted)
    taken = (incl - w_sorted) < budget if cover else incl <= budget
    _, out = lax.sort((perm, taken), num_keys=1)
    return out


def lower_bound_cost(total_cus: jnp.ndarray,
                     billing: BillingParams) -> jnp.ndarray:
    """Paper 'LB': the bill if every paid CU-second were used at 100%."""
    quanta = jnp.ceil(total_cus / billing.quantum)
    return quanta * billing.price_per_quantum
