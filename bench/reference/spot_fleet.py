"""Plain reference of one grid point of the spot-fleet platform.

Written from the paper (arXiv:1604.04804: eqs. 1-14, Fig. 1, Appendix A)
and the simulator's documented semantics, with no code of the program: it
reads only the deployment file (``bench/configs/<config>.json``), the
workload schedule and the point's parameters.  One monitoring instant:

  arrivals -> market step (all Table-V types, correlated log-AR(1) with
  demand spikes) -> outbid slots lost -> boots finish, quanta renew and
  are billed at the slot's own type price -> work executes at the rates
  decided last instant -> completions and the TTC clock -> Kalman update
  (eqs. 4-9), TTC confirmation, proportional-fair rates (eqs. 11-14),
  AIMD target (Fig. 1) -> bid, pick the cheapest-per-CU type on offer,
  start or release whole instances toward the CU target.

Random draws follow the platform's documented streams: the execution
noise chain starts at ``PRNGKey(seed)``, the market chain at
``PRNGKey(seed + 7919)``, each split once per instant.

Slot choice is written as counting, not sorting: a slot's place in an
order is the number of slots that come before it.  Every float is of
``dtype``, so the same code in a lower precision is the control.

Only the options the deployments state are implemented (Kalman predictor,
AIMD on committed CUs, immediate release, one item type); any other value
raises.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

OFF, BOOTING, ACTIVE = 0, 1, 2
POLICIES = ("multiple", "on_demand", "ttc", "ema")

# A point's summary holds the sweep's fields ``cost``, ``cost_horizon``,
# ``violations``, ``preemptions``, ``finished``, ``max_committed``,
# ``mean_price``, ``max_price``.  Fields that count (compared with a floor
# of 1 under the gap):
COUNTS = ("violations", "preemptions", "finished")


class _Market(NamedTuple):
    x: jnp.ndarray
    prices: jnp.ndarray
    spike: jnp.ndarray
    ema: jnp.ndarray
    key: jnp.ndarray


class _State(NamedTuple):
    # workloads
    active: jnp.ndarray
    m: jnp.ndarray
    d: jnp.ndarray
    confirmed: jnp.ndarray
    t_submit: jnp.ndarray
    t_done: jnp.ndarray
    s: jnp.ndarray
    done_acc: jnp.ndarray
    # Kalman bank, one filter per workload
    b_hat: jnp.ndarray
    pi: jnp.ndarray
    b_prev: jnp.ndarray
    has_meas: jnp.ndarray
    reliable: jnp.ndarray
    # fleet
    phase: jnp.ndarray
    a: jnp.ndarray
    boot_left: jnp.ndarray
    itype: jnp.ndarray
    bid: jnp.ndarray
    cum_cost: jnp.ndarray
    n_lost: jnp.ndarray
    market: _Market
    key: jnp.ndarray


def _check(cfg: dict) -> None:
    p = cfg["platform"]
    want = {("controller", "predictor"): "kalman",
            ("controller", "policy"): "aimd",
            ("controller", "aimd_base"): "committed",
            ("billing", "terminate"): "immediate",
            ("spot", "enabled"): True}
    for (group, key), value in want.items():
        if p[group][key] != value:
            raise ValueError(f"the spot_fleet reference models "
                             f"{group}.{key} = {value!r}, not "
                             f"{p[group][key]!r}")


def make(cfg: dict, ticks: int, sched, dtype=jnp.float32):
    """``point(seed, bid_mult, policy, primary, mix) -> dict`` of one grid
    point's summary on the workload schedule ``sched``; ``policy`` indexes
    ``POLICIES``, ``primary`` the config's instance types, ``mix`` is a
    0/1 mask over them."""
    _check(cfg)
    f = lambda v: jnp.asarray(v, dtype)  # noqa: E731
    plat = cfg["platform"]
    ctl, bil, mkt = plat["control"], plat["billing"], plat["spot"]
    table = cfg["instance_types"]
    cores_t = f(np.asarray(table["cores"], np.float32))
    od_t = f(np.asarray(table["on_demand"], np.float32))
    base_t = f(np.asarray(table["spot"], np.float32))
    n_types = len(table["cores"])
    pool = int(plat["sim"]["pool"])
    dt = float(ctl["monitor_dt"])
    quantum = float(bil["quantum"])
    alpha, beta = float(ctl["alpha"]), float(ctl["beta"])
    n_min, n_max = float(ctl["n_min"]), float(ctl["n_max"])
    n_w_max = float(ctl["n_w_max"])
    boot_rate = float(plat["controller"]["bootstrap_rate"])
    slot_ids = jnp.arange(pool)

    # ---- market (Appendix A) ---------------------------------------------
    h = dt / 3600.0
    rho = float(mkt["rho"])
    rho_h = rho ** h
    vol = mkt["vol0"] + mkt["vol_scale"] * jnp.log2(cores_t + 1.0)
    vol_h = vol * jnp.sqrt(f((1.0 - rho_h ** 2) / (1.0 - rho ** 2)))
    corr = float(mkt["corr"])
    p_spike = mkt["p_spike_per_core"] * cores_t
    ema_hr = f(mkt["ema_alpha"])
    ema_w = 1.0 - (1.0 - ema_hr) ** h

    def market_step(mk: _Market) -> _Market:
        key, k_mkt, k_eps, k_enter, k_exit, k_mult = jax.random.split(
            mk.key, 6)
        common = f(jax.random.normal(k_mkt))
        own = f(jax.random.normal(k_eps, (n_types,)))
        eps = jnp.sqrt(f(corr)) * common + jnp.sqrt(f(1.0 - corr)) * own
        x = rho_h * mk.x + vol_h * eps
        ends = f(jax.random.uniform(k_exit, (n_types,))) < jnp.minimum(
            f(h / mkt["spike_hours"]), 1.0)
        starts = f(jax.random.uniform(k_enter, (n_types,))) < jnp.minimum(
            p_spike * h, 1.0)
        size = f(jax.random.uniform(k_mult, (n_types,),
                                    minval=mkt["spike_lo"],
                                    maxval=mkt["spike_hi"]))
        calm = (mk.spike <= 1.0) | ends
        spike = jnp.where(calm, jnp.where(starts, size, 1.0), mk.spike)
        prices = base_t * jnp.exp(x) * spike
        ema = (1.0 - ema_w) * mk.ema + ema_w * prices
        return _Market(x=x, prices=prices, spike=spike, ema=ema, key=key)

    def bids_for(policy, bid_mult, mk: _Market, urgency):
        static = bid_mult * base_t
        cap = jnp.maximum(od_t, static)
        ttc = static + urgency * (cap - static)
        ema = jnp.minimum(bid_mult * mk.ema, od_t)
        return jnp.select([policy == 0, policy == 1, policy == 2],
                          [static, od_t, ttc], ema)

    def cheapest_on_offer(prices, bids, mix):
        ok = (prices <= bids) & (mix > 0)
        per_cu = jnp.where(ok, prices / cores_t, jnp.inf)
        best = jnp.min(per_cu)
        # lowest type index among the cheapest per CU; type 0 when none
        # is on offer (nothing starts then)
        first = jnp.min(jnp.where(ok & (per_cu == best),
                                  jnp.arange(n_types), n_types))
        return jnp.where(jnp.any(ok), first, 0), jnp.any(ok)

    # ---- fleet -------------------------------------------------------------
    def start(st: _State, n_cus, itype_new, price, bid, allowed):
        """Start OFF slots, lowest index first, until ``n_cus`` CUs of
        type ``itype_new`` are covered (the last may overshoot)."""
        off = st.phase == OFF
        c_new = cores_t[itype_new]
        before = jnp.cumsum(off.astype(dtype)) - 1.0   # OFF slots ahead
        go = off & allowed & (before * c_new < n_cus)
        return st._replace(
            phase=jnp.where(go, BOOTING, st.phase),
            a=jnp.where(go, quantum, st.a),
            boot_left=jnp.where(go, bil["boot_delay"], st.boot_left),
            itype=jnp.where(go, itype_new, st.itype),
            bid=jnp.where(go, bid, st.bid),
            cum_cost=st.cum_cost + jnp.sum(jnp.where(go, price, 0.0)))

    def release(st: _State, n_cus, cores):
        """Release whole instances with the least paid time left (booting
        ones after active ones), as long as the CUs released stay within
        ``n_cus``."""
        live = st.phase != OFF
        rank_key = jnp.where(st.phase == ACTIVE, st.a, st.a + 2 * quantum)
        k_i, k_j = rank_key[:, None], rank_key[None, :]
        ahead = live[None, :] & ((k_j < k_i) | (
            (k_j == k_i) & (slot_ids[None, :] <= slot_ids[:, None])))
        upto = jnp.sum(jnp.where(ahead, cores[None, :], 0.0), axis=1)
        go = live & (upto <= n_cus)
        return st._replace(
            phase=jnp.where(go, OFF, st.phase),
            a=jnp.where(go, 0.0, st.a),
            boot_left=jnp.where(go, 0.0, st.boot_left),
            bid=jnp.where(go, jnp.inf, st.bid))

    def band_scale(demand, capacity, room):
        """Eqs. 13-14: rescale demand into [beta * capacity, capacity +
        room]."""
        return jnp.where(
            demand > capacity + room,
            (capacity + room) / jnp.maximum(demand, 1e-9),
            jnp.where(demand < beta * capacity,
                      beta * capacity / jnp.maximum(demand, 1e-9), 1.0))

    def ramp(p, c0, p_r, over):
        rising = c0 + (1.0 - c0 + over) * jnp.minimum(
            p / jnp.maximum(p_r, 1e-6), 1.0)
        settled = 1.0 + over * jnp.exp(-(p - p_r) / 0.15)
        return jnp.where(p <= p_r, rising, settled)

    def point(seed, bid_mult, policy, primary, mix):
        seed = jnp.asarray(seed, jnp.int32)
        t_arrive = jnp.asarray(sched.t_arrive, jnp.int32)
        valid = jnp.asarray(sched.valid, bool)
        m0 = f(sched.m0[:, 0])
        b_true = f(sched.b_true[:, 0])
        sigma = f(sched.sigma)
        c0, p_r, over = f(sched.c0), f(sched.p_r), f(sched.overshoot)
        d_req = f(sched.d_requested)
        w = t_arrive.shape[0]
        bid_mult = f(bid_mult)
        mix = f(mix)
        mk = _Market(x=jnp.zeros((n_types,), dtype), prices=base_t,
                     spike=jnp.ones((n_types,), dtype), ema=base_t,
                     key=jax.random.PRNGKey(seed + 7919))
        zw = jnp.zeros((w,), dtype)
        fw = jnp.zeros((w,), bool)
        st = _State(
            active=fw, m=zw, d=d_req, confirmed=fw,
            t_submit=jnp.full((w,), -1), t_done=jnp.full((w,), -1),
            s=zw, done_acc=zw,
            b_hat=zw, pi=zw, b_prev=zw, has_meas=fw, reliable=fw,
            phase=jnp.zeros((pool,), jnp.int32), a=jnp.zeros((pool,), dtype),
            boot_left=jnp.zeros((pool,), dtype),
            itype=jnp.zeros((pool,), jnp.int32),
            bid=jnp.full((pool,), jnp.inf, dtype),
            cum_cost=f(0.0), n_lost=f(0.0), market=mk,
            key=jax.random.PRNGKey(seed))
        # The platform starts with N_min CUs of the cheapest type on offer
        # at the base prices, already booted.
        bids0 = bids_for(policy, bid_mult, mk, f(0.0))
        it0, ok0 = cheapest_on_offer(mk.prices, bids0, mix)
        st = start(st, jnp.round(f(n_min)), it0, mk.prices[it0], bids0[it0],
                   ok0)
        st = st._replace(boot_left=jnp.zeros_like(st.boot_left),
                         phase=jnp.where(st.phase != OFF, ACTIVE, st.phase))

        def tick(st: _State, t):
            key, k_exec = jax.random.split(st.key)
            # arrivals
            arrive = (t_arrive == t) & valid
            active = st.active | arrive
            m = jnp.where(arrive, m0, st.m)
            d = jnp.where(arrive, d_req, st.d)
            t_submit = jnp.where(arrive, t, st.t_submit)
            b_hat = jnp.where(arrive, 0.0, st.b_hat)
            pi = jnp.where(arrive, 0.0, st.pi)
            b_prev = jnp.where(arrive, 0.0, st.b_prev)
            has_meas = st.has_meas & ~arrive
            reliable = st.reliable & ~arrive

            # market and preemption
            mk = market_step(st.market)
            slot_price = mk.prices[st.itype]
            cores = cores_t[st.itype]
            lost = (st.phase != OFF) & (slot_price > st.bid)
            phase = jnp.where(lost, OFF, st.phase)
            a = jnp.where(lost, 0.0, st.a)
            boot_left = jnp.where(lost, 0.0, st.boot_left)
            bid = jnp.where(lost, jnp.inf, st.bid)
            n_lost = st.n_lost + jnp.sum(lost.astype(dtype))

            # clock: boots finish, quanta renew at the current price
            on = phase != OFF
            boot_left = jnp.where(on, jnp.maximum(boot_left - dt, 0.0),
                                  boot_left)
            phase = jnp.where(on & (boot_left <= 0.0), ACTIVE, phase)
            a = jnp.where(on, a - dt, a)
            renewals = jnp.where(on & (a <= 0.0),
                                 jnp.floor(-a / quantum) + 1.0, 0.0)
            a = a + renewals * quantum
            cum_cost = st.cum_cost + jnp.sum(renewals * slot_price)

            # execution at last instant's rates
            cap = jnp.sum(jnp.where(phase == ACTIVE, cores, 0.0))
            want = jnp.sum(st.s)
            squeeze = jnp.where(want > cap, cap / jnp.maximum(want, 1e-9),
                                1.0)
            granted = st.s * squeeze * dt * plat["sim"]["efficiency"]
            p_done = 1.0 - m / jnp.maximum(m0, 1.0)
            k_noise, k_meas = jax.random.split(k_exec)
            noise = jnp.exp(plat["sim"]["exec_noise"]
                            * f(jax.random.normal(k_noise, (w,))))
            b_exec = b_true * ramp(p_done, c0, p_r, over) * noise
            items = jnp.minimum(m, granted / jnp.maximum(b_exec, 1e-9))
            items = jnp.where(active, items, 0.0)
            done_acc = st.done_acc + items
            measured = jnp.floor(done_acc) > jnp.floor(st.done_acc)
            meas_sigma = sigma / jnp.sqrt(jnp.clip(items, 1.0, 4.0))
            b_meas = b_exec * jnp.exp(
                meas_sigma * f(jax.random.normal(k_meas, (w,))))
            m = jnp.maximum(m - items, 0.0)
            done_acc = jnp.where(arrive, 0.0, done_acc)

            # completions and the TTC clock
            done_now = active & (m <= 0.0)
            d = jnp.where(active & ~done_now, d - dt, d)
            t_done = jnp.where(done_now, t, st.t_done)
            active = active & ~done_now

            # Kalman bank (eqs. 4-9): the first measurement sets the
            # estimate; later ones correct it with the lagged measurement
            first = measured & ~has_meas
            b0 = jnp.where(first, b_meas, b_hat)
            prev0 = jnp.where(first, b_meas, b_prev)
            regular = measured & has_meas
            pi_minus = pi + ctl["sigma_z2"]
            gain = pi_minus / (pi_minus + ctl["sigma_v2"])
            b_new = jnp.where(regular, b0 + gain * (prev0 - b0), b0)
            pi = jnp.where(regular, (1.0 - gain) * pi_minus, pi)
            reliable = reliable | (regular & (b_new - b_hat < 0.0))
            b_prev = jnp.where(measured, b_meas, prev0)
            has_meas = has_meas | measured
            b_hat = b_new

            # TTC confirmation and proportional-fair rates (eqs. 1, 11-14)
            r = m * b_hat
            newly = active & reliable & (m0 > 0.0) & ~st.confirmed
            d = jnp.where(newly, jnp.maximum(d, r / n_w_max), d)
            confirmed = st.confirmed | newly
            usable = jnp.sum(jnp.where(phase == ACTIVE, cores, 0.0))
            sched_on = active & confirmed
            s_star = jnp.where(sched_on, r / jnp.maximum(d, 1e-9), 0.0)
            wanted = jnp.minimum(s_star, ctl["surge_mult"] * n_w_max)
            n_star = jnp.sum(wanted)
            scale = band_scale(n_star, usable, alpha)
            s = jnp.where(sched_on, jnp.minimum(s_star * scale, n_w_max), 0.0)
            probing = active & ~confirmed
            s = jnp.where(probing, boot_rate, s)
            n_star = n_star + jnp.sum(jnp.where(probing, boot_rate, 0.0))

            # AIMD (Fig. 1) on the committed CUs
            committed = jnp.sum(jnp.where(phase != OFF, cores, 0.0))
            target = jnp.where(committed <= n_star,
                               jnp.minimum(committed + alpha, n_max),
                               jnp.maximum(beta * committed, n_min))

            # bids, type choice, scaling toward the CU target
            behind = jnp.where(
                active, (1.0 - d / jnp.maximum(d_req, 1e-9))
                - (1.0 - m / jnp.maximum(m0, 1e-9)), -jnp.inf)
            urgency = jnp.clip(mkt["ttc_gain"] * jnp.max(behind), 0.0, 1.0)
            bids = bids_for(policy, bid_mult, mk, urgency)
            it_new, on_offer = cheapest_on_offer(mk.prices, bids, mix)
            gap = jnp.round(target) - committed
            st2 = st._replace(phase=phase, a=a, boot_left=boot_left,
                              bid=bid, cum_cost=cum_cost)
            st2 = start(st2, jnp.maximum(gap, 0.0), it_new,
                        mk.prices[it_new], bids[it_new], on_offer)
            st2 = release(st2, jnp.maximum(-gap, 0.0), cores)
            committed_after = jnp.sum(jnp.where(
                st2.phase != OFF, cores_t[st2.itype], 0.0))

            new = st2._replace(
                active=active, m=m, d=d, confirmed=confirmed,
                t_submit=t_submit, t_done=t_done, s=s, done_acc=done_acc,
                b_hat=b_hat, pi=pi, b_prev=b_prev, has_meas=has_meas,
                reliable=reliable, n_lost=n_lost, market=mk, key=key)
            return new, (st2.cum_cost, committed_after, mk.prices[primary])

        final, (cost_t, committed_t, price_t) = jax.lax.scan(
            tick, st, jnp.arange(ticks))

        submitted = (final.t_submit >= 0) & valid
        finished = (final.t_done >= 0) & valid
        t_end = jnp.max(jnp.where(valid, final.t_done, -1))
        open_work = jnp.any(submitted & ~finished)
        at_end = cost_t[jnp.clip(t_end + 1, 0, ticks - 1)]
        allowed = jnp.ceil(d_req / dt)
        late = (final.t_done - final.t_submit) - allowed
        violated = (submitted & finished & (late > 1)) | (submitted
                                                          & ~finished)
        return dict(
            cost=jnp.where(open_work | (t_end < 0), cost_t[-1], at_end),
            cost_horizon=cost_t[-1],
            violations=jnp.sum(violated.astype(jnp.int32)),
            preemptions=final.n_lost,
            finished=jnp.sum(finished.astype(jnp.int32)),
            max_committed=jnp.max(committed_t),
            mean_price=jnp.mean(price_t),
            max_price=jnp.max(price_t))

    return point


def build(cfg: dict, ticks: int, sched, dtype=jnp.float32,
          compiler_options=None):
    """``make`` vmapped over points and jitted, with XLA's
    ``compiler_options``."""
    return jax.jit(jax.vmap(make(cfg, ticks, sched, dtype)),
                   compiler_options=compiler_options)


def run(fn, cfg: dict, points: dict, block: int = 256) -> dict:
    """Summaries of many points (``points``: arrays ``seed``, ``bid_mult``,
    ``policy`` names, ``instance`` names) by ``fn`` from ``build``, in
    blocks of ``block`` rows, as numpy float64 arrays keyed by field."""
    names = list(cfg["instance_types"]["names"])
    n = len(points["seed"])
    pol = np.asarray([POLICIES.index(p) for p in points["policy"]], np.int32)
    prim = np.asarray([names.index(i) for i in points["instance"]], np.int32)
    mix = (np.arange(len(names))[None, :] == prim[:, None]).astype(
        np.float32)
    seed = np.asarray(points["seed"], np.int32)
    bid = np.asarray(points["bid_mult"], np.float32)
    out: dict = {}
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        pad = block - (hi - lo) if n > block else 0
        args = [np.concatenate([x[lo:hi], np.repeat(x[hi - 1:hi], pad, 0)])
                for x in (seed, bid, pol, prim, mix)]
        for k, v in fn(*args).items():
            out.setdefault(k, []).append(np.asarray(v, np.float64)[:hi - lo])
    return {k: np.concatenate(v) for k, v in out.items()}
