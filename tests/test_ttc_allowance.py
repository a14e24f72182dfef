"""The TTC allowance in monitoring ticks, ``ceil(ttc / dt)``, is counted
exactly wherever it is computed: under ``jit`` and ``vmap``, on the sweep
path, and in the armed detectors' per-tick violation count.

XLA compiles a division by a constant as a product with the rounded
reciprocal, so ``jnp.ceil(6300 / 60)`` inside ``jit`` reads 106, not 105;
the 6,300-s TTC at one-minute monitoring sits on a tick boundary."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.controller import ControllerConfig
from repro.core.types import BillingParams, ControlParams
from repro.obs import DetectSpec, ObsSpec
from repro.sim import (SimConfig, SpotConfig, SweepSpec, make_axes,
                       paper_schedule, runner, workloads)
from repro.sim.sweep import sweep

K_MAX = 2000
SEEDS = [0, 1, 2, 3, 4, 5]


def _exact(d: np.ndarray, dt: float) -> np.ndarray:
    return np.array([math.ceil(x / dt) for x in d.tolist()], np.float64)


def _ttcs(dt: float) -> np.ndarray:
    """Every multiple ``k * dt`` for ``k`` up to ``K_MAX``, and its
    neighbours one second either side."""
    k = np.arange(K_MAX + 1, dtype=np.float64)
    d = np.concatenate([k * dt, k * dt + 1.0, k * dt - 1.0])
    return d[d >= 0.0].astype(np.float32)


@pytest.mark.parametrize("dt", [60.0, 300.0, 3600.0, 7.0, 0.5])
def test_allowance_is_the_integer_ceiling_under_jit_and_vmap(dt):
    d = _ttcs(dt)
    want = _exact(d.astype(np.float64), dt)
    jitted = jax.jit(lambda x: runner.ticks_allowed(x, dt))(jnp.asarray(d))
    np.testing.assert_array_equal(np.asarray(jitted, np.float64), want)
    mapped = jax.jit(jax.vmap(lambda x: runner.ticks_allowed(x, dt)))(
        jnp.asarray(d))
    np.testing.assert_array_equal(np.asarray(mapped, np.float64), want)


def _headline_cfg(obs: ObsSpec | None = None, ticks: int = 650) -> SimConfig:
    return SimConfig(
        ctrl=ControllerConfig(params=ControlParams(monitor_dt=60.0),
                              billing=BillingParams(terminate="immediate")),
        ticks=ticks, spot=SpotConfig(enabled=True, bid_policy="on_demand"),
        obs=obs)


def test_sweep_counts_the_violations_that_run_counts():
    """On the paper's schedule at 1-min monitoring, TTC 6,300 s = 105
    ticks: the jitted sweep and the unbatched run agree point by point."""
    sched = paper_schedule(ttc=6300.0, arrival_gap_ticks=5)
    cfg = _headline_cfg()
    res = sweep(SweepSpec(axes=make_axes(SEEDS, [1.0]), workload=sched), cfg)
    swept = np.asarray(res.violations)
    single = np.array([int(runner.run(sched, cfg, seed=s).violations)
                       for s in SEEDS])
    np.testing.assert_array_equal(swept, single)
    assert single.sum() > 0           # the rule is exercised, not vacuous


def test_armed_detectors_count_the_violations_of_violation_rows():
    """The detectors' per-tick count, summed over a run whose slow burn
    window outlasts it, equals the finished rows ``violation_rows``
    marks late."""
    ticks = 160
    det = DetectSpec(burn_fast=8, burn_slow=ticks + 1)
    sched = workloads.as_jax_schedule(
        paper_schedule(ttc=1800.0, arrival_gap_ticks=2))
    cfg = _headline_cfg(obs=ObsSpec(detect=det), ticks=ticks)
    pp = runner.default_params(cfg)
    counted = 0
    for seed in SEEDS[:3]:
        final, _ = runner.cached_scan(sched, cfg, trace=True,
                                      with_rt=False)(sched, seed, pp)
        rows = runner.violation_rows(final.work, sched, cfg)
        late_done = int(jnp.sum(rows & (final.work.t_done >= 0)))
        assert float(final.obs.detect.viol_slow) == late_done
        counted += late_done
    assert counted > 0
