"""Instance lifecycle + quantized billing (paper §II.C/§IV, Appendix A)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, strategies as st

from repro.core import billing
from repro.core.types import BillingParams
from repro.sim.spot import CORES_TABLE

B = BillingParams(boot_delay=120.0, terminate="boundary")
BI = dataclasses.replace(B, terminate="immediate")


def test_start_pays_full_quantum():
    c = billing.init(8)
    c = billing.scale_to(c, jnp.asarray(3.0), B)
    assert float(c.cum_cost) == pytest.approx(3 * B.price_per_quantum)
    assert float(billing.committed(c)) == 3


def test_boot_completes_then_usable():
    c = billing.scale_to(billing.init(8), jnp.asarray(2.0), B)
    assert float(billing.usable(c)) == 0
    c = billing.advance(c, 120.0, B)
    assert float(billing.usable(c)) == 2


def test_renewal_charges_next_quantum():
    c = billing.scale_to(billing.init(4), jnp.asarray(1.0), B)
    c0 = float(c.cum_cost)
    c = billing.advance(c, B.quantum + 1.0, B)
    assert float(c.cum_cost) == pytest.approx(c0 + B.price_per_quantum)


def test_boundary_drain_never_renews():
    c = billing.scale_to(billing.init(4), jnp.asarray(2.0), B)
    c = billing.advance(c, 120.0, B)
    c = billing.scale_to(c, jnp.asarray(1.0), B)      # mark one for drain
    cost_before = float(c.cum_cost)
    c = billing.advance(c, B.quantum + 1.0, B)
    # drained instance reclaimed (no charge); survivor renewed (one charge)
    assert float(c.cum_cost) == pytest.approx(
        cost_before + B.price_per_quantum)
    assert float(billing.committed(c)) == 1


def test_drained_instance_still_executes():
    c = billing.scale_to(billing.init(4), jnp.asarray(2.0), B)
    c = billing.advance(c, 120.0, B)
    c = billing.scale_to(c, jnp.asarray(1.0), B)
    assert float(billing.usable(c)) == 1          # control-plane view
    assert float(billing.capacity(c)) == 2        # execution-plane view


def test_undrain_is_free():
    c = billing.scale_to(billing.init(4), jnp.asarray(2.0), B)
    c = billing.advance(c, 120.0, B)
    c = billing.scale_to(c, jnp.asarray(1.0), B)
    cost = float(c.cum_cost)
    c = billing.scale_to(c, jnp.asarray(2.0), B)  # cancel the drain
    assert float(c.cum_cost) == pytest.approx(cost)
    assert float(billing.committed(c)) == 2


def test_immediate_termination_forfeits():
    c = billing.scale_to(billing.init(4), jnp.asarray(2.0), BI)
    c = billing.advance(c, 120.0, BI)
    c = billing.scale_to(c, jnp.asarray(1.0), BI)
    assert float(billing.capacity(c)) == 1        # gone now
    # money stays spent
    assert float(c.cum_cost) == pytest.approx(2 * B.price_per_quantum)


def test_shrink_picks_smallest_remaining():
    c = billing.scale_to(billing.init(4), jnp.asarray(1.0), BI)
    c = billing.advance(c, 1800.0, BI)            # 30 min used
    c = billing.scale_to(c, jnp.asarray(2.0), BI)  # add a fresh one
    c = billing.advance(c, 120.0, BI)
    # shrink: the old instance (less remaining) should go, not the fresh one
    c = billing.scale_to(c, jnp.asarray(1.0), BI)
    on = np.asarray(c.phase) >= 1
    assert on.sum() == 1
    assert float(c.a[np.nonzero(on)[0][0]]) > B.quantum - 1000


def test_lower_bound():
    lb = billing.lower_bound_cost(jnp.asarray(97_000.0), B)
    assert float(lb) == pytest.approx(np.ceil(97_000 / 3600) * 0.0081)


@given(st.lists(st.integers(0, 12), min_size=1, max_size=24),
       st.sampled_from(["boundary", "immediate"]))
@settings(max_examples=40, deadline=None)
def test_lifecycle_invariants(targets, mode):
    """Cost is non-decreasing; committed tracks targets within pool; no
    negative remaining time on live instances."""
    bp = dataclasses.replace(B, terminate=mode)
    c = billing.init(16)
    prev_cost = 0.0
    for t in targets:
        c = billing.advance(c, 60.0, bp)
        c = billing.scale_to(c, jnp.asarray(float(t)), bp)
        cost = float(c.cum_cost)
        assert cost >= prev_cost - 1e-9
        prev_cost = cost
        live = np.asarray(c.phase) >= 1
        assert 0 <= live.sum() <= 16
        assert (np.asarray(c.a)[live] > -60.0).all()
        assert float(billing.committed(c)) == pytest.approx(
            min(float(t), 16), abs=0)


def _take_by_indexing(key, weights, budget, cover):
    """The argsort / gather / scatter formulation ``_take`` replaced."""
    order = jnp.argsort(key)
    w_sorted = weights[order]
    incl = jnp.cumsum(w_sorted)
    taken = (incl - w_sorted) < budget if cover else incl <= budget
    return jnp.zeros(key.shape, bool).at[order].set(taken)


def _take_batch(seed, rows, pool):
    """Keys with ties, ``inf``, signed zeros and negatives; spot weights."""
    rng = np.random.default_rng(seed)
    specials = np.asarray([np.inf, -0.0, 0.0, -1.0, 2.0, -3600.0],
                          np.float32)
    key = rng.integers(-4, 5, (rows, pool)).astype(np.float32) * 900.0
    pick = rng.random((rows, pool))
    key = np.where(pick < 0.4, specials[rng.integers(0, 6, (rows, pool))],
                   key).astype(np.float32)
    weights = np.asarray(CORES_TABLE)[rng.integers(0, len(CORES_TABLE),
                                                   (rows, pool))]
    total = weights.sum(axis=1)
    budget = np.round(rng.random(rows) * total * 1.2).astype(np.float32)
    budget[:3] = [0.0, total[1], total[2] + 1.0]
    return jnp.asarray(key), jnp.asarray(weights), jnp.asarray(budget)


@pytest.mark.parametrize("cover", [True, False])
@pytest.mark.parametrize("pool", [1, 4, 160])
@pytest.mark.parametrize("seed", [0, 1])
def test_take_matches_the_indexing_formulation(seed, pool, cover):
    """``_take``'s two sorts give the argsort/gather/scatter marks bit for
    bit under ``vmap``."""
    key, weights, budget = _take_batch(seed, 64, pool)
    got = jax.vmap(lambda k, w, b: billing._take(k, w, b, cover))(
        key, weights, budget)
    want = jax.vmap(lambda k, w, b: _take_by_indexing(k, w, b, cover))(
        key, weights, budget)
    assert got.dtype == want.dtype == jnp.bool_
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_take_ties_signed_zeros_and_breaks_ties_by_slot():
    """A draining slot at ``a = 0`` keys ``-0.0``: the sort ties it with a
    ``0.0`` key, as ``jnp.argsort`` does, and equal keys are taken in
    slot order."""
    key = jnp.asarray([0.0, -0.0, 5.0, 5.0, jnp.inf], jnp.float32)
    ones = jnp.ones((5,), jnp.float32)
    marks = billing._take(key, ones, jnp.asarray(1.0))
    assert np.asarray(marks).tolist() == [True, False, False, False, False]
    marks = billing._take(key[::-1], ones, jnp.asarray(1.0))
    assert np.asarray(marks).tolist() == [False, False, False, True, False]
    marks = billing._take(key, ones, jnp.asarray(3.0))
    assert np.asarray(marks).tolist() == [True, True, True, False, False]


@pytest.mark.parametrize("cover", [True, False])
def test_take_lowers_without_gather_or_scatter(cover):
    """The slot ordering is sorts only: a vmapped per-row gather or
    scatter runs as a scalar loop on the TPU."""
    key, weights, budget = _take_batch(0, 8, 160)
    text = jax.jit(jax.vmap(
        lambda k, w, b: billing._take(k, w, b, cover))).lower(
            key, weights, budget).as_text()
    assert "sort" in text
    assert "gather" not in text
    assert "scatter" not in text
