"""``spot.by_type``: per-slot Table-V lookups as selects, not a gather."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import billing
from repro.core.types import BillingParams, ControlParams
from repro.core.controller import ControllerConfig
from repro.sim import (SimConfig, SpotConfig, default_params, paper_schedule,
                       run, spot)
from repro.sim import runner, workloads

SPECIALS = np.asarray([np.inf, -0.0, 0.0, -1.5, -np.inf, np.nan],
                      np.float32)


def _ids(rng, shape):
    """Type ids of ``shape`` that hold every one of the six types."""
    ids = rng.integers(0, spot.N_TYPES, shape).reshape(-1)
    ids[:spot.N_TYPES] = np.arange(spot.N_TYPES)
    return jnp.asarray(ids.reshape(shape), jnp.int32)


def _case(name):
    rng = np.random.default_rng(7)
    if name == "vmap_batched_table":
        table = rng.lognormal(-3.0, 1.0, (64, spot.N_TYPES)).astype(
            np.float32)
        table[:8] = SPECIALS[rng.permuted(np.tile(np.arange(6), (8, 1)),
                                          axis=1)]
        ids = _ids(rng, (64, 160))
        return (jnp.asarray(table), ids, jax.vmap(spot.by_type),
                jax.vmap(lambda t, i: t[i]))
    if name == "vmap_cores_table":
        ids = _ids(rng, (64, 160))
        return (spot.CORES_TABLE, ids, jax.vmap(spot.by_type, (None, 0)),
                jax.vmap(lambda t, i: t[i], (None, 0)))
    if name == "specials_1d":
        return (jnp.asarray(SPECIALS), _ids(rng, (160,)), spot.by_type,
                lambda t, i: t[i])
    if name == "cores_1d":
        return (spot.CORES_TABLE, _ids(rng, (160,)), spot.by_type,
                lambda t, i: t[i])
    assert name.startswith("specials_scalar_")
    k = int(name.rsplit("_", 1)[1])
    return (jnp.asarray(SPECIALS), jnp.asarray(k, jnp.int32), spot.by_type,
            lambda t, i: t[i])


@pytest.mark.parametrize("name", [
    "vmap_batched_table", "vmap_cores_table", "specials_1d", "cores_1d",
    *[f"specials_scalar_{k}" for k in range(6)]])
def test_by_type_equals_indexing_bit_for_bit(name):
    """The select chain returns ``table[itype]`` itself: ``inf``, ``nan``,
    signed zeros and negatives survive, compared as ``int32`` bit
    patterns."""
    table, ids, fn, ref = _case(name)
    got = np.asarray(fn(table, ids))
    want = np.asarray(ref(table, ids))
    assert got.dtype == want.dtype == np.float32
    assert got.shape == want.shape == ids.shape
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


def test_by_type_lowers_without_gather():
    """Under ``vmap`` with a batched table the lookup is compares and
    selects: a vmapped per-row gather runs as a scalar loop on the TPU."""
    table, ids, _, _ = _case("vmap_batched_table")
    text = jax.jit(jax.vmap(spot.by_type)).lower(table, ids).as_text()
    assert "select" in text
    assert "gather" not in text


def _bits(tree):
    return [(jax.tree_util.keystr(path), np.asarray(leaf))
            for path, leaf in jax.tree_util.tree_leaves_with_path(tree)]


def test_mixed_fleet_run_matches_the_indexing_lookup(monkeypatch):
    """A fleet of all six types, so one pool holds slots of several types:
    every field of the trace equals, bit for bit, the run whose per-slot
    lookups index the table.  A lookup that held only for one type per
    pool would fail here.

    On the default market one 16-CU m4.4xlarge slot serves the suite, so
    the pool never mixes; a fast, volatile market (hourly ``rho`` 0.5,
    ``vol0`` 0.5, uncorrelated types) moves the cheapest type per CU
    between acquisitions."""
    sched = workloads.as_jax_schedule(
        paper_schedule(ttc=7500.0, arrival_gap_ticks=1))
    cfg = SimConfig(
        ctrl=ControllerConfig(params=ControlParams(monitor_dt=300.0),
                              billing=BillingParams(terminate="immediate")),
        ticks=130,
        spot=SpotConfig(enabled=True, fleet=spot.INSTANCE_NAMES,
                        bid_policy="on_demand", rho=0.5, vol0=0.5, corr=0.0))
    seeds = range(4)
    got = [run(sched, cfg, seed=s) for s in seeds]
    scan = runner.cached_scan(sched, cfg, trace=True, with_rt=False)
    live_types = []
    for s in seeds:
        final, _ = scan(sched, s, default_params(cfg))
        live = np.asarray(final.cluster.phase) != billing.OFF
        itype = np.asarray(final.cluster.itype)
        live_types.append(len(np.unique(itype[live])))
    assert max(live_types) > 1, live_types

    calls = []

    def by_indexing(table, itype):
        calls.append(1)
        return table[itype]

    monkeypatch.setattr(runner, "_JIT_CACHE", {})
    monkeypatch.setattr(spot, "by_type", by_indexing)
    want = [run(sched, cfg, seed=s) for s in seeds]
    assert calls, "the run did not trace the per-slot lookups again"

    for s, g, w in zip(seeds, got, want):
        got_leaves, want_leaves = _bits(g), _bits(w)
        assert [p for p, _ in got_leaves] == [p for p, _ in want_leaves]
        for (path, a), (_, b) in zip(got_leaves, want_leaves):
            assert a.dtype == b.dtype and a.shape == b.shape, (s, path)
            assert a.tobytes() == b.tobytes(), (s, path)
