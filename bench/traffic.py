"""Traffic of a cell, made from a mix file and ``--seed``.

A mix file (``bench/mixes/<traffic>.json``) holds only parameters.  This
one generator reads them:

* ``generator: "paper_suite"`` — a deterministic workload schedule: the
  ``rows`` in submission order (family, item count, optional fixed mean
  item cost), one arrival every ``arrival_gap_ticks``, every workload
  asking for ``ttc`` seconds.  Each row's mean item cost is its family's
  ``mean_cus`` times a lognormal jitter of ``jitter_sigma``, drawn in row
  order from ``numpy.random.default_rng(jitter_seed)``; a row with a fixed
  cost still consumes its draw.
* ``grid`` — the points of one dispatch: seeds × bid multiples × bid
  policies × instance types, in that order.  The seeds of dispatch ``d``
  are drawn from ``(seed, d)``, so no two dispatches of a run, and no two
  runs with different ``--seed``, simulate the same Monte-Carlo seeds,
  while every dispatch has the same size and the same grid shape.

The suite and the grid are numpy: the program receives only the arrays.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

# Grid seeds stay below 2**30 so that ``seed + 7919`` (the market's own
# PRNG chain) never wraps a 32-bit integer.
SEED_BOUND = 2**30


class Schedule(NamedTuple):
    """One workload schedule as plain arrays (W rows, one item type)."""

    t_arrive: np.ndarray     # (W,) int32 arrival tick
    family: np.ndarray       # (W,) int32 index into the mix's families
    m0: np.ndarray           # (W, 1) float32 items
    b_true: np.ndarray       # (W, 1) float32 mean CU-seconds per item
    sigma: np.ndarray        # (W,) float32 per-item measurement noise
    c0: np.ndarray           # (W,) float32 ramp floor
    p_r: np.ndarray          # (W,) float32 ramp knee
    overshoot: np.ndarray    # (W,) float32 ramp overshoot
    d_requested: np.ndarray  # (W,) float32 requested TTC, seconds
    valid: np.ndarray        # (W,) bool


class Grid(NamedTuple):
    """The points of one dispatch, flattened in grid order."""

    seed: np.ndarray      # (B,) int32
    bid_mult: np.ndarray  # (B,) float32
    policy: np.ndarray    # (B,) object: bid-policy names
    instance: np.ndarray  # (B,) object: instance-type names


def schedule(mix: dict) -> Schedule:
    if mix.get("generator") != "paper_suite":
        raise ValueError(f"unknown traffic generator {mix.get('generator')!r}")
    fams = list(mix["families"])
    rng = np.random.default_rng(int(mix["jitter_seed"]))
    rows = mix["rows"]
    w = len(rows)
    b_true = np.zeros((w, 1))
    per = {k: np.zeros(w) for k in ("sigma", "c0", "p_r", "overshoot")}
    family = np.zeros(w, np.int32)
    m0 = np.zeros((w, 1))
    for i, row in enumerate(rows):
        name, count = row[0], row[1]
        prm = mix["families"][name]
        family[i] = fams.index(name)
        m0[i, 0] = float(count)
        b_true[i, 0] = prm["mean_cus"] * float(
            rng.lognormal(0.0, float(mix["jitter_sigma"])))
        if len(row) > 2:
            b_true[i, 0] = float(row[2])
        for k in per:
            per[k][i] = prm[k]
    f32 = np.float32
    return Schedule(
        t_arrive=(np.arange(w) * int(mix["arrival_gap_ticks"])).astype(
            np.int32),
        family=family,
        m0=m0.astype(f32), b_true=b_true.astype(f32),
        sigma=per["sigma"].astype(f32), c0=per["c0"].astype(f32),
        p_r=per["p_r"].astype(f32), overshoot=per["overshoot"].astype(f32),
        d_requested=np.full(w, float(mix["ttc"]), f32),
        valid=np.ones(w, bool))


def points_per_dispatch(mix: dict) -> int:
    g = mix["grid"]
    return (int(g["seeds"]) * len(g["bid_mults"]) * len(g["policies"])
            * len(g["instances"]))


def grid_seeds(mix: dict, seed: int, dispatch: int) -> np.ndarray:
    """The Monte-Carlo seeds of dispatch ``dispatch`` of a run."""
    rng = np.random.default_rng([int(seed) % 2**63, int(dispatch)])
    return rng.integers(0, SEED_BOUND, int(mix["grid"]["seeds"])).astype(
        np.int32)


def grid(mix: dict, seed: int, dispatch: int) -> Grid:
    g = mix["grid"]
    s, b, p, i = np.meshgrid(grid_seeds(mix, seed, dispatch),
                             np.asarray(g["bid_mults"], np.float32),
                             np.arange(len(g["policies"])),
                             np.arange(len(g["instances"])), indexing="ij")
    return Grid(seed=s.ravel().astype(np.int32),
                bid_mult=b.ravel().astype(np.float32),
                policy=np.asarray(g["policies"], object)[p.ravel()],
                instance=np.asarray(g["instances"], object)[i.ravel()])

