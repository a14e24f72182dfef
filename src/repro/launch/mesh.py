"""Production meshes for the dry-run, launchers and the sweep engine.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run sets XLA_FLAGS before any jax import; tests import this
module under a single CPU device without side effects).

``make_sweep_mesh`` is the mesh the sweep executor (``sim.sweep``) shards
grid chunks over: one flat ``"batch"`` axis across the host's local
devices.  On CPU CI, multi-device meshes come from the
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` idiom (set in the
environment *before* the first jax import) — the forced host devices are
real XLA devices, so a ``shard_map`` over them exercises the exact
partitioning a TPU/GPU fleet would see.
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import Mesh


def _mk_mesh(shape: tuple, axes: tuple) -> Mesh:
    """``jax.make_mesh`` with Auto axis types."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16×16 (data, model).  Multi-pod: 2×16×16 (pod, data,
    model) — the 'pod' axis composes with 'data' for gradient reduction and
    carries the lowest-frequency collectives across the DCI/ICI boundary."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk_mesh(shape, axes)


def make_host_mesh() -> Mesh:
    """Whatever this host offers (CPU smoke / examples): 1×N (data, model)."""
    return _mk_mesh((1, len(jax.devices())), ("data", "model"))


# The axis name every batch-sharded sweep partitions over.
SWEEP_AXIS = "batch"


def make_sweep_mesh(devices: int | None = None) -> Mesh:
    """A 1-D ``("batch",)`` mesh over up to ``devices`` local devices.

    This is the mesh ``sim.sweep`` shard_maps grid chunks over: the B axis
    of a chunk is partitioned across ``batch``, every device vmapping its
    shard of full simulations (embarrassingly parallel — no collectives).
    ``devices=None`` takes every local device.
    """
    avail = jax.devices()
    n = len(avail) if devices is None else int(devices)
    if not 1 <= n <= len(avail):
        raise ValueError(
            f"devices must be in [1, {len(avail)}] (local devices), got "
            f"{devices}")
    return Mesh(np.asarray(avail[:n]), (SWEEP_AXIS,))
