"""Compile-only rehearsals for one TPU v5e chip, with no chip attached.

The TPU compiler compiles for a described ``v5e:2x2`` topology: what it
refuses here (unaligned tiling, too much fast memory, a program that does
not fit) would be refused on the chip.  Nothing runs, so these tests say
nothing about results or times.

The topology is described only inside a fixture: only one process at a
time may load the TPU library, so describing it while the module is
imported would make pytest-xdist workers collect different tests.
"""

from __future__ import annotations

import dataclasses
import functools
import os
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.kalman_update import kernel as kalman_kernel
from repro.kernels.kalman_update import ops as kalman_ops
from repro.sim import paper_schedule, runner
from repro.sim import sweep as sweep_lib
from repro.sim import workloads as wl

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:          # benchmarks/ is a namespace package
    sys.path.insert(0, str(REPO))
from benchmarks import bench_throughput as bt  # noqa: E402

SCHEDULE = paper_schedule(ttc=7500.0, arrival_gap_ticks=1)
SWEEP_POINTS = 4096
CHUNK = 16384         # the chunk size of chip_smoke.py's sweep


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    # Keep the TPU compiler's logs out of /tmp, outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip_compile():
    """Compile with the persistent cache off (a compile for a described
    chip can be written to it but never read back without one) and with
    every trace cache cleared before and after, so no interpreted trace
    leaks in and no compiled-kernel trace leaks out to other tests."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


def _structs(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), jnp.asarray(x).dtype,
                                       sharding=sharding), tree)


@pytest.mark.parametrize("rows,cols", [
    (4096, 256),                     # 1M estimators
    (CHUNK * SCHEDULE.n, 1),         # a 16,384-point sweep chunk, folded
])
def test_kalman_kernel_compiles_for_v5e(one_chip, chip_compile, rows, cols):
    f32 = jax.ShapeDtypeStruct((rows, cols), jnp.float32, sharding=one_chip)
    mask = jax.ShapeDtypeStruct((rows, cols), jnp.int8, sharding=one_chip)
    fn = functools.partial(kalman_kernel.kalman_fused, sigma_z2=0.5,
                           sigma_v2=0.5, interpret=False)
    compiled = jax.jit(fn).lower(f32, f32, f32, mask).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # The kernel's stable name, which a device trace's op names carry.
    assert re.search(r"%kalman_update[\w.]* = .*tpu_custom_call", text)
    print(compiled.memory_analysis())


def test_sweep_chunk_with_kalman_kernel_compiles_for_v5e(
        one_chip, chip_compile, monkeypatch):
    """The executor's whole chunk program at 4,096 points, bench_throughput
    config, with the compiled Pallas Kalman kernel inside the scan."""
    # On this CPU host the kernel would resolve to interpret mode; the
    # described chip needs the compiled one.
    monkeypatch.setattr(kalman_ops, "resolve_interpret", lambda _: False)
    cfg = bt._cfg()
    cfg = dataclasses.replace(
        cfg, ctrl=dataclasses.replace(cfg.ctrl, kalman_kernel=True))
    # Only the point count shapes the program: seeds x one bid x policies.
    axes = bt._axes(range(SWEEP_POINTS // len(bt.POLICIES)), (2.0,))
    args = _structs((*axes, wl.as_jax_schedule(SCHEDULE),
                     runner.default_params(cfg)), one_chip)
    fn = sweep_lib._sweep_callable(SCHEDULE, cfg, None)
    compiled = fn.lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert re.search(r"%kalman_update[\w.]* = .*tpu_custom_call", text)
    print(compiled.memory_analysis())
