"""``correct`` comes out false when the timed path is broken underneath,
and the control (the reference one precision lower) fails the limits.

Each fault test drives a whole run of a cell without the look for a chip,
at a size the CPU holds, with one fault planted in the program."""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from bench import harness, testing  # noqa: E402
from repro.sim import runner  # noqa: E402
from repro.sim import sweep as sweep_lib  # noqa: E402

CELL = "spot_frontier.suite"


# The four-chip cell, added to the test's checkout where BENCHMARK.json
# does not hold it.
X4 = {"name": "spot_frontier.suite_x4", "config": "spot_frontier",
      "traffic": "suite_frontier_x4", "chips": 4,
      "why": "the frontier grid sharded over four chips"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    root = testing.tiny_root(str(tmp_path_factory.mktemp("bench")))
    testing.add_cell(root, X4)
    return root


@pytest.fixture
def fresh_compile(monkeypatch):
    """A run compiles anew: the planted fault is in the traced program."""
    monkeypatch.setattr(runner, "_JIT_CACHE", {})


def test_a_sound_run_is_correct(root, fresh_compile):
    rc, result, err = testing.run_cell(root, CELL)
    assert rc == 0, err
    assert result["correct"] is True, err
    assert result["failed"] == 0


def _frozen_step(make_step):
    def patched(*args, **kwargs):
        make_step(*args, **kwargs)
        return lambda state, _: (state, None)
    return patched


def _half_batch(callable_):
    def patched(*args, **kwargs):
        fn = callable_(*args, **kwargs)

        def half(*xs):
            out = fn(*xs)
            return jax.tree.map(
                lambda a: jnp.concatenate([a[:a.shape[0] // 2]] * 2), out)
        return half
    return patched


def _altered_answer(summarize):
    def patched(*args, **kwargs):
        s = summarize(*args, **kwargs)
        return s._replace(cost=s.cost * 1.001)
    return patched


@pytest.mark.parametrize("target,attr,fault", [
    (runner, "make_step", _frozen_step),
    (sweep_lib, "_sweep_callable", _half_batch),
    (sweep_lib, "summarize", _altered_answer),
], ids=["state_unchanged", "half_batch", "answer_altered"])
def test_a_broken_timed_path_is_not_correct(root, fresh_compile, monkeypatch,
                                            target, attr, fault):
    monkeypatch.setattr(target, attr, fault(getattr(target, attr)))
    rc, result, err = testing.run_cell(root, CELL)
    assert rc == 0, err
    assert result["correct"] is False, err
    assert result["checks"]["diverged_share"]["value"] >= 0.5


_FOUR = """
import json, sys, time
sys.path.insert(0, {repo!r})
import jax, jax.numpy as jnp
from bench import testing
from repro.sim import sweep as sweep_lib
if {fault!r} == "exchange_left_out":
    make = sweep_lib._sweep_callable
    def patched(workload, cfg, mesh, donate=False):
        fn = make(workload, cfg, mesh, donate=donate)
        if mesh is None:
            return fn
        def first_shard_only(*xs):
            out = fn(*xs)
            return jax.tree.map(lambda a: jnp.tile(
                a[:a.shape[0] // mesh.size],
                (mesh.size,) + (1,) * (a.ndim - 1)), out)
        return first_shard_only
    sweep_lib._sweep_callable = patched
rc, result, err = testing.run_cell({root!r}, "spot_frontier.suite_x4")
print(json.dumps({{"rc": rc, "result": result, "n": len(jax.devices())}}))
"""


@pytest.mark.parametrize("fault,correct", [("none", True),
                                           ("exchange_left_out", False)])
def test_four_devices_and_the_exchange_left_out(root, fault, correct):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _FOUR.format(repo=REPO, root=root,
                                            fault=fault)],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n"] == 4 and out["rc"] == 0
    assert out["result"]["device"]["count"] == 4
    assert out["result"]["correct"] is correct, proc.stderr[-3000:]


def test_the_control_fails_the_limits(root):
    cell = harness.Cell(root, CELL, require_chip=False)
    rows = np.arange(cell.points_per_dispatch)
    points = cell.points(3_000_000_021, [1], rows)
    ref = cell.reference(points)
    low = cell.reference(points, dtype=jnp.bfloat16)
    nums = cell.numbers(low, ref)
    assert any(v > cell.limits[k] for k, v in nums.items()), nums
    assert cell.numbers(ref, ref) == {"diverged_share": 0.0,
                                      "median_gap": 0.0}
