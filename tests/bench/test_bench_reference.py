"""The plain reference (``bench/reference/spot_fleet.py``) agrees with the
program's sweep on a small grid of each deployment, on the CPU."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from bench import harness, testing  # noqa: E402

# Left out of BENCHMARK.json while the program miscounts its TTC allowance
# on the sweep path (PERF.md, Open questions); checked here all the same.
HEADLINE = {"name": "paper_headline.suite", "config": "paper_headline",
            "traffic": "suite_headline", "chips": 1,
            "why": "the paper's own experiment"}


@pytest.mark.parametrize("cell_name,seeds", [
    ("spot_frontier.suite", 2), ("paper_headline.suite", 8)])
def test_reference_agrees_with_the_sweep(tmp_path, cell_name, seeds):
    root = testing.tiny_root(str(tmp_path / "root"), seeds=seeds, ticks=None)
    testing.add_cell(root, HEADLINE)
    cell = harness.Cell(root, cell_name, require_chip=False)
    seed = 3_000_000_023
    prog = cell.dispatch(seed, 1)
    points = cell.points(seed, [1], np.arange(cell.points_per_dispatch))
    ref = cell.reference(points)
    assert set(ref) <= set(prog)
    for field in ref:
        p = np.asarray(prog[field], np.float64)
        if field in cell.ref.COUNTS:
            np.testing.assert_array_equal(p, ref[field], err_msg=field)
        else:
            # Sums over ticks are taken in another order (mean_price).
            np.testing.assert_allclose(p, ref[field], rtol=1e-5,
                                       err_msg=field)


def test_the_reference_compiles_without_a_dump(tmp_path):
    """XLA dumps what the program compiles, for the trace's op names; the
    reference's modules are kept out of that dump."""
    import jax
    import jax.numpy as jnp

    dump = {"xla_dump_to": str(tmp_path), "xla_dump_hlo_as_text": True}

    def program(x):
        return jnp.sin(x) * 2.0

    def point(x):
        return jnp.cos(x) + 1.0

    jax.jit(program, compiler_options=dump)(jnp.ones(3))
    jax.jit(point, compiler_options={**dump, **harness.NO_DUMP})(jnp.ones(3))
    dumped = {name.split(".")[1] for name in os.listdir(tmp_path)
              if name.endswith("after_optimizations.txt")}
    assert dumped == {"jit_program"}
