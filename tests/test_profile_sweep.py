"""The reduction of ``benchmarks/profile_sweep.py`` from trace to shares:
op kinds and source lines read from compiled HLO, and busy time from op
intervals.  The trace itself comes only from a chip."""

import pathlib
import sys

import jax
import jax.numpy as jnp
import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:          # benchmarks/ is a namespace package
    sys.path.insert(0, str(REPO))
from benchmarks import profile_sweep as ps  # noqa: E402


def _permute(x, order):
    # lax.sort, not jnp.argsort: jnp's jitted helpers reuse their first
    # trace in the process, source line included.
    taken = x[order] * 2.0                       # gather
    back = jnp.zeros_like(x).at[order].set(taken)  # scatter
    return back, jax.lax.sort(x)                 # sort


def _line_of(snippet: str) -> int:
    lines = pathlib.Path(__file__).read_text().splitlines()
    return next(i for i, ln in enumerate(lines, 1) if snippet in ln)


@pytest.mark.parametrize("kind,snippet", [
    ("gather", "taken = x[order]"),
    ("scatter", ".at[order].set(taken)"),
    ("sort", "jax.lax.sort(x)"),
])
def test_hlo_index_names_kind_and_source_line(kind, snippet):
    x = jnp.linspace(0.0, 1.0, 160)
    order = jnp.arange(160)[::-1]
    hlo = jax.jit(_permute).lower(x, order).compile().as_text()
    sources = {src for k, src in ps.hlo_index(hlo).values() if k == kind}
    want = f"test_profile_sweep.py:{_line_of(snippet)} _permute"
    assert want in sources, sources


def test_summary_shares_are_of_busy_time():
    index = {"fusion.1": ("gather", "a.py:1 f"),
             "scatter.2": ("scatter", "a.py:2 f"),
             "while.3": ("container", "a.py:3 f")}
    ops = [("fusion.1", 0, 300), ("scatter.2", 200, 200),
           ("while.3", 0, 1000), ("%fusion.1", 600, 100),
           ("copy.9", 700, 100)]
    # The while spans everything; the leaves' union is 0-400 and 600-800.
    assert ps.busy_ns([o for o in ops if o[0] != "while.3"]) == 600
    rep = ps.summarize(ops, index, window_s=2e-6)
    assert rep["busy_ms"] == pytest.approx(1000 / 1e6)
    assert rep["idle_share"] == pytest.approx(0.5)
    assert rep["unmatched_events"] == 1
    shares = {r["key"]: r["share_of_busy"] for r in rep["by_kind"]}
    assert shares == pytest.approx({"gather": 0.4, "scatter": 0.2,
                                    "unmatched": 0.1})
