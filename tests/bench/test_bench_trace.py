"""The benchmark's reduction from a device trace to time by op kind
(``bench/trace.py``), on hand-built op lists and HLO text."""

import os
import sys
import time

import jax
import jax.numpy as jnp
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
from bench import trace as tr  # noqa: E402
from bench.layers import (device_idle_share, elementwise_ns,  # noqa: E402
                          gather_scatter_ns, sort_ns)

HLO = """HloModule jit_step, entry_computation_layout={()->f32[8]}

%fused_scatter (p0: f32[8], p1: s32[8], p2: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  %p1 = s32[8]{0} parameter(1)
  %p2 = f32[8]{0} parameter(2)
  ROOT %scatter.7 = f32[8]{0} scatter(%p0, %p1, %p2), update_window_dims={}
}

%fused_nested (q0: f32[8]) -> f32[8] {
  %q0 = f32[8]{0} parameter(0)
  ROOT %fusion.8 = f32[8]{0} fusion(%q0), kind=kLoop, calls=%fused_scatter
}

%body (b: f32[8]) -> f32[8] {
  %b = f32[8]{0} parameter(0)
  ROOT %gather.3 = f32[8]{0} gather(%b, %b), offset_dims={}
}

ENTRY %main (a: f32[8], i: s32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %i = s32[8]{0} parameter(1)
  %fusion.1 = f32[8]{0} fusion(%a, %i, %a), kind=kLoop, calls=%fused_scatter
  %fusion.2 = f32[8]{0} fusion(%a), kind=kLoop, calls=%fused_nested
  %sort.4 = f32[8]{0} sort(%a), dimensions={0}
  %add.5 = f32[8]{0} add(%a, %a)
  ROOT %while.6 = f32[8]{0} while(%add.5), condition=%body, body=%body
}
"""


def test_hlo_index_classifies_ops_and_fusions_by_what_they_hold():
    index = tr.hlo_index(HLO)
    assert index["fusion.1"] == "scatter"     # a fusion that holds one
    assert index["fusion.2"] == "scatter"     # held two fusions deep
    assert index["gather.3"] == "gather"
    assert index["sort.4"] == "sort"
    assert index["add.5"] == "other"
    assert index["while.6"] == "container"
    assert tr.module_name(HLO) == "jit_step"


def test_hlo_index_reads_a_compiled_module():
    def permute(x, order):
        return jnp.zeros_like(x).at[order].set(x[order] * 2.0)

    x = jnp.linspace(0.0, 1.0, 160)
    hlo = jax.jit(permute).lower(x, jnp.arange(160)[::-1]).compile().as_text()
    kinds = set(tr.hlo_index(hlo).values())
    assert "scatter" in kinds and "gather" in kinds


def test_a_module_dumped_twice_is_read_from_its_newest_dump(tmp_path):
    """After a change to the program, the checkout holds the dumps of the
    old and the new program under one module name: the newest is read,
    even where the old one is larger."""
    old = HLO.replace("%sort.4 = f32[8]{0} sort(%a), dimensions={0}",
                      "%sort.4 = f32[8]{0} sort(%a), dimensions={0}\n"
                      "  %pad.10 = f32[8]{0} add(%a, %a)")
    new = HLO.replace("%fusion.1 = f32[8]{0} fusion(%a, %i, %a), kind=kLoop,"
                      " calls=%fused_scatter",
                      "%fusion.1 = f32[8]{0} add(%a, %a)")
    for i, (name, text) in enumerate([("module_0001", old),
                                      ("module_0009", new)]):
        path = tmp_path / f"{name}.jit_step.tpu_after_optimizations.txt"
        path.write_text(text)
        os.utime(path, (time.time() - 60 + i, time.time() - 60 + i))
    (tmp_path / "module_0003.jit_step.before_optimizations.txt").write_text(
        old)
    assert len(old) > len(new)
    indexes = tr.dump_indexes(str(tmp_path))
    assert set(indexes) == {"jit_step"}
    assert indexes["jit_step"]["fusion.1"] == "other"
    assert "pad.10" not in indexes["jit_step"]


def test_busy_time_is_the_union_of_overlapping_ops():
    ops = [("fusion.1", 0, 300, ""), ("scatter.2", 200, 200, ""),
           ("%fusion.1", 600, 100, ""), ("copy.9", 650, 150, "")]
    assert tr.busy_ns(ops) == 400 + 200
    assert tr.idle_gaps(ops, 0, 1000) == [(400, 600), (800, 1000)]


def test_kinds_per_module_and_the_layer_readers():
    indexes = {"jit_step": tr.hlo_index(HLO),
               "jit_other": {"fusion.1": "other"}}
    ops = [("fusion.1", 0, 300, "jit_step"), ("gather.3", 300, 100, ""),
           ("sort.4", 400, 50, "jit_step"), ("while.6", 0, 1000, "jit_step"),
           ("fusion.1", 500, 40, "jit_other"), ("add.5", 540, 10, "jit_step")]
    kinds = tr.by_kind(ops, indexes)
    assert kinds == {"scatter": 300, "gather": 100, "sort": 50,
                     "other": 50}
    ctx = {"by_kind_ns": dict(kinds), "busy_s": 0.75, "window_s": 1.0,
           "point_ticks": 10, "chips": 1}
    assert gather_scatter_ns.read(ctx) == pytest.approx(40.0)
    assert sort_ns.read(ctx) == pytest.approx(5.0)
    assert elementwise_ns.read(ctx) == pytest.approx(5.0)
    assert device_idle_share.read(ctx) == pytest.approx(0.25)
    top = dict(tr.top_ops(ops, indexes, 2))
    assert top == {"fusion.1 (scatter)": 300, "gather.3 (gather)": 100}


def test_readers_return_nothing_when_there_is_nothing_to_read():
    unjoined = {"by_kind_ns": {"unmatched": 900, "gather": 100},
                "busy_s": 0.0, "window_s": 1.0, "point_ticks": 10,
                "chips": 1}
    assert gather_scatter_ns.read(unjoined) is None
    assert sort_ns.read(unjoined) is None
    assert device_idle_share.read(unjoined) is None
