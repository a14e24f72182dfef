"""The sweep executor's own measurement (``repro.obs.spans``): host spans
and chunk steps in the profiler's trace, the profile's timings read off
those spans, the compile counters, the phase scopes of the scan step and
the Pallas kernel's name."""

import dataclasses
import json
import os
import re
import subprocess
import sys
import tempfile
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import controller as ctrl
from repro.core.controller import ControllerConfig
from repro.core.types import BillingParams, ControlParams
from repro.obs import spans
from repro.sim import SimConfig, SpotConfig, SweepSpec, make_axes
from repro.sim import paper_schedule, runner
from repro.sim.sweep import sweep

SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "src")

# A profiled chunked sweep of 13 points in chunks of 4 on four CPU
# devices, warmed up first, then run twice under the profiler: plain and
# profiled.  It prints the program's spans as the trace holds them.
_TRACED_SWEEP = r"""
import glob, json, os, sys, dataclasses
import jax
from jax.profiler import ProfileData
from repro.core.controller import ControllerConfig
from repro.core.types import BillingParams, ControlParams
from repro.sim import SimConfig, SpotConfig, SweepSpec, make_axes
from repro.sim import paper_schedule
from repro.sim.sweep import sweep

cfg = SimConfig(ctrl=ControllerConfig(
    params=ControlParams(monitor_dt=300.0),
    billing=BillingParams(terminate="immediate")), ticks=12,
    spot=SpotConfig(enabled=True))
sched = paper_schedule(ttc=7500.0, arrival_gap_ticks=1)
axes = make_axes(range(13), [1.1])
plain = SweepSpec(axes=axes, workload=sched, chunk_size=4)
profiled = SweepSpec(axes=axes, workload=sched, chunk_size=4, profile=True)
sweep(plain, cfg)
with jax.profiler.trace(sys.argv[1]):
    sweep(plain, cfg)
    report = sweep(profiled, cfg)
path, = glob.glob(os.path.join(sys.argv[1], "plugins", "profile", "*",
                               "*.xplane.pb"))
events = []
for plane in ProfileData.from_file(path).planes:
    if plane.name.startswith("/device:"):
        continue
    for line in plane.lines:
        for ev in line.events:
            if ev.name == "sweep_chunk" or ev.name.startswith("sweep"):
                events.append([ev.name, int(ev.start_ns),
                               int(ev.start_ns + ev.duration_ns)])
print(json.dumps({"devices": len(jax.devices()), "events": sorted(
    events, key=lambda e: e[1]), "chunks": [dataclasses.asdict(c)
                                            for c in report.chunks]}))
"""


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=SRC)
    proc = subprocess.run(
        [sys.executable, "-c", _TRACED_SWEEP,
         str(tmp_path_factory.mktemp("trace"))],
        env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["devices"] == 4
    sweeps = [e for e in out["events"] if e[0] == "sweep"]
    assert len(sweeps) == 2
    # Each event with the sweep it lies in.
    out["inside"] = [[e for e in out["events"]
                      if s[1] <= e[1] and e[2] <= s[2] and e is not s]
                     for s in sweeps]
    return out


def test_a_traced_chunked_sweep_shows_its_spans_nested_in_sweep(traced):
    names = {e[0] for e in traced["events"]}
    assert {"sweep", "sweep.prepare", "sweep.lookup", "sweep.slice_pad",
            "sweep.launch", "sweep.to_host", "sweep.assemble",
            "sweep_chunk"} <= names
    for inside in traced["inside"]:
        # Four chunks a sweep, each one step holding its own phases.
        steps = [e for e in inside if e[0] == "sweep_chunk"]
        assert len(steps) == 4
        for name in ("sweep.slice_pad", "sweep.launch", "sweep.to_host"):
            held = [e for e in inside if e[0] == name]
            assert len(held) == 4
            assert all(any(s[1] <= e[1] and e[2] <= s[2] for s in steps)
                       for e in held)
        assert [e[0] for e in inside].count("sweep.prepare") == 1
    # Every program span lies in one of the two sweeps.
    held = sum(len(inside) for inside in traced["inside"])
    assert held == len(traced["events"]) - 2
    # Only the profiled sweep compiles ahead of time, in its first chunk.
    assert [e[0] for e in traced["inside"][0]].count("sweep.compile") == 0
    assert [e[0] for e in traced["inside"][1]].count("sweep.compile") == 1


def test_chunk_profile_timings_are_the_span_durations(traced):
    inside = traced["inside"][1]           # the profiled sweep
    launch = [e for e in inside if e[0] == "sweep.launch"]
    to_host = [e for e in inside if e[0] == "sweep.to_host"]
    chunks = traced["chunks"]
    assert [c["chunk"] for c in chunks] == [0, 1, 2, 3]
    for c, a, b in zip(chunks, launch, to_host):
        span_s = (a[2] - a[1] + b[2] - b[1]) / 1e9
        # Two clocks read around one stretch of the host's work.
        assert abs(c["execute_s"] - span_s) <= 2e-3 + 0.05 * span_s
    compile_span, = [e for e in inside if e[0] == "sweep.compile"]
    assert chunks[0]["compile_s"] == pytest.approx(
        (compile_span[2] - compile_span[1]) / 1e9, rel=0.05, abs=2e-3)


def _cfg(pool: int) -> SimConfig:
    return SimConfig(
        ctrl=ControllerConfig(params=ControlParams(monitor_dt=300.0),
                              billing=BillingParams(terminate="immediate")),
        ticks=6, pool=pool, spot=SpotConfig(enabled=True))


def test_a_first_sweep_compiles_and_a_second_of_its_shape_does_not():
    # A pool size no other test uses: the first call misses every cache.
    cfg = _cfg(pool=161)
    spec = SweepSpec(axes=make_axes(range(3), [1.2]),
                     workload=paper_schedule(ttc=7500.0,
                                             arrival_gap_ticks=1))
    before = spans.counters()
    sweep(spec, cfg)
    first = spans.counters()
    assert first["compiles"] >= before["compiles"] + 1
    assert first["trace_s"] > before["trace_s"]
    assert first["compile_s"] > before["compile_s"]
    assert first["callable_misses"] == before["callable_misses"] + 1
    assert first["sweeps"] == before["sweeps"] + 1
    assert first["last_sweep"] == {k: first[k] for k in (
        "trace_s", "compile_s", "compiles", "callable_misses")}
    assert first["first_sweep"] is not None
    sweep(spec, cfg)
    second = spans.counters()
    for k in ("trace_s", "compile_s", "compiles", "callable_misses"):
        assert second[k] == first[k], k
    assert second["first_sweep"] == first["first_sweep"]


def test_nested_trace_time_is_counted_once(monkeypatch):
    """An inner trace and a compile inside an outer trace: their seconds
    are the outer's exclusive time plus their own, not the sum of all."""
    monkeypatch.setattr(spans, "_counts", dict(spans._counts, trace_s=0.0,
                                               compile_s=0.0, compiles=0))
    mon = jax.monitoring
    mon.record_scalar(spans._TRACE, 0.0)           # the outer trace starts
    mon.record_scalar(spans._TRACE, 0.0)           # an inner one
    mon.record_event_duration_secs(spans._TRACE, 0.25)
    mon.record_scalar(spans._COMPILE, 0.0)         # a compile inside
    mon.record_event_duration_secs(spans._COMPILE, 0.5)
    mon.record_event_duration_secs(spans._TRACE, 1.0)   # the outer ends
    mon.record_scalar(spans._LOWER, 0.0)
    mon.record_event_duration_secs(spans._LOWER, 0.125)
    got = spans.counters()
    assert got["trace_s"] == pytest.approx(0.25 + (1.0 - 0.75) + 0.125)
    assert got["compile_s"] == pytest.approx(0.5)
    assert got["compiles"] == 1
    assert spans._stack() == []


def _op_names(hlo: str) -> list[str]:
    return re.findall(r'op_name="([^"]*)"', hlo)


@pytest.mark.parametrize("use_kernel", [False, True])
def test_the_kalman_scope_holds_the_filter_update_on_both_paths(use_kernel):
    """``controller.step`` runs the Kalman bank's update under ``kalman``,
    through the jnp path and through the Pallas kernel (interpreted on
    the CPU)."""
    cfg = SimConfig(ticks=4)
    cc = dataclasses.replace(cfg.ctrl, kalman_kernel=use_kernel)
    st = runner.init_state(paper_schedule(ttc=7500.0, arrival_gap_ticks=1),
                           cfg)
    meas = jnp.ones(st.work.m.shape)
    mask = jnp.ones(st.work.m.shape, bool)

    def step(st, meas):
        return ctrl.step(st.c, st.work, st.cluster, meas, mask, meas, meas,
                         cc)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", UserWarning)   # interpret mode
        hlo = jax.jit(step).lower(st, meas).compile().as_text()
    scoped = [n for n in _op_names(hlo) if "kalman" in n.split("/")]
    assert scoped
    assert any("kalman_update" in n for n in scoped) == use_kernel
    assert not any("kalman_update" in n for n in _op_names(hlo)
                   if n not in scoped)


def test_a_sweep_gives_the_same_summaries_with_its_spans_on():
    """The spans and scopes leave results alone: a sweep traced under the
    profiler returns what an untraced one does, bit for bit."""
    cfg = _cfg(pool=160)
    spec = SweepSpec(axes=make_axes(range(2), [1.1, 1.4]),
                     workload=paper_schedule(ttc=7500.0,
                                             arrival_gap_ticks=1))
    a = sweep(spec, cfg)
    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            b = sweep(spec, cfg)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
