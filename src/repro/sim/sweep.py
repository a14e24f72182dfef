"""Mesh-sharded, disk-streaming, resumable sweeps over the simulator.

The entire simulation — correlated multi-type market process, billing,
preemption, controller, workload execution — is one pure ``lax.scan``
(``runner.scan_run``), so a cost sweep over seeds × bid levels × bid
policies × fleet mixes × workload scenarios is a single
``jax.jit(jax.vmap(...))`` call: one compile, one device dispatch, every
grid point in parallel.  Sweeps run the scan in **summary mode**
(``runner.scan_run(trace=False)``): the eight per-run scalars accumulate
inside the scan carry and the scan emits no per-tick outputs, so a B-point
grid moves O(B) floats instead of the O(B·T·W·K) a stacked trace would.

The public entry point is one facade over one frozen spec::

    spec = SweepSpec(axes=make_axes(...), workload=schedule_or_set,
                     chunk_size=1024, devices=4, stream_dir="out/sweep")
    result = sweep(spec, cfg)

``SweepSpec`` bundles the experiment grid (:class:`SweepAxes`), the
workload world (a static schedule, a ``scenarios.ScenarioSet``, or a
``tenants.TenantSet`` for shared-fleet runs) and the execution options —
validated in exactly one place (``SweepSpec.__post_init__``):

  * ``chunk_size`` — micro-batch the B axis: every chunk is padded to one
    shape and pushed through one cached compiled callable (one compile for
    any grid size, live memory bounded by the chunk);
  * ``devices`` / ``mesh`` — shard each chunk's B axis over a 1-D
    ``("batch",)`` device mesh (``launch.mesh.make_sweep_mesh``) with
    ``jax.shard_map``, every device vmapping its shard (no collectives).
    Chunks are padded up to a device multiple — explicitly, and asserted
    never to reach a result;
  * ``stream_dir`` — stream each completed chunk's summaries to disk
    (atomic ``checkpoint.checkpointer`` chunk files + a manifest) instead
    of returning in-memory arrays: ``sweep`` then returns a
    :class:`SweepStream` handle, an interrupted sweep resumes from the
    last committed chunk, and peak host memory stays O(chunk) no matter
    the grid size.

``run_sweep`` / ``tenants.tenant_sweep`` survive as thin deprecated
wrappers that build the equivalent ``SweepSpec``; ``run_single`` is the
loop-of-one reference the vmapped engine is tested against.

Axes:
  * ``seed``      — Monte-Carlo replication (market + execution noise +
                    scenario sampling);
  * ``bid_mult``  — bid as a multiple of the base spot price (the 'ema'
                    policy's EMA multiple and the 'ttc' policy's floor;
                    ignored under 'on_demand');
  * ``policy``    — bid policy (``spot.BID_POLICIES``): static multiple,
                    on-demand cap, TTC-aware, market-aware EMA.  The
                    sentinel -1 defers to ``cfg.spot.bid_policy``;
  * ``itype`` / ``mix`` — fleet mix over the Appendix-A Table V types:
                    ``mix`` is the (T,)-mask of allowed types,  ``itype``
                    the mix's primary type (reported in the trace);
  * ``scenario``  — which workload world the run lives in.  With a
                    ``scenarios.ScenarioSet`` the id picks the generator
                    (``lax.switch``) and each grid point samples its own
                    schedule from (seed, scenario); with a plain
                    ``Schedule`` or a ``TenantSet`` the axis must be
                    all-zero.

Schedules are *traced pytree inputs* of the compiled sweep, not constants
closed over at trace time: compilation caches key on the schedule's shape
(``workloads.schedule_shape``) or on the scenario/tenant specs, so two
schedules of one shape — or any number of generated scenarios — share one
compile.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import shutil
import warnings
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec

from ..checkpoint import checkpointer
from ..core.types import PolicyParams
from ..launch import mesh as mesh_lib
from ..obs import spans
from . import faults as faults_lib
from . import runner, spot
from . import scenarios as scen_lib
from . import workloads as wl

FleetMix = Sequence[str | int] | str | int
ScheduleLike = "wl.Schedule | wl.JaxSchedule | scen_lib.ScenarioSet"


class SweepAxes(NamedTuple):
    """The flattened experiment grid (B = len of every field)."""

    seed: jnp.ndarray      # (B,) int32
    bid_mult: jnp.ndarray  # (B,) float32
    itype: jnp.ndarray     # (B,) int32 primary type per fleet mix
    policy: jnp.ndarray    # (B,) int32 BID_POLICIES id (-1: use config's)
    mix: jnp.ndarray       # (B, T) float32 fleet-membership masks
    scenario: jnp.ndarray  # (B,) int32 scenario id (0 = first/only)


class RunSummary(NamedTuple):
    """Per-run scalars (each (B,)-shaped after the vmap)."""

    cost: jnp.ndarray          # $ at last completion; full horizon if
                               # submitted work never finished
    cost_horizon: jnp.ndarray  # $ at the end of the simulation window
    violations: jnp.ndarray    # TTC violations (incl. unfinished workloads)
    preemptions: jnp.ndarray   # instances reclaimed by the market
    finished: jnp.ndarray      # workloads completed
    max_committed: jnp.ndarray # peak control-plane fleet, in CUs
    mean_price: jnp.ndarray    # mean $/quantum of the primary type
    max_price: jnp.ndarray     # worst $/quantum seen (primary type)
    # Total in-scan detector alerts (obs.detect).  ``None`` — a leafless
    # pytree, absent from compiled programs and chunk files — whenever the
    # config carries no detector spec, so every pre-detector summary
    # consumer (digests, streams, parity tests) is untouched.
    alerts: jnp.ndarray | None = None


def _alert_count(final) -> jnp.ndarray | None:
    """Total detector alerts from the final carry — ``None`` (leafless)
    unless the run carried ``ObsSpec.detect`` registers."""
    obs_c = getattr(final, "obs", None)
    if obs_c is None or getattr(obs_c, "detect", None) is None:
        return None
    return jnp.sum(obs_c.detect.n_alerts).astype(jnp.int32)


def summarize(final, schedule: wl.Schedule | wl.JaxSchedule,
              cfg: runner.SimConfig,
              valid: jnp.ndarray | None = None) -> RunSummary:
    """Read one run's summary out of the final scan carry, jnp-pure.

    Every statistic was accumulated inside the scan (``runner.SummaryCarry``
    plus the cost/preemption registers ``ClusterState`` already carries), so
    this needs no per-tick trace — it is the read-out both trace- and
    summary-mode runs share, which is what makes the two modes bit-identical
    by construction.

    ``valid`` is the explicit workload-valid mask (default: the schedule's
    own): padded rows are excluded from the finished count, the violation
    count and the cost-at-completion endpoint, so a generated scenario's
    padding can never inflate — or deflate — a summary.
    """
    sched = wl.as_jax_schedule(schedule)
    if valid is None:
        valid = sched.valid
    work = final.work
    submitted = (work.t_submit >= 0) & valid
    finished = (work.t_done >= 0) & valid
    unfinished = jnp.any(submitted & ~finished)
    t_end = jnp.max(jnp.where(valid, work.t_done, -1))
    # ``cost_at_done`` is the trace's ``cum_cost[t_end + 1]``; the register
    # never fired when nothing finished, a completion landed on the last
    # tick, or submitted work is still running — all cases the trace-mode
    # ``cost_at_completion`` resolves to the full-horizon bill.  The
    # register tracks the *unmasked* last completion, so if an explicit
    # ``valid`` hides a later-finishing row it holds the wrong endpoint —
    # bill to the horizon then too (conservative; never under-reports).
    # With the default mask this never triggers: padding cannot finish.
    register_stale = t_end != jnp.max(work.t_done)
    use_horizon = (unfinished | (t_end < 0) | (t_end + 1 > cfg.ticks - 1)
                   | register_stale)
    cost = jnp.where(use_horizon, final.cluster.cum_cost,
                     final.summ.cost_at_done)
    return RunSummary(
        cost=cost,
        cost_horizon=final.cluster.cum_cost,
        violations=runner.count_violations(work, sched, cfg, valid=valid),
        preemptions=final.cluster.n_preempt,
        finished=jnp.sum(finished.astype(jnp.int32)),
        max_committed=final.summ.max_committed,
        mean_price=final.summ.price_sum / cfg.ticks,
        max_price=final.summ.price_max,
        alerts=_alert_count(final),
    )


def summarize_trace(final, ys, schedule: wl.Schedule | wl.JaxSchedule,
                    cfg: runner.SimConfig,
                    valid: jnp.ndarray | None = None) -> RunSummary:
    """Collapse a *trace-mode* run's stacked scan outputs to scalars.

    The pre-summary-mode implementation, kept as the independent reference
    the carry registers are tested against (``tests/test_throughput.py``).
    ``mean_price`` is the only field whose reduction order differs from the
    in-carry accumulation (parallel vs sequential float sum); everything
    else is bit-identical.
    """
    sched = wl.as_jax_schedule(schedule)
    if valid is None:
        valid = sched.valid
    work = final.work
    finished = (work.t_done >= 0) & valid
    return RunSummary(
        cost=runner.cost_at_completion(work, ys["cum_cost"], valid=valid),
        cost_horizon=ys["cum_cost"][-1],
        violations=runner.count_violations(work, sched, cfg, valid=valid),
        preemptions=ys["n_preempted"][-1],
        finished=jnp.sum(finished.astype(jnp.int32)),
        max_committed=jnp.max(ys["n_committed"]),
        mean_price=jnp.mean(ys["spot_price"]),
        max_price=jnp.max(ys["spot_price"]),
        alerts=_alert_count(final),
    )


def _as_mix(entry: FleetMix) -> tuple[int, np.ndarray]:
    """Normalize one fleet-mix spec to (primary itype, (T,) mask)."""
    if isinstance(entry, (str, int)):
        entry = (entry,)
    members = [spot.instance_index(m) if isinstance(m, str) else int(m)
               for m in entry]
    if not members:
        raise ValueError("a fleet mix needs at least one instance type")
    mask = np.zeros((spot.N_TYPES,), np.float32)
    mask[members] = 1.0
    return members[0], mask


def _scenario_ids(scenarios) -> list[int]:
    """Normalize the ``scenarios`` argument of ``make_axes`` to id list."""
    if scenarios is None:
        return [0]
    if isinstance(scenarios, int):
        return list(range(scenarios))
    if isinstance(scenarios, scen_lib.ScenarioSet):
        return list(range(len(scenarios)))
    return [int(s) for s in scenarios]


def make_axes(seeds: Sequence[int],
              bid_mults: Sequence[float],
              instances: Sequence[FleetMix] = ("m3.medium",),
              policies: Sequence[str | int] | None = None,
              scenarios=None) -> SweepAxes:
    """Cartesian-product grid, flattened to (B,) arrays.

    ``instances`` entries are fleet mixes: a single type name/id (the
    classic granularity axis) or a sequence of them (a heterogeneous
    fleet).  ``policies`` are ``spot.BID_POLICIES`` names/ids; the default
    defers to ``cfg.spot.bid_policy`` at sweep time.  ``scenarios`` is the
    workload-world axis: a ``scenarios.ScenarioSet`` (enumerated), a count,
    or explicit ids; the default is the single scenario 0.  Grid order is
    seeds × bid_mults × policies × mixes × scenarios, so reshaping a
    summary field to ``(len(seeds), len(bid_mults), len(policies),
    len(instances), n_scenarios)`` recovers the axes.
    """
    primaries, masks = zip(*(_as_mix(e) for e in instances))
    if policies is None:
        pol_ids = [-1]
    else:
        pol_ids = [spot.bid_policy_index(p) if isinstance(p, str) else int(p)
                   for p in policies]
    scen_ids = _scenario_ids(scenarios)
    s, b, p, m, c = np.meshgrid(np.asarray(seeds),
                                np.asarray(bid_mults, float),
                                np.asarray(pol_ids),
                                np.arange(len(masks)),
                                np.asarray(scen_ids), indexing="ij")
    mix = np.stack(masks)[m.ravel()]
    return SweepAxes(seed=jnp.asarray(s.ravel(), jnp.int32),
                     bid_mult=jnp.asarray(b.ravel(), jnp.float32),
                     itype=jnp.asarray(np.asarray(primaries)[m.ravel()],
                                       jnp.int32),
                     policy=jnp.asarray(p.ravel(), jnp.int32),
                     mix=jnp.asarray(mix, jnp.float32),
                     scenario=jnp.asarray(c.ravel(), jnp.int32))


# --------------------------------------------------------------------------
# The unified spec: one frozen object holds the grid, the workload world
# and every execution option, validated in exactly one place.

def _is_tenant_set(workload) -> bool:
    # Lazy import: sim.tenants imports this module.
    from . import tenants as tenants_lib
    return isinstance(workload, tenants_lib.TenantSet)


@dataclasses.dataclass(frozen=True, eq=False)
class SweepSpec:
    """Everything one sweep needs, besides the ``SimConfig``.

    ``axes`` is the flattened grid (``make_axes``); ``workload`` the world
    every grid point runs in — a static ``workloads.Schedule`` /
    ``JaxSchedule``, a ``scenarios.ScenarioSet`` (the ``scenario`` axis
    picks the generator, each point samples its own schedule from (seed,
    scenario)), or a ``tenants.TenantSet`` (shared-fleet runs returning a
    ``TenantRun`` instead of a ``RunSummary``); ``params`` one
    ``PolicyParams`` pytree broadcast to every point (default: the
    config's own coefficients).

    Execution options (keyword-only, validated here and nowhere else):

      * ``chunk_size`` — micro-batch size (≥ 1).  ``None`` = whole grid in
        one batch.  Chunks are padded up to one common, device-divisible
        shape; padded rows are asserted never to reach a result or a chunk
        file.
      * ``devices`` — shard each chunk over this many local devices (≥ 1,
        capped at the host's device count and the grid size) via
        ``jax.shard_map`` on a 1-D ``("batch",)`` mesh.  ``None`` = all
        local devices.  Mutually exclusive with ``mesh``.
      * ``mesh`` — an explicit 1-axis ``jax.sharding.Mesh`` to shard over
        instead (e.g. ``launch.mesh.make_sweep_mesh()``).
      * ``stream_dir`` — stream completed chunks to this directory instead
        of returning in-memory arrays: ``sweep`` returns a
        :class:`SweepStream` handle (call ``.load()`` to materialize), and
        an interrupted sweep re-run with the same spec resumes from the
        last committed chunk.
      * ``resume`` — with ``stream_dir``: reuse committed chunks found in
        the directory (the default).  ``False`` discards them and
        recomputes from scratch.
      * ``profile`` — wrap the result in a :class:`SweepReport` carrying
        per-chunk wall-clock (compile vs execute vs chunk-write), the XLA
        peak-bytes estimate, and a Perfetto trace exporter
        (``report.write_trace``).
    """

    axes: SweepAxes
    workload: object
    params: PolicyParams | None = None
    # Traced fault intensities (``sim.faults``): a ``FaultSpec`` whose
    # leaves are scalars (one chaos world for the whole grid) or
    # (B,)-leading arrays (fault timing/intensity as a first-class sweep
    # axis — chaos sweeps chunk/shard/stream like everything else).
    # Requires ``SimConfig.faults`` to be set; None rides the fault-free
    # spec when the config enables the engine.
    faults: "faults_lib.FaultSpec | None" = None
    chunk_size: int | None = dataclasses.field(default=None, kw_only=True)
    devices: int | None = dataclasses.field(default=None, kw_only=True)
    mesh: Mesh | None = dataclasses.field(default=None, kw_only=True)
    stream_dir: str | os.PathLike | None = dataclasses.field(
        default=None, kw_only=True)
    resume: bool = dataclasses.field(default=True, kw_only=True)
    # Runtime profiling (``repro.obs`` plane iii): per-chunk wall clock
    # with the compile vs execute split (AOT ``lower().compile()`` on the
    # first chunk), XLA peak-bytes estimate, and — when streaming — the
    # chunk-write time, each the duration of the sweep's own host spans
    # (``obs.spans``).  ``sweep`` then returns a :class:`SweepReport`
    # wrapping the unchanged result; the stream manifest gains a
    # ``"profile"`` record.  Off by default: an unprofiled sweep compiles
    # through the jit cache, with no AOT compile of its own.
    profile: bool = dataclasses.field(default=False, kw_only=True)

    def __post_init__(self):
        # THE validation point for every execution option (the per-function
        # ad-hoc checks the old run_sweep grew are all retired into here).
        if not isinstance(self.axes, SweepAxes):
            raise TypeError(
                f"axes must be a SweepAxes (see make_axes), got "
                f"{type(self.axes).__name__}")
        b = int(np.shape(self.axes.seed)[0])
        if b < 1:
            raise ValueError("the sweep grid is empty (B = 0)")
        lens = {f: int(np.shape(getattr(self.axes, f))[0])
                for f in SweepAxes._fields}
        if set(lens.values()) != {b}:
            raise ValueError(
                f"axes fields disagree on the grid size: {lens}")
        if self.faults is not None:
            if not isinstance(self.faults, faults_lib.FaultSpec):
                raise TypeError(
                    f"faults must be a FaultSpec (see "
                    f"sim.faults.make_fault_spec), got "
                    f"{type(self.faults).__name__}")
            for name, leaf in zip(faults_lib.FaultSpec._fields, self.faults):
                shape = np.shape(leaf)
                if shape not in ((), (b,)) and not (
                        len(shape) >= 1 and shape[0] == b):
                    raise ValueError(
                        f"FaultSpec.{name} must be a scalar or lead with "
                        f"the grid axis B={b}, got shape {shape}")
        if self.chunk_size is not None and int(self.chunk_size) < 1:
            raise ValueError(
                f"chunk_size must be >= 1, got {self.chunk_size}")
        if self.devices is not None and int(self.devices) < 1:
            raise ValueError(f"devices must be >= 1, got {self.devices}")
        if self.mesh is not None:
            if self.devices is not None:
                raise ValueError(
                    "pass either devices= or mesh=, not both")
            if len(self.mesh.axis_names) != 1:
                raise ValueError(
                    "the sweep mesh must have exactly one (batch) axis, "
                    f"got axes {self.mesh.axis_names} — use "
                    "launch.mesh.make_sweep_mesh")
        if self.stream_dir is not None:
            sd = os.fspath(self.stream_dir)
            if not sd:
                raise ValueError("stream_dir must be a non-empty path")
            if os.path.isfile(sd):
                raise ValueError(f"stream_dir {sd!r} is a file")

    @property
    def n_points(self) -> int:
        return int(np.shape(self.axes.seed)[0])


# --------------------------------------------------------------------------
# Per-point programs (the in-jit surface ``repro.opt`` builds on).

def _check_axes(cfg: runner.SimConfig, axes: SweepAxes,
                workload=None) -> None:
    """Config-dependent grid validation shared by every executor entry."""
    if not cfg.spot.enabled:
        raise ValueError("sweeps need SimConfig.spot.enabled=True")
    # Guard a silent trap: a config that names a non-default instance while
    # the axes (which win) never visit it almost certainly means make_axes
    # was left at its m3.medium default.  Tenant sweeps are exempt — their
    # legacy entry points always defaulted the fleet to m3.medium
    # regardless of the config, and the committed baselines pin that.
    cfg_itype = spot.instance_index(cfg.spot.instance)
    if (cfg_itype != 0 and not _is_tenant_set(workload)
            and not np.any(np.asarray(axes.mix)[:, cfg_itype] > 0)):
        raise ValueError(
            f"SpotConfig.instance={cfg.spot.instance!r} never appears in "
            "the sweep axes, which override the config — pass "
            "instances=[...] to make_axes")
    n_scen = (len(workload)
              if isinstance(workload, scen_lib.ScenarioSet) else 1)
    scen = np.asarray(axes.scenario)
    if scen.size and (scen.min() < 0 or scen.max() >= n_scen):
        raise ValueError(
            f"scenario axis references id {int(scen.max())} but the "
            f"workload provides {n_scen} scenario(s) — pass a ScenarioSet "
            "and scenarios=... to make_axes")


def _point_sched(cfg: runner.SimConfig, trace: bool = False):
    """One grid point with the schedule as an explicit (traced) argument —
    the single definition of what a sweep runs per point (policy-sentinel
    resolution, runtime construction, scan, masked summary).  ``params``
    is the traced ``PolicyParams`` pytree every run consumes (its relative
    ``bid_mult`` multiplies this point's bid-multiple axis).  With the
    chaos engine on (``cfg.faults``) the closure accepts a trailing traced
    ``FaultSpec`` (default: the fault-free spec)."""
    cfg_policy = spot.bid_policy_index(cfg.spot.bid_policy)

    def one(sched, seed, bid_mult, itype, policy, mix, params, fspec=None):
        policy = jnp.where(policy < 0, cfg_policy, policy)
        rt = spot.make_runtime(cfg.spot, itype=itype, bid_mult=bid_mult,
                               policy=policy, mix=mix)
        final, ys = runner.scan_run(sched, cfg, seed=seed, spot_rt=rt,
                                    trace=trace, params=params, fspec=fspec)
        summary = summarize(final, sched, cfg)
        return (summary, ys) if trace else summary

    return one


def point_fn(schedule: ScheduleLike, cfg: runner.SimConfig,
             trace: bool = False):
    """One grid point as a vmappable closure of (seed, bid_mult, itype,
    policy, mix, scenario, params) — the low-level *in-jit* program the
    executor vmaps and ``repro.opt`` builds objectives from (host-side
    callers should go through ``sweep(SweepSpec(...), cfg)`` instead).
    With a ``ScenarioSet`` the scenario id picks the generator and the
    schedule is sampled per (seed, scenario) inside the trace; with a
    plain schedule the id is ignored.  ``params`` is the (traced)
    ``PolicyParams`` pytree — the tuner in ``repro.opt`` vmaps candidate
    populations over exactly this argument.  ``trace=True`` additionally
    returns the per-tick ``ys`` (what ``benchmarks.bench_throughput``
    sizes the trace-mode baseline with)."""
    base = _point_sched(cfg, trace=trace)
    if isinstance(schedule, scen_lib.ScenarioSet):
        sset = schedule

        def one(seed, bid_mult, itype, policy, mix, scenario, params,
                fspec=None):
            sched = sset.sample(scenario,
                                scen_lib.schedule_key(seed, scenario))
            return base(sched, seed, bid_mult, itype, policy, mix, params,
                        fspec)

        return one

    sj = wl.as_jax_schedule(schedule)

    def one(seed, bid_mult, itype, policy, mix, scenario, params,
            fspec=None):
        del scenario
        return base(sj, seed, bid_mult, itype, policy, mix, params, fspec)

    return one


# --------------------------------------------------------------------------
# The compiled chunk program: vmap over the chunk's rows, shard_map over
# the batch mesh when it spans more than one device.

def _sweep_callable(workload, cfg: runner.SimConfig,
                    mesh: Mesh | None, donate: bool = False):
    """Cached compiled sweep over a fixed-shape batch of axes.

    One entry per (scenario set | tenant set | schedule shape, cfg, mesh,
    donation): chunked sweeps reuse it for every micro-batch and *every
    same-shape schedule*, so a 10⁵-point grid — or a loop over many
    schedules — compiles exactly once.  The returned callable takes
    ``(*axes_fields, sched, params)`` (``sched`` ignored under a
    ScenarioSet/TenantSet, whose generators are compiled in).  With
    ``donate=True`` the axis buffers are donated — each chunk's inputs are
    freed the moment the device is done with them (the chunked path passes
    per-chunk copies, never the caller's arrays; donation is a no-op on
    CPU, where XLA ignores it, so it is requested only on accelerator
    backends); the schedule argument is never donated.  With a multi-device
    ``mesh`` the chunk's B axis is partitioned over the mesh's ``batch``
    axis by ``jax.shard_map`` — each device vmaps its shard of full
    simulations, schedule and params fully replicated, no collectives — so
    the same compiled program scales from 1 host CPU to a real accelerator
    mesh.  Results come back as ordinary global (B,)-leading arrays: no
    device-axis reshapes, directly host-transferable.
    """
    donate = donate and jax.default_backend() != "cpu"
    mesh = None if (mesh is not None and mesh.size == 1) else mesh
    # Key on the config with the PolicyParams-traced leaves struck out:
    # the params pytree is a broadcast *argument* of the compiled sweep,
    # so sweeps at different tuned coefficients share one compile.
    # ``cfg.faults`` is part of that key (it survives strip_tuned), and it
    # also decides the callable's arity: with the chaos engine on, the
    # callable takes a trailing (B,)-leaved ``FaultSpec`` batch.
    mesh_key = 1 if mesh is None else mesh
    chaos = cfg.faults is not None
    if isinstance(workload, scen_lib.ScenarioSet):
        cfg_key = runner.strip_tuned(cfg)
        key = ("sweep", workload, cfg_key, mesh_key, donate)
        sched_key_fn = point_fn(workload, cfg)

        def pt(seed, bid_mult, itype, policy, mix, scenario, sched, params,
               *fs):
            del sched
            return sched_key_fn(seed, bid_mult, itype, policy, mix, scenario,
                                params, *fs)
    elif _is_tenant_set(workload):
        from . import tenants as tenants_lib
        scfg = workload.sim_config(cfg)
        cfg_key = runner.strip_tuned(scfg)
        key = ("sweep", workload, cfg_key, mesh_key, donate)
        tenant_fn = tenants_lib.point_fn(workload, cfg)

        def pt(seed, bid_mult, itype, policy, mix, scenario, sched, params,
               *fs):
            del sched
            return tenant_fn(seed, bid_mult, itype, policy, mix, scenario,
                             params, *fs)
    else:
        cfg_key = runner.strip_tuned(cfg)
        key = ("sweep", wl.schedule_shape(workload), cfg_key, mesh_key,
               donate)
        base = _point_sched(cfg)

        def pt(seed, bid_mult, itype, policy, mix, scenario, sched, params,
               *fs):
            del scenario
            return base(sched, seed, bid_mult, itype, policy, mix, params,
                        *fs)

    fn = runner._JIT_CACHE.get(key)
    if fn is not None:
        return fn
    spans.count_callable_miss()
    in_axes = (0, 0, 0, 0, 0, 0, None, None) + ((0,) if chaos else ())
    batched = jax.vmap(pt, in_axes=in_axes)
    if mesh is not None:
        p_b = PartitionSpec(mesh.axis_names[0])
        p_r = PartitionSpec()
        batched = jax.shard_map(
            batched, mesh=mesh,
            in_specs=(p_b,) * 6 + (p_r, p_r) + ((p_b,) if chaos else ()),
            out_specs=p_b, check_vma=False)
    donate_kw = dict(donate_argnums=(0, 1, 2, 3, 4, 5)) if donate else {}
    fn = jax.jit(batched, **donate_kw)
    runner._cache_put(key, fn)
    return fn


def _pad_axes(axes: SweepAxes, n: int) -> SweepAxes:
    """Pad the B axis up to ``n`` rows by repeating the last row (the
    padded results are sliced off — and asserted gone — before any result
    or chunk file is produced)."""
    b = axes.seed.shape[0]
    if b == n:
        return axes
    return SweepAxes(*(jnp.pad(f, [(0, n - b)] + [(0, 0)] * (f.ndim - 1),
                               mode="edge") for f in axes))


def _slice_axes(axes: SweepAxes, lo: int, hi: int,
                copy: bool = True) -> SweepAxes:
    # With ``copy`` (accelerator backends) the slices are fresh buffers,
    # never views of the caller's arrays: the chunked path donates its
    # input buffers to the compiled sweep.  On CPU donation is off, so the
    # defensive copy would be pure waste — plain slices suffice.
    if not copy:
        return SweepAxes(*(f[lo:hi] for f in axes))
    return SweepAxes(*(jnp.array(f[lo:hi], copy=True) for f in axes))


def _norm_faults(spec: SweepSpec, cfg: runner.SimConfig, b: int):
    """Resolve the spec's fault axis against the config's chaos switch.

    Returns ``None`` when the engine is off, else a ``FaultSpec`` whose
    every leaf is (B,)-leading float32 — scalars broadcast so the fault
    axis chunks/shards/pads exactly like the other sweep axes."""
    if cfg.faults is None:
        if spec.faults is not None:
            raise ValueError(
                "SweepSpec.faults is set but SimConfig.faults is None — "
                "the chaos engine compiles in via the config (set "
                "cfg.faults=FaultConfig()), the spec only carries the "
                "traced intensities")
        return None
    fs = (faults_lib.make_fault_spec() if spec.faults is None
          else spec.faults)
    return faults_lib.FaultSpec(*(
        jnp.broadcast_to(jnp.asarray(f, jnp.float32), (b,) + np.shape(f))
        if np.ndim(f) == 0 else jnp.asarray(f, jnp.float32) for f in fs))


def _pad_fspec(fspec, b: int, n: int):
    """Pad a (B,)-leading ``FaultSpec`` batch to ``n`` rows (edge mode,
    mirroring ``_pad_axes``; padded rows never reach a result)."""
    if fspec is None or b == n:
        return fspec
    return jax.tree.map(
        lambda f: jnp.pad(f, [(0, n - b)] + [(0, 0)] * (f.ndim - 1),
                          mode="edge"), fspec)


def _slice_fspec(fspec, lo: int, hi: int):
    # The fault batch is never donated (donate_argnums stops at the axes),
    # so plain slices suffice on every backend.
    if fspec is None:
        return None
    return jax.tree.map(lambda f: f[lo:hi], fspec)


def _take_rows(host_tree, rows: int, chunk: int, where: str):
    """Slice one computed chunk down to its live rows, asserting that the
    compiled call produced exactly the padded chunk shape — the guarantee
    that ``_pad_axes``'s repeated rows can never leak into a summary or a
    written chunk file."""
    def cut(leaf):
        if leaf.shape[0] != chunk:
            raise AssertionError(
                f"sweep chunk produced {leaf.shape[0]} rows where the "
                f"padded chunk shape is {chunk} — padded points would leak "
                f"into {where}")
        return leaf[:rows] if rows != chunk else leaf

    return jax.tree.map(cut, host_tree)


# --------------------------------------------------------------------------
# Runtime profiling (SweepSpec.profile): per-chunk timings + memory.

@dataclasses.dataclass(frozen=True)
class ChunkProfile:
    """One micro-batch's runtime profile (``SweepSpec.profile=True``).

    Each time is the duration of the chunk's host spans (``obs.spans``),
    so a profiled sweep run under ``jax.profiler.trace`` shows the same
    spans on the device trace's clock: ``compile_s`` is ``sweep.compile``,
    non-zero only on the chunk that triggered the AOT compile (all chunks
    share one padded shape, hence one executable); ``execute_s`` is
    ``sweep.launch`` plus ``sweep.to_host`` (the launch, the run and the
    summaries brought to the host); ``write_s`` is ``sweep.write``, only
    on streamed sweeps (the atomic chunk-file commit); ``resumed`` marks
    chunks a streamed sweep found already committed — their timings are
    zero because no work was re-done.
    """

    chunk: int
    rows: int
    compile_s: float = 0.0
    execute_s: float = 0.0
    write_s: float = 0.0
    peak_bytes: int | None = None   # XLA memory_analysis (temp+out+args)
    resumed: bool = False


@dataclasses.dataclass(frozen=True)
class SweepReport:
    """A profiled sweep's result plus its per-chunk runtime profile.

    ``result`` is exactly what the unprofiled ``sweep`` call would have
    returned (a summary pytree, or a :class:`SweepStream` handle when
    streaming) — profiling wraps, never alters.
    """

    result: object
    chunks: list          # [ChunkProfile] in chunk order
    total_s: float        # the ``sweep`` span: checks, compile, dispatch, I/O

    def write_trace(self, path) -> None:
        """Render the chunk timeline as Chrome/Perfetto trace-event JSON
        (one complete span per chunk; open in ui.perfetto.dev)."""
        from ..obs import export
        export.write_trace(path, export.sweep_trace_events(self.chunks))


def peak_bytes(compiled) -> int | None:
    """XLA's peak-memory estimate for one compiled executable: temp +
    output + argument bytes.  ``None`` only where the backend offers no
    analysis (``Compiled.memory_analysis`` returns ``None`` there); any
    other failure is raised."""
    ma = compiled.memory_analysis()
    if ma is None:
        return None
    return int(ma.temp_size_in_bytes + ma.output_size_in_bytes
               + ma.argument_size_in_bytes)


# --------------------------------------------------------------------------
# Streaming executor: chunk files + manifest, resumable after a kill.

_MANIFEST = "sweep_manifest.json"
_STREAM_SCHEMA = 1


def _workload_token(workload) -> str:
    """A process-stable identity string for the manifest (guards a
    stream_dir against being resumed with a different sweep)."""
    if isinstance(workload, scen_lib.ScenarioSet):
        return f"scenarios:{','.join(workload.names)}:{workload.max_w}"
    if _is_tenant_set(workload):
        return (f"tenants:{','.join(workload.names)}:"
                f"{workload.n}x{workload.max_w}")
    sched = wl.as_jax_schedule(workload)
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(sched):
        h.update(np.asarray(leaf).tobytes())
    return f"schedule:{h.hexdigest()[:16]}"


def _spec_digest(axes: SweepAxes, b: int, chunk: int, cfg_token: str,
                 workload_token: str, pp, fspec=None) -> str:
    h = hashlib.sha256()
    h.update(f"{b}:{chunk}:{cfg_token}:{workload_token}".encode())
    for f in axes:
        h.update(np.asarray(f).tobytes())
    for leaf in jax.tree.leaves(pp):
        h.update(np.asarray(leaf).tobytes())
    if fspec is not None:
        # The fault axis is part of the sweep's identity: resuming a chaos
        # stream with different fault intensities must be refused.
        for leaf in jax.tree.leaves(fspec):
            h.update(np.asarray(leaf).tobytes())
    return h.hexdigest()


@dataclasses.dataclass(frozen=True, eq=False)
class SweepStream:
    """Handle to a streamed sweep's on-disk result.

    The executor wrote one atomic chunk file per micro-batch
    (``checkpoint.checkpointer`` layout: ``step_<i>/`` + ``.done``
    marker); this handle reads them back.  ``load()`` concatenates every
    chunk into the exact pytree the in-memory path would have returned —
    bit-identical, the contract ``tests/test_sweepspec.py`` pins —
    while ``load_chunk(i)`` keeps peak memory at one chunk for
    reduce-style consumers.
    """

    directory: str
    n_points: int
    chunk_size: int      # padded rows per full chunk
    n_chunks: int
    manifest: dict = dataclasses.field(repr=False)
    _struct: object = dataclasses.field(repr=False)   # padded-chunk shapes

    def rows(self, i: int) -> int:
        """Live (un-padded) rows of chunk ``i``."""
        if not 0 <= i < self.n_chunks:
            raise IndexError(f"chunk {i} out of range [0, {self.n_chunks})")
        return min(self.chunk_size, self.n_points - i * self.chunk_size)

    def completed(self) -> list[int]:
        """Committed chunk ids present on disk (sorted)."""
        return [s for s in checkpointer.committed_steps(self.directory)
                if s < self.n_chunks]

    def load_chunk(self, i: int):
        """One chunk's summaries as a (rows(i),)-leading pytree."""
        rows = self.rows(i)
        like = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct((rows,) + s.shape[1:], s.dtype),
            self._struct)
        return checkpointer.restore(self.directory, i, like)

    def load(self):
        """Every chunk, concatenated — the in-memory path's return value."""
        chunks = [jax.tree.map(np.asarray, self.load_chunk(i))
                  for i in range(self.n_chunks)]
        cat = (chunks[0] if len(chunks) == 1 else
               jax.tree.map(lambda *xs: np.concatenate(xs), *chunks))
        return jax.tree.map(jnp.asarray, cat)


def _stream_init(directory: str, digest: str, b: int, chunk: int,
                 n_chunks: int, resume: bool) -> dict:
    """Create or validate the stream manifest; returns it.  A directory
    holding a *different* sweep's manifest is refused outright; with
    ``resume=False`` any previous chunks (and manifest) are discarded."""
    path = os.path.join(directory, _MANIFEST)
    manifest = {"schema": _STREAM_SCHEMA, "digest": digest, "n_points": b,
                "chunk": chunk, "n_chunks": n_chunks}
    os.makedirs(directory, exist_ok=True)
    if not resume:
        for name in os.listdir(directory):
            if name == _MANIFEST or name.startswith("step_"):
                full = os.path.join(directory, name)
                if os.path.isdir(full):
                    shutil.rmtree(full)
                else:
                    os.remove(full)
    elif os.path.exists(path):
        with open(path) as f:
            prev = json.load(f)
        # A previous profiled run annotates the manifest with its timings;
        # identity is everything *but* that record.
        prev = {k: v for k, v in prev.items() if k != "profile"}
        if prev != manifest:
            raise ValueError(
                f"stream_dir {directory!r} holds a different sweep "
                f"(manifest {prev} != {manifest}) — point stream_dir at a "
                "fresh directory or pass resume=False to discard it")
        return manifest
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(manifest, f)
    os.replace(tmp, path)
    return manifest


# --------------------------------------------------------------------------
# The facade.

def sweep(spec: SweepSpec, cfg: runner.SimConfig):
    """Run every grid point of ``spec`` under ``cfg`` — THE sweep entry
    point (summary mode, chunked, mesh-sharded, optionally streamed).

    Returns a :class:`RunSummary` of (B,)-shaped arrays — or a
    ``tenants.TenantRun`` when ``spec.workload`` is a ``TenantSet`` — in
    grid order, or a :class:`SweepStream` handle when ``spec.stream_dir``
    is set (the streamed path never materializes the full grid in memory;
    call ``.load()`` to do that explicitly).

    The *axes* choose each run's fleet mix, bid policy, bid multiple and
    scenario; ``cfg.spot.instance``/``fleet``/``bid_mult`` are not
    consulted (they only apply to single, non-swept runs).
    ``cfg.spot.bid_policy`` *is* the policy of every grid point whose
    ``policy`` axis is the -1 sentinel (the ``make_axes`` default).

    Execution options live on the spec (see :class:`SweepSpec`); an
    interrupted streamed sweep resumes from its last committed chunk when
    re-invoked with the same spec and ``stream_dir``.

    Each phase of the call is a host span of ``obs.spans`` (``sweep`` and
    the ``sweep.*`` spans inside it, one ``sweep_chunk`` step per chunk),
    so a profiler trace names what the device waits on.
    """
    with spans.span("sweep", points=spec.n_points) as whole:
        result, profiles = _sweep(spec, cfg)
    spans.sweep_done()
    if not spec.profile:
        return result
    return SweepReport(result=result, chunks=profiles, total_s=whole.seconds)


def _sweep(spec: SweepSpec, cfg: runner.SimConfig):
    """``sweep``'s work: the result, and with ``spec.profile`` one
    :class:`ChunkProfile` per chunk (else ``None``)."""
    with spans.span("sweep.prepare"):
        workload = spec.workload
        is_set = isinstance(workload, scen_lib.ScenarioSet)
        is_tenants = _is_tenant_set(workload)
        check_cfg = workload.sim_config(cfg) if is_tenants else cfg
        _check_axes(check_cfg, spec.axes, workload)
        pp = (runner.default_params(check_cfg) if spec.params is None
              else spec.params)
        # The dummy stands in for the (unused) schedule argument when the
        # scenario set / tenant set generates schedules internally.
        sched = (jnp.zeros((0,)) if (is_set or is_tenants)
                 else wl.as_jax_schedule(workload))
        axes = spec.axes
        b = spec.n_points
        fspec = _norm_faults(spec, check_cfg, b)

        avail = len(jax.devices())
        if spec.mesh is not None:
            n_dev = min(spec.mesh.size, b)
            mesh = spec.mesh if n_dev == spec.mesh.size else None
        else:
            n_dev = avail if spec.devices is None else min(int(spec.devices),
                                                           avail)
            n_dev = min(n_dev, b)
            mesh = None
        if n_dev > 1 and mesh is None:
            mesh = mesh_lib.make_sweep_mesh(n_dev)

        ftail = () if fspec is None else (fspec,)
        fast = (spec.chunk_size is None and n_dev == 1
                and spec.stream_dir is None and not spec.profile)
        chunk = b if spec.chunk_size is None else min(int(spec.chunk_size),
                                                      b)
        # Each compiled chunk covers a device multiple of runs (the
        # explicit padding policy: the grid never has to divide the device
        # count).
        chunk = -(-chunk // n_dev) * n_dev
        donating = jax.default_backend() != "cpu"
        n_chunks = -(-b // chunk)

    if fast:
        with spans.span("sweep.lookup"):
            fn = _sweep_callable(workload, cfg, None)
        with spans.span("sweep.launch", rows=b, padded=b):
            return fn(*axes, sched, pp, *ftail), None

    with spans.span("sweep.lookup"):
        fn = _sweep_callable(workload, cfg, mesh, donate=True)
    if spec.stream_dir is not None:
        return _run_streamed(
            fn, axes, sched, pp, b, chunk, n_chunks,
            os.fspath(spec.stream_dir), spec.resume,
            donating, workload, check_cfg, fspec=fspec,
            profile=spec.profile)

    outs = []
    profiles: list[ChunkProfile] | None = [] if spec.profile else None
    chunker = _ChunkRunner(fn, axes, fspec, sched, pp, chunk, donating,
                           spec.profile)
    for i in range(n_chunks):
        lo = i * chunk
        hi = min(lo + chunk, b)
        with spans.chunk(i):
            host, times = chunker.run(lo, hi, "the summary")
        outs.append(host)
        if spec.profile:
            profiles.append(ChunkProfile(chunk=i, rows=hi - lo,
                                         peak_bytes=chunker.peak, **times))
    with spans.span("sweep.assemble", rows=b):
        cat = (outs[0] if len(outs) == 1 else
               jax.tree.map(lambda *xs: np.concatenate(xs), *outs))
        for leaf in jax.tree.leaves(cat):
            if leaf.shape[0] != b:
                raise AssertionError(
                    f"chunked sweep produced {leaf.shape[0]} rows for {b} "
                    "grid points — padded points would leak into the "
                    "summary")
        return jax.tree.map(jnp.asarray, cat), profiles


class _ChunkRunner:
    """Runs one chunk of a chunked sweep at a time, each phase in its own
    host span: the axes sliced and padded to the chunk shape, the launch,
    the summaries brought to the host and cut to the chunk's live rows.

    A profiled sweep takes the compile-vs-execute split via the AOT path:
    every chunk is padded to one shape, so the first chunk's executable
    serves them all and only it pays (and reports) the compile; ``peak``
    is then XLA's peak-bytes estimate of that executable."""

    def __init__(self, fn, axes, fspec, sched, pp, chunk, donating,
                 profile):
        self.fn, self.axes, self.fspec = fn, axes, fspec
        self.sched, self.pp, self.chunk = sched, pp, chunk
        self.donating, self.profile = donating, profile
        self.compiled = None
        self.peak = None

    def run(self, lo: int, hi: int, where: str):
        """Rows ``[lo, hi)``: their summaries on the host, and the
        ``ChunkProfile`` times they took (each a span's duration)."""
        chunk = self.chunk
        with spans.span("sweep.slice_pad", rows=hi - lo, padded=chunk):
            part = _pad_axes(_slice_axes(self.axes, lo, hi,
                                         copy=self.donating), chunk)
            fpart = (() if self.fspec is None else
                     (_pad_fspec(_slice_fspec(self.fspec, lo, hi), hi - lo,
                                 chunk),))
        args = (*part, self.sched, self.pp, *fpart)
        compile_s = 0.0
        if self.profile and self.compiled is None:
            with spans.span("sweep.compile") as compiling:
                self.compiled = self.fn.lower(*args).compile()
            compile_s = compiling.seconds
            self.peak = peak_bytes(self.compiled)
        run = self.fn if self.compiled is None else self.compiled
        with spans.span("sweep.launch", rows=hi - lo,
                        padded=chunk) as launch:
            res = run(*args)
        # Off-device before the next chunk so live bytes stay O(chunk);
        # summaries are plain pytrees of dense arrays, so the transfer is
        # reformat-free.
        with spans.span("sweep.to_host", rows=hi - lo) as to_host:
            host = _take_rows(jax.tree.map(np.asarray, res), hi - lo, chunk,
                              where)
        return host, {"compile_s": compile_s,
                      "execute_s": launch.seconds + to_host.seconds}


def _run_streamed(fn, axes: SweepAxes, sched, pp, b: int, chunk: int,
                  n_chunks: int, directory: str, resume: bool,
                  donating: bool, workload, check_cfg,
                  fspec=None, profile: bool = False,
                  ) -> "tuple[SweepStream, list[ChunkProfile] | None]":
    """Stream each completed chunk's summaries to disk; resumable.

    Chunk ``i`` is written atomically as ``step_<i>`` via the
    checkpointer (a crash mid-write leaves no ``.done`` marker, so the
    chunk is simply recomputed on resume), *already sliced to its live
    rows* — padded rows never reach a chunk file.  A manifest pins the
    sweep's identity (axes/config/workload/params/faults digest +
    chunking), so a directory can only ever be resumed with the sweep
    that started it.  Committed chunks are integrity-checked against the
    per-file sha256 digests in their chunk manifests; a corrupted or
    truncated chunk is silently recomputed instead of resumed.
    """
    with spans.span("sweep.prepare"):
        cfg_token = repr(runner.strip_tuned(check_cfg))
        digest = _spec_digest(axes, b, chunk, cfg_token,
                              _workload_token(workload), pp, fspec=fspec)
        manifest = _stream_init(directory, digest, b, chunk, n_chunks,
                                resume)
        done = {s for s in checkpointer.committed_steps(directory)
                if checkpointer.verify(directory, s)}

        part0 = _pad_axes(_slice_axes(axes, 0, min(chunk, b), copy=False),
                          chunk)
        ftail0 = (() if fspec is None else
                  (_pad_fspec(_slice_fspec(fspec, 0, min(chunk, b)),
                              min(chunk, b), chunk),))
        struct = jax.eval_shape(fn, *part0, sched, pp, *ftail0)

    profiles: list[ChunkProfile] | None = [] if profile else None
    chunker = _ChunkRunner(fn, axes, fspec, sched, pp, chunk, donating,
                           profile)
    for i in range(n_chunks):
        rows = min(chunk, b - i * chunk)
        if i in done:
            if profile:
                # Committed on a previous run — no work re-done, so the
                # span is zero-length but still present in the timeline.
                profiles.append(ChunkProfile(chunk=i, rows=rows,
                                             resumed=True))
            continue
        lo = i * chunk
        hi = min(lo + chunk, b)
        with spans.chunk(i):
            host, times = chunker.run(lo, hi, "a written chunk file")
            with spans.span("sweep.write", rows=hi - lo) as write:
                checkpointer.save(directory, i, host)
        if profile:
            profiles.append(ChunkProfile(
                chunk=i, rows=hi - lo, write_s=write.seconds,
                peak_bytes=chunker.peak, **times))
        del host   # live bytes stay O(chunk) no matter the grid

    with spans.span("sweep.assemble", rows=b):
        if profile:
            # Persist the run's profile next to the sweep identity.  The
            # manifest comparison on resume strips this key: profiling a
            # sweep must never un-resume its own stream_dir.
            manifest = dict(manifest,
                            profile=[dataclasses.asdict(p) for p in profiles])
            path = os.path.join(directory, _MANIFEST)
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                json.dump(manifest, f)
            os.replace(tmp, path)

        stream = SweepStream(directory=directory, n_points=b,
                             chunk_size=chunk, n_chunks=n_chunks,
                             manifest=manifest, _struct=struct)
    return stream, profiles


# --------------------------------------------------------------------------
# Deprecated wrappers (PR-3-era entry points) and the loop-of-one reference.

_WARNED_RUN_SWEEP = False  # deprecation fires once per process, not per call


def run_sweep(schedule: ScheduleLike, cfg: runner.SimConfig,
              axes: SweepAxes, *,
              chunk_size: int | None = None,
              devices: int | None = None,
              params: PolicyParams | None = None) -> RunSummary:
    """Deprecated: build a :class:`SweepSpec` and call :func:`sweep`.

    Thin keyword-only wrapper kept so PR-3..6 callers keep working; the
    execution is byte-for-byte the new engine's (same compile cache, same
    chunk padding, same results)."""
    global _WARNED_RUN_SWEEP
    if not _WARNED_RUN_SWEEP:
        _WARNED_RUN_SWEEP = True
        warnings.warn(
            "run_sweep is deprecated — build a SweepSpec and call "
            "repro.sim.sweep.sweep(spec, cfg)", DeprecationWarning,
            stacklevel=2)
    return sweep(SweepSpec(axes=axes, workload=schedule, params=params,
                           chunk_size=chunk_size, devices=devices), cfg)


def run_single(schedule: ScheduleLike, cfg: runner.SimConfig, *,
               seed: int, bid_mult: float,
               instance: FleetMix = "m3.medium",
               policy: str | int | None = None,
               scenario: int = 0,
               params: PolicyParams | None = None) -> RunSummary:
    """One grid point as a standalone jitted run — the reference the
    vmapped sweep is tested against (and a handy debug entry point).
    With a ``ScenarioSet`` the point's schedule is sampled exactly as the
    sweep would (same per-(seed, scenario) key).  Runs through the cached
    summary-mode entry point: repeated calls with different seeds / bids /
    mixes / same-shape schedules reuse one compiled simulation."""
    itype, mask = _as_mix(instance)
    if policy is None:
        policy = spot.bid_policy_index(cfg.spot.bid_policy)
    if isinstance(schedule, scen_lib.ScenarioSet):
        if not 0 <= int(scenario) < len(schedule):
            raise ValueError(
                f"scenario id {scenario} out of range for the "
                f"{len(schedule)}-scenario set {schedule.names}")
        sched = schedule.sample(scenario,
                                scen_lib.schedule_key(seed, scenario))
    else:
        if int(scenario) != 0:
            raise ValueError(
                f"scenario id {scenario} given, but a plain schedule "
                "provides only scenario 0 — pass a ScenarioSet")
        sched = wl.as_jax_schedule(schedule)
    rt = spot.make_runtime(cfg.spot, itype=itype, bid_mult=bid_mult,
                           policy=policy, mix=jnp.asarray(mask))
    pp = runner.default_params(cfg) if params is None else params
    final, _ = runner.cached_scan(sched, cfg, trace=False,
                                  with_rt=True)(sched, seed, rt, pp)
    return summarize(final, sched, cfg)
