#!/usr/bin/env python3
"""Chip smoke test: the Monte-Carlo sweep engine end to end on a TPU.

    python chip_smoke.py             # one chip, phases 1-4 below
    python chip_smoke.py --chips 4   # the four-chip shard_map path only

With one chip, everything runs in this one process:

  1. device check: anything that is not a TPU is refused;
  2. the ``bench_throughput`` sweep (spiky m3.xlarge spot market, 130
     monitoring ticks of 300 s, the paper's 30-workload suite) over 2,048
     seeds x 8 bid multiples x 4 bid policies = 65,536 grid points, in
     4 chunks of 16,384 through one compiled program;
  3. the first chunk again with the Pallas Kalman kernel compiled in
     (``ControllerConfig.kalman_kernel=True``), against phase 2;
  4. 64 of the grid points on the host CPU backend, against phase 2.

``--chips 4`` runs phase 2's grid sharded over four chips by
``jax.shard_map``, and the same grid on one chip, and nothing else.

Times printed here are observations of one run, not benchmark results.
The last line of standard output is one JSON object naming the device.
Any failure, or a comparison outside its tolerance, exits non-zero
without that line.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import warnings

import numpy as np

CHECKOUT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(CHECKOUT, "src"))

SEEDS = 2048
BID_MULTS = (1.02, 1.1, 1.2, 1.5, 2.0, 2.5, 4.0, 8.0)
CHUNK = 16384
HOST_POINTS = 64

# Two runs of one grid point are said to diverge when their finished or
# violation counts differ, or their costs differ by more than COST_RTOL.
# Schedules and market noise come from threefry, so both runs see the same
# inputs bit for bit; what differs is float32 rounding (division and
# transcendentals on the TPU vs the CPU, fusion order).  A cost is a sum of
# ~130 per-tick bills, so pure rounding drift stays near 130 * 2**-23
# ~ 1.6e-5 relative; COST_RTOL leaves about 6x room above that.
COST_RTOL = 1e-4
# The kernel and jnp paths run on the same chip; they differ only in how
# the Kalman bank update is written, with the same f32 operations.  On a
# TPU v5 lite they were bit-identical on all 16,384 points, so no point may
# diverge: a fault in one 256-row kernel block moves about 9 points.
KERNEL_MAX_DIVERGED = 0
# Chip vs host differs in every transcendental, and rounding can flip a
# discrete decision (an AIMD step, a preemption, a deadline met on the tie
# tick) that is then amplified over the remaining ticks.  On a TPU v5 lite
# the 64 points differed by at most 2.2e-6 relative with no flip; allow
# 3 of the 64 points (5%) to diverge, a wrong backend diverges on most.
HOST_MAX_DIVERGED = 3


def _host_platforms() -> None:
    """Phase 4 needs the CPU backend beside the chip: where
    ``JAX_PLATFORMS`` lists backends without it, add it last, so the chip
    stays the default device."""
    plat = os.environ.get("JAX_PLATFORMS", "")
    if plat and "cpu" not in plat.split(","):
        os.environ["JAX_PLATFORMS"] = plat + ",cpu"


def _grid():
    from benchmarks import bench_throughput as bt
    from repro.sim import make_axes, paper_schedule
    axes = make_axes(seeds=range(SEEDS), bid_mults=BID_MULTS,
                     instances=[bt.MARKET["instance"]],
                     policies=list(bt.POLICIES))
    return axes, paper_schedule(ttc=7500.0, arrival_gap_ticks=1), bt._cfg()


def _host(tree):
    import jax
    return jax.tree.map(np.asarray, tree)


def _run_profiled(axes, schedule, cfg, devices: int, label: str):
    """One profiled sweep; prints compile, execute and rate per chunk."""
    from repro.sim import SweepSpec
    from repro.sim.sweep import sweep
    t0 = time.perf_counter()
    rep = sweep(SweepSpec(axes=axes, workload=schedule, chunk_size=CHUNK,
                          devices=devices, profile=True), cfg)
    wall = time.perf_counter() - t0
    chunks = rep.chunks
    n_compiles = sum(c.compile_s > 0 for c in chunks)
    points = sum(c.rows for c in chunks)
    print(f"{label}: points={points} chunks={len(chunks)} "
          f"compiles={n_compiles} compile_s={chunks[0].compile_s:.3f} "
          f"wall_s={wall:.3f}", flush=True)
    for c in chunks:
        print(f"{label}:   chunk {c.chunk} rows={c.rows} "
              f"execute_s={c.execute_s:.4f}", flush=True)
    steady = chunks[1:]
    if steady:
        rate = sum(c.rows for c in steady) / sum(c.execute_s for c in steady)
        print(f"{label}: steady_points_per_s={rate:.1f} "
              f"(chunks 1..{len(chunks) - 1})", flush=True)
    if chunks[0].peak_bytes is None:
        raise RuntimeError(f"{label}: the backend gave no memory analysis")
    print(f"{label}: xla_peak_bytes_estimate={chunks[0].peak_bytes}",
          flush=True)
    if n_compiles != 1:
        raise RuntimeError(f"{label}: {n_compiles} compiles, expected 1")
    return _host(rep.result)


def _check_summary(res, n: int, n_workloads: int, label: str) -> None:
    """Every field finite and of the grid's length; counts in range."""
    for name, leaf in zip(res._fields, res):
        if leaf is None:
            continue
        if leaf.shape != (n,):
            raise RuntimeError(f"{label}: {name} has shape {leaf.shape}, "
                               f"expected ({n},)")
        if not np.all(np.isfinite(leaf)):
            raise RuntimeError(f"{label}: {name} has non-finite values")
    for name in ("finished", "violations"):
        v = getattr(res, name)
        if v.min() < 0 or v.max() > n_workloads:
            raise RuntimeError(f"{label}: {name} outside [0, {n_workloads}]"
                               f": [{v.min()}, {v.max()}]")
    if res.preemptions.min() < 0:
        raise RuntimeError(f"{label}: negative preemption count")


def _compare(label: str, got, ref, max_diverged: int | None) -> bool:
    """Print how far two summaries of the same points differ; True when
    at most ``max_diverged`` points diverge, or, for ``None``, when the
    two are bit-identical."""
    bit_identical = all(np.array_equal(a, b) for a, b in zip(got, ref)
                        if a is not None)
    cg = got.cost.astype(np.float64)
    cr = ref.cost.astype(np.float64)
    rel = np.abs(cg - cr) / np.maximum(np.abs(cr), np.finfo(np.float32).tiny)
    flipped = ((got.finished != ref.finished)
               | (got.violations != ref.violations))
    diverged = int((flipped | (rel > COST_RTOL)).sum())
    if max_diverged is None:
        ok, limit = bit_identical, "bit_identical"
    else:
        ok, limit = diverged <= max_diverged, str(max_diverged)
    print(f"{label}: points={cg.size} bit_identical={bit_identical} "
          f"max_rel_cost_diff={rel.max():.6e} "
          f"finished_or_violations_differ={int(flipped.sum())} "
          f"diverged={diverged} limit={limit} "
          f"{'ok' if ok else 'OUT OF TOLERANCE'}", flush=True)
    return ok


def _peak_in_use(dev) -> int:
    return dev.memory_stats()["peak_bytes_in_use"]


def _kernel_in_hlo(schedule, cfg_k, axes) -> bool:
    """Whether the executor's chunk program holds the compiled Pallas
    kernel (a Mosaic ``tpu_custom_call``), not its interpreted form."""
    from repro.sim import runner
    from repro.sim import sweep as sweep_lib
    from repro.sim import workloads as wl
    fn = sweep_lib._sweep_callable(schedule, cfg_k, None, donate=True)
    return "tpu_custom_call" in fn.lower(
        *axes, wl.as_jax_schedule(schedule),
        runner.default_params(cfg_k)).as_text()


def _slice(axes, idx):
    import jax.numpy as jnp
    from repro.sim import SweepAxes
    return SweepAxes(*(jnp.asarray(np.asarray(f)[idx]) for f in axes))


def _take(res, idx):
    return type(res)(*(None if f is None else f[idx] for f in res))


def one_chip() -> bool:
    import jax
    from repro.sim.sweep import SweepSpec, sweep

    ok = True
    axes, schedule, cfg = _grid()
    n = int(axes.seed.shape[0])
    dev = jax.devices()[0]

    # Phase 2: the main sweep.
    main = _run_profiled(axes, schedule, cfg, 1, "sweep")
    _check_summary(main, n, schedule.n, "sweep")
    print(f"sweep: device_peak_bytes_in_use={_peak_in_use(dev)}", flush=True)

    # Phase 3: the first chunk with the Pallas Kalman kernel compiled in.
    cfg_k = dataclasses.replace(
        cfg, ctrl=dataclasses.replace(cfg.ctrl, kalman_kernel=True))
    first = np.arange(CHUNK)
    first_axes = _slice(axes, first)
    has_kernel = _kernel_in_hlo(schedule, cfg_k, first_axes)
    print(f"kernel: tpu_custom_call_in_hlo={has_kernel}", flush=True)
    if not has_kernel:
        raise RuntimeError("kernel: the Kalman kernel was not compiled in")
    kern = _run_profiled(first_axes, schedule, cfg_k, 1, "kernel")
    _check_summary(kern, CHUNK, schedule.n, "kernel")
    print(f"kernel: device_peak_bytes_in_use={_peak_in_use(dev)}",
          flush=True)
    ok &= _compare("kernel_vs_jnp", kern, _take(main, first),
                   KERNEL_MAX_DIVERGED)

    # Phase 4: a spread of the same points on the host CPU backend.  The
    # stride is odd, so the points cycle through every (bid, policy) pair.
    idx = np.arange(HOST_POINTS) * (n // HOST_POINTS - 1)
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu):
        ref = _host(sweep(SweepSpec(axes=_slice(axes, idx),
                                    workload=schedule, devices=1), cfg))
    print(f"host: device={cpu.platform} points={HOST_POINTS} "
          f"wall_s={time.perf_counter() - t0:.3f}", flush=True)
    _check_summary(ref, HOST_POINTS, schedule.n, "host")
    ok &= _compare("chip_vs_host", _take(main, idx), ref, HOST_MAX_DIVERGED)
    return ok


def four_chips() -> bool:
    axes, schedule, cfg = _grid()
    n = int(axes.seed.shape[0])
    sharded = _run_profiled(axes, schedule, cfg, 4, "sharded4")
    _check_summary(sharded, n, schedule.n, "sharded4")
    single = _run_profiled(axes, schedule, cfg, 1, "single")
    _check_summary(single, n, schedule.n, "single")
    # Each chip runs the same per-point program, with no collective, on a
    # quarter of each chunk: the result must be bit-identical to one chip,
    # as it was on a TPU v5 lite on all 65,536 points.
    return _compare("sharded4_vs_single", sharded, single, None)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded sweep and its one-chip "
                         "comparison")
    args = ap.parse_args(argv)

    # Keep the TPU runtime's logs out of /tmp, outside the checkout.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _host_platforms()
    import jax
    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)} jax={jax.__version__}", flush=True)
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); refusing "
              "to run", file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, JAX found {len(devs)}", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache(CHECKOUT)}", flush=True)

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        try:
            ok = four_chips() if args.chips == 4 else one_chip()
        finally:
            for w in caught:
                print(warnings.formatwarning(w.message, w.category,
                                             w.filename, w.lineno),
                      file=sys.stderr, end="")
            donation = sum("donated buffers were not usable" in str(w.message)
                           for w in caught)
            print(f"warnings: {len(caught)} total, {donation} "
                  "'donated buffers were not usable'", flush=True)
    if not ok:
        print("chip_smoke: a comparison is out of tolerance", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
