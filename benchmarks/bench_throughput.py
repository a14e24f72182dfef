"""Sweep-engine throughput benchmark: summary-mode vs trace-mode scans.

The paper's headline results rest on Monte-Carlo sweeps over market
scenarios (Figs. 4-5), and the sweep engine's cost model is simple: a
trace-mode sweep stacks the full per-tick trace — six ``(T,)`` series plus
three ``(T, W, K)`` arrays — for *every* grid point, moving O(B·T·W·K)
floats to produce O(B) summary numbers; a summary-mode sweep accumulates
the eight per-run scalars inside the scan carry and moves O(B).

This benchmark times both modes on two fixed grids:

  * ``frontier`` — the PR-2 policy-frontier shape (seeds × bid multiples ×
    bid policies on the spiky m3.xlarge market of ``bench_bidding``);
  * ``large``    — the same frontier scaled 100× (10× under ``--smoke``)
    along the seed axis, run through the unified executor's chunked path
    (``sweep(SweepSpec(chunk_size=...))``, one cached compile for every
    micro-batch); trace mode at this size is *not executed* — its output
    bytes are derived analytically via ``jax.eval_shape`` to show what the
    old engine would have streamed;
  * ``streamed`` — the large grid again through the disk-streaming
    executor (``stream_dir=``): chunks land on disk, peak host live bytes
    stay at one padded chunk (grid ≥10× larger, CI-gated), the loaded
    result is bit-checked against the in-memory path, and a
    kill-and-resume round-trip recomputes exactly the discarded chunk;
  * ``sharded``  — shard_map over every local device vs a single device
    (bit-parity + speedup; null on single-device hosts).

Per mode it records compile seconds, steady-state runs/sec, the bytes the
call returns (``jax.eval_shape``, deterministic across hosts) and XLA's
peak live bytes (``compiled.memory_analysis()``: temp + output + args;
None where the backend reports nothing).  Acceptance (gated in CI by
``check_bench_regression.py`` against ``benchmarks/baselines/``):
summary mode must show ≥5× lower returned/peak bytes or ≥3× the runs/sec
of trace mode on the frontier grid.

Emits ``results/BENCH_throughput.json`` (``kind: "throughput"``).

CLI:  PYTHONPATH=src python -m benchmarks.bench_throughput [--smoke]
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax
import numpy as np

from repro.core.controller import ControllerConfig
from repro.core.types import BillingParams, ControlParams
from repro.sim import (SimConfig, SpotConfig, SweepSpec, make_axes,
                       paper_schedule, runner, sweep)

SCHEMA_VERSION = 2
MEM_RATIO_FLOOR = 5.0
SPEED_RATIO_FLOOR = 3.0
# The streamed path must keep the grid at least this many times larger
# than peak host live bytes (one padded chunk of summaries).
STREAM_RATIO_FLOOR = 10.0

# PR-2 policy-frontier market (bench_bidding.MARKET) and grid shape.
MARKET = dict(instance="m3.xlarge", p_spike_per_core=0.02, spike_hours=3.0,
              ema_alpha=0.15)
POLICIES = ("multiple", "ttc", "ema", "on_demand")
FULL_MULTS = (1.02, 1.1, 1.2, 1.5, 2.5, 4.0, 8.0)
SMOKE_MULTS = (1.02, 1.5, 2.5, 8.0)
TICKS = 130
MONITOR_DT = 300.0
STEADY_ITERS = 3


def _cfg() -> SimConfig:
    return SimConfig(
        ctrl=ControllerConfig(params=ControlParams(monitor_dt=MONITOR_DT),
                              billing=BillingParams(terminate="immediate")),
        ticks=TICKS, spot=SpotConfig(enabled=True, **MARKET))


def _axes(seeds, mults):
    return make_axes(seeds=list(seeds), bid_mults=list(mults),
                     instances=[MARKET["instance"]], policies=list(POLICIES))


def _mode_fn(schedule, cfg, trace: bool):
    """The jitted sweep of one mode — ``sweep.point_fn``, the exact
    per-point program the unified executor runs (at the config's default
    ``PolicyParams``, broadcast exactly as ``sweep.sweep`` broadcasts
    them).
    Trace mode returns what trace mode is *for*: the full per-tick ys of
    every grid point (the PR-2 baseline's memory shape); summary mode the
    eight scalars."""
    pp = runner.default_params(cfg)
    fn = jax.vmap(sweep.point_fn(schedule, cfg, trace=trace),
                  in_axes=(0, 0, 0, 0, 0, 0, None))
    return jax.jit(lambda *axes: fn(*axes, pp))


def _tree_bytes(tree) -> int:
    return int(sum(np.dtype(leaf.dtype).itemsize * int(np.prod(leaf.shape))
                   for leaf in jax.tree.leaves(tree)))


def _measure(fn, axes) -> dict:
    """Compile + steady-state timings and byte counts for one sweep mode.

    Compiles once via the AOT path and times the *compiled* executable, so
    the XLA memory analysis and the timing loop share one compilation.
    """
    b = int(axes.seed.shape[0])
    out_bytes = _tree_bytes(jax.eval_shape(fn, *axes))
    t0 = time.perf_counter()
    compiled = fn.lower(*axes).compile()
    compile_s = time.perf_counter() - t0
    peak = sweep.peak_bytes(compiled)
    jax.block_until_ready(compiled(*axes))   # warm dispatch
    t0 = time.perf_counter()
    for _ in range(STEADY_ITERS):
        jax.block_until_ready(compiled(*axes))
    steady_s = (time.perf_counter() - t0) / STEADY_ITERS
    return {
        "points": b,
        "compile_s": round(compile_s, 4),
        "steady_s": round(steady_s, 4),
        "runs_per_s": round(b / steady_s, 2),
        "output_bytes": out_bytes,
        "peak_bytes": peak,
    }


def run_frontier(schedule, cfg, seeds, mults) -> dict:
    axes = _axes(seeds, mults)
    trace = _measure(_mode_fn(schedule, cfg, trace=True), axes)
    summary = _measure(_mode_fn(schedule, cfg, trace=False), axes)

    def ratio(num, den):
        return round(num / den, 2) if num and den else None

    peak_ratio = ratio(trace["peak_bytes"], summary["peak_bytes"])
    return {
        "points": trace["points"],
        "trace": trace,
        "summary": summary,
        # trace-vs-summary, >1 = summary wins
        "memory_ratio": ratio(trace["output_bytes"],
                              summary["output_bytes"]),
        "peak_ratio": peak_ratio,
        "speed_ratio": ratio(summary["runs_per_s"], trace["runs_per_s"]),
    }


def run_large(schedule, cfg, axes, chunk_size) -> tuple:
    """The frontier grid scaled along the seed axis, summary mode through
    the chunked executor; trace mode sized but never executed
    (``jax.eval_shape`` only — the point is that it need not fit).
    Returns ``(report_dict, in_memory_result)`` so the streamed section
    can verify bit-parity without a third full sweep."""
    b = int(axes.seed.shape[0])

    trace_bytes = _tree_bytes(
        jax.eval_shape(_mode_fn(schedule, cfg, trace=True), *axes))
    summary_bytes = _tree_bytes(
        jax.eval_shape(_mode_fn(schedule, cfg, trace=False), *axes))

    # Warm the chunk cache, then time the whole chunked sweep end to end
    # (per-chunk dispatch + host concatenation included).
    spec = SweepSpec(axes=axes, workload=schedule, chunk_size=chunk_size)
    sweep.sweep(spec, cfg)
    t0 = time.perf_counter()
    result = sweep.sweep(spec, cfg)
    wall = time.perf_counter() - t0
    report = {
        "points": b,
        "chunk_size": chunk_size,
        "summary": {
            "points": b,
            "runs_per_s": round(b / wall, 2),
            "steady_s": round(wall, 4),
            "output_bytes": summary_bytes,
        },
        "trace_output_bytes_analytic": trace_bytes,
        "memory_ratio": round(trace_bytes / summary_bytes, 2),
    }
    return report, result


def _trees_equal(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


def run_streamed(schedule, cfg, axes, reference) -> dict:
    """Disk-streaming executor on the large grid: write chunks to a
    scratch directory, check the loaded result is bit-identical to the
    in-memory path, then delete the last committed chunk and resume.

    The chunk size is picked so the full grid of summaries is well over
    ``STREAM_RATIO_FLOOR``× the live bytes of one padded chunk — the
    bounded-memory contract CI gates.
    """
    import shutil
    import tempfile

    b = int(axes.seed.shape[0])
    stream_chunk = max(1, b // 16)
    grid_bytes = _tree_bytes(
        jax.eval_shape(_mode_fn(schedule, cfg, trace=False), *axes))
    live_bytes = int(round(grid_bytes * stream_chunk / b))
    scratch = tempfile.mkdtemp(prefix="bench_stream_")
    try:
        spec = SweepSpec(axes=axes, workload=schedule,
                         chunk_size=stream_chunk, stream_dir=scratch)
        t0 = time.perf_counter()
        handle = sweep.sweep(spec, cfg)
        wall = time.perf_counter() - t0
        n_chunks = handle.n_chunks
        parity = _trees_equal(handle.load(), reference)

        # Kill-and-resume: discard the last committed chunk, re-invoke the
        # same spec, and check only that chunk was recomputed.
        last = handle.completed()[-1]
        shutil.rmtree(os.path.join(scratch, f"step_{last:08d}"))
        os.remove(os.path.join(scratch, f"step_{last:08d}.done"))
        before = set(handle.completed())
        resumed = sweep.sweep(spec, cfg)
        resume_ok = (_trees_equal(resumed.load(), reference)
                     and len(before) == n_chunks - 1)
    finally:
        shutil.rmtree(scratch, ignore_errors=True)
    return {
        "points": b,
        "chunk_size": stream_chunk,
        "n_chunks": n_chunks,
        "wall_s": round(wall, 4),
        "grid_bytes": grid_bytes,
        "live_bytes": live_bytes,
        "stream_ratio": round(grid_bytes / live_bytes, 2),
        "parity": bool(parity),
        "resume_ok": bool(resume_ok),
    }


def run_sharded(schedule, cfg, axes) -> dict:
    """shard_map over every local device vs a single device on the
    frontier grid: wall-clock ratio and bit-parity.  On a single-device
    host the fields are null — the gate tolerates that; the multi-device
    CI job exercises the parity contract through the test suite."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        return {"devices": n_dev, "parity": None, "speedup": None}
    b = int(axes.seed.shape[0])

    def timed(devices):
        spec = SweepSpec(axes=axes, workload=schedule, chunk_size=b,
                         devices=devices)
        sweep.sweep(spec, cfg)  # warm the compile cache
        t0 = time.perf_counter()
        out = sweep.sweep(spec, cfg)
        return out, time.perf_counter() - t0

    single, t1 = timed(1)
    sharded, tn = timed(None)
    return {
        "devices": n_dev,
        "parity": _trees_equal(single, sharded),
        "single_s": round(t1, 4),
        "sharded_s": round(tn, 4),
        "speedup": round(t1 / tn, 2) if tn > 0 else None,
    }


def main(emit, smoke: bool = False) -> dict:
    seeds = tuple(range(2 if smoke else 6))
    mults = SMOKE_MULTS if smoke else FULL_MULTS
    factor = 10 if smoke else 100
    chunk_size = 128 if smoke else 1024
    schedule = paper_schedule(ttc=7500.0, arrival_gap_ticks=1)
    cfg = _cfg()

    front = run_frontier(schedule, cfg, seeds, mults)
    for mode in ("trace", "summary"):
        m = front[mode]
        emit(f"thru_frontier_{mode}_runs_per_s", m["runs_per_s"],
             f"compile={m['compile_s']}s;out_bytes={m['output_bytes']};"
             f"peak={m['peak_bytes']}")
    emit("thru_frontier_memory_ratio", front["memory_ratio"],
         f"target>={MEM_RATIO_FLOOR};peak_ratio={front['peak_ratio']}")
    emit("thru_frontier_speed_ratio", front["speed_ratio"],
         f"alt_target>={SPEED_RATIO_FLOOR}")

    big_seeds = range(len(list(seeds)) * factor)
    big_axes = _axes(big_seeds, mults)
    large, in_memory = run_large(schedule, cfg, big_axes, chunk_size)
    large["factor"] = factor
    emit("thru_large_summary_runs_per_s", large["summary"]["runs_per_s"],
         f"points={large['points']};chunk={chunk_size}")
    emit("thru_large_memory_ratio", large["memory_ratio"],
         f"trace_bytes={large['trace_output_bytes_analytic']}")

    streamed = run_streamed(schedule, cfg, big_axes, in_memory)
    emit("thru_streamed_ratio", streamed["stream_ratio"],
         f"target>={STREAM_RATIO_FLOOR};live_bytes={streamed['live_bytes']}")
    emit("thru_streamed_parity", float(streamed["parity"]), "bool")
    emit("thru_streamed_resume_ok", float(streamed["resume_ok"]), "bool")

    sharded = run_sharded(schedule, cfg, _axes(seeds, mults))
    if sharded["parity"] is not None:
        emit("thru_sharded_parity", float(sharded["parity"]),
             f"devices={sharded['devices']};speedup={sharded['speedup']}")

    ok = (front["memory_ratio"] is not None
          and front["memory_ratio"] >= MEM_RATIO_FLOOR) or \
         (front["speed_ratio"] is not None
          and front["speed_ratio"] >= SPEED_RATIO_FLOOR)
    emit("thru_acceptance_summary_mode_ok", float(ok), "bool")
    streamed_ok = (streamed["parity"] and streamed["resume_ok"]
                   and streamed["stream_ratio"] >= STREAM_RATIO_FLOOR)
    emit("thru_acceptance_streamed_ok", float(streamed_ok), "bool")

    report = {
        "kind": "throughput",
        "schema_version": SCHEMA_VERSION,
        "smoke": bool(smoke),
        "config": {
            "market": dict(MARKET),
            "ticks": TICKS,
            "monitor_dt": MONITOR_DT,
            "seeds": list(seeds),
            "bid_mults": list(mults),
            "policies": list(POLICIES),
            "large_factor": factor,
            "chunk_size": chunk_size,
            "devices": len(jax.devices()),
            "backend": jax.default_backend(),
        },
        "grids": {"frontier": front, "large": large,
                  "streamed": streamed, "sharded": sharded},
        "acceptance": {
            "summary_mode_ok": bool(ok),
            "streamed_ok": bool(streamed_ok),
            "sharded_parity": sharded["parity"],
            "memory_ratio_floor": MEM_RATIO_FLOOR,
            "speed_ratio_floor": SPEED_RATIO_FLOOR,
            "stream_ratio_floor": STREAM_RATIO_FLOOR,
        },
    }
    os.makedirs("results", exist_ok=True)
    with open(os.path.join("results", "BENCH_throughput.json"), "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")

    if not ok:
        raise SystemExit(
            "throughput acceptance not met: summary mode shows "
            f"memory_ratio={front['memory_ratio']} (floor "
            f"{MEM_RATIO_FLOOR}) and speed_ratio={front['speed_ratio']} "
            f"(floor {SPEED_RATIO_FLOOR})")
    if not streamed_ok:
        raise SystemExit(
            "streamed acceptance not met: parity="
            f"{streamed['parity']} resume_ok={streamed['resume_ok']} "
            f"stream_ratio={streamed['stream_ratio']} (floor "
            f"{STREAM_RATIO_FLOOR})")
    if sharded["parity"] is False:
        raise SystemExit(
            "sharded sweep is not bit-identical to the single-device path")
    return report


def _cli() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced grids for CI; same acceptance checks")
    args = ap.parse_args()

    def emit(name, value, derived=""):
        print(f"{name},{value:.6g},{derived}", flush=True)

    print("name,value,derived")
    main(emit, smoke=args.smoke)


if __name__ == "__main__":
    _cli()
