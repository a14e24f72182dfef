"""One run of one benchmark cell: set-up, a timed window, the check.

Everything that belongs to a cell is found by name from
``BENCHMARK.json``:

* the deployment, ``bench/configs/<config>.json`` (the program's
  ``SimConfig`` fields under ``platform``, Table V under
  ``instance_types``, and the name of its plain reference);
* the traffic, ``bench/mixes/<traffic>.json``, read by ``bench.traffic``;
* each per-layer metric, ``bench/layers/<metric>.py``, whose ``read(ctx)``
  returns a number or ``None`` (nothing to read: the metric is left out);
* the reference, ``bench/reference/<reference>.py``;
* the limits of the comparison, ``bench/limits/<cell>.json``.

The window calls ``repro.sim.sweep.sweep(SweepSpec, cfg)`` once per
dispatch, on a grid of one chunk, and brings the summary to the host.
After it, a sample of the window's points, drawn from ``--seed``, is run
through the reference and compared field by field.
"""

from __future__ import annotations

import argparse
import collections
import contextlib
import functools
import importlib.util
import json
import os
import shutil
import sys
import time

from bench import traffic

CACHE = ".bench_cache"     # inside the checkout; listed in .gitignore
SPANS = ("build_axes", "sweep_call", "fetch_result")


class Refused(Exception):
    """The run cannot report: no chip, too few chips, or a bad cell."""


def load_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise Refused(f"no such file: {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(root: str, name: str) -> dict:
    """The cell's entry, config, mix and per-layer metrics, by name."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json: "
                      f"{sorted(cells)}")
    cell = cells[name]
    config = load_json(os.path.join(root, "bench", "configs",
                                    cell["config"] + ".json"))
    mix = load_json(os.path.join(root, "bench", "mixes",
                                 cell["traffic"] + ".json"))
    ends = [m for m in bench["end_to_end"]
            if name in m.get("workloads", [name])]
    layers = [m for m in bench["per_layer"]
              if name in m.get("workloads", [name])]
    return dict(cell=cell, config=config, mix=mix, end_to_end=ends,
                per_layer=layers)


def sim_config(config: dict, ticks: int):
    """The program's ``SimConfig`` from a deployment file."""
    from repro.core.controller import ControllerConfig
    from repro.core.types import BillingParams, ControlParams
    from repro.sim.runner import SimConfig
    from repro.sim.spot import SpotConfig
    p = config["platform"]
    return SimConfig(
        ctrl=ControllerConfig(params=ControlParams(**p["control"]),
                              billing=BillingParams(**p["billing"]),
                              **p["controller"]),
        ticks=int(ticks), spot=SpotConfig(**p["spot"]), **p["sim"])


def gaps(prog: dict, ref: dict, counts) -> "np.ndarray":
    """(points, fields) relative gaps, field by field of the reference:
    ``|p - r| / |r|``, with ``|r|`` floored at 1 for the ``counts``
    fields; a non-finite result, or a field the program did not report,
    is an infinite gap."""
    import numpy as np
    cols = []
    for k in ref:
        r = np.asarray(ref[k], np.float64)
        if k not in prog:
            cols.append(np.full(r.shape[0], np.inf))
            continue
        p = np.asarray(prog[k], np.float64)
        den = np.maximum(np.abs(r), 1.0 if k in counts else 1e-30)
        g = np.abs(p - r) / den
        cols.append(np.where(np.isfinite(g), g, np.inf))
    return np.stack(cols, axis=1)


def flatten(result) -> dict:
    """A sweep's ``RunSummary`` as named numpy arrays."""
    import numpy as np
    return {k: np.asarray(v) for k, v in result._asdict().items()
            if v is not None}


def program_workload(mix: dict):
    """The program's input for a mix: the suite as a ``JaxSchedule``."""
    import jax.numpy as jnp
    from repro.sim import workloads as wl
    return wl.JaxSchedule(*(jnp.asarray(x) for x in traffic.schedule(mix)))


def check_numbers(g, tol: float) -> dict:
    """The numbers compared: the share of points with some field off by
    more than ``tol``, and the median over points of the widest gap."""
    import numpy as np
    widest = g.max(axis=1)
    return {"diverged_share": float(np.mean(widest > tol)),
            "median_gap": float(np.median(widest))}


def sample_rows(n: int, k: int, seed: int) -> "np.ndarray":
    import numpy as np
    rng = np.random.default_rng([int(seed) % 2**63, 7])
    return np.sort(rng.choice(n, size=min(k, n), replace=False))


def _paths(root: str, workload: str) -> dict:
    cache = os.path.join(root, CACHE)
    return dict(jax=os.path.join(cache, "jax"),
                trace=os.path.join(cache, "trace"),
                hlo=os.path.join(cache, "hlo", workload))


def _setup_env(root: str, workload: str, trace: bool) -> dict:
    """Environment for JAX, set before it is imported: the persistent
    compile cache at a fixed path inside the checkout, no TPU logs under
    ``/tmp``, and XLA's dump of the programs it compiles (the
    reference's left out, ``NO_DUMP``), kept per cell, so that a traced
    run that loads its programs from the cache still finds the optimized
    HLO that the trace's op names refer to (the run that compiled them,
    the first in a checkout, wrote it; after a change to the program the
    newest dump of a module is read)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    paths = _paths(root, workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = paths["jax"]
    os.makedirs(paths["hlo"], exist_ok=True)
    if trace:
        shutil.rmtree(paths["trace"], ignore_errors=True)
        os.makedirs(paths["trace"])
    flags = f"--xla_dump_to={paths['hlo']} --xla_dump_hlo_as_text"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                               + flags).strip()
    return paths


# XLA options for the reference's compile: none of its modules is dumped
# beside the program's (only the program's ops are looked up there).
NO_DUMP = {"xla_dump_hlo_module_re": "^$"}


class Cell:
    """One cell, ready to dispatch: the device checked, JAX's caches set,
    the program's config and workload built, the reference loaded."""

    def __init__(self, root: str, workload: str, trace: bool = False,
                 require_chip: bool = True):
        self.root, self.name = root, workload
        spec = load_cell(root, workload)
        self.entry, self.config, self.mix = (spec["cell"], spec["config"],
                                             spec["mix"])
        self.end_to_end, self.per_layer = spec["end_to_end"], spec["per_layer"]
        self.chips = int(self.entry["chips"])
        # A run without the chip check (the tests) leaves the process's
        # environment and JAX's caches as they are.
        self.paths = (_setup_env(root, workload, trace) if require_chip
                      else _paths(root, workload))

        import jax
        devices = jax.devices()
        self.platform = devices[0].platform
        if require_chip and (self.platform != "tpu"
                             or len(devices) < self.chips):
            raise Refused(f"cell {workload} needs {self.chips} TPU chip(s); "
                          f"JAX found {len(devices)} {self.platform} "
                          "device(s)")
        self.devices = devices[:self.chips]
        if require_chip:
            jax.config.update("jax_compilation_cache_dir", self.paths["jax"])
            jax.config.update("jax_persistent_cache_min_compile_time_secs",
                              0.0)

        src = os.path.join(root, "src")
        if src not in sys.path:
            sys.path.insert(0, src)
        from repro.sim import sweep as sweep_lib
        self._sweep = sweep_lib
        self.ticks = int(self.mix["ticks"])
        self.cfg = sim_config(self.config, self.ticks)
        self.workload = program_workload(self.mix)
        ref = self.config["reference"]
        self.ref = load_module(
            os.path.join(root, "bench", "reference", ref + ".py"),
            "bench_reference_" + ref)
        self._ref_fns: dict = {}
        self.annotate = (jax.profiler.TraceAnnotation if trace
                         else lambda name: contextlib.nullcontext())

    @functools.cached_property
    def limits(self) -> dict:
        """The cell's own limits: ``tol``, a field's gap above which a
        point counts as off, and the largest value of each number that
        ``check_numbers`` gives."""
        return load_json(os.path.join(self.root, "bench", "limits",
                                      self.name + ".json"))

    @property
    def points_per_dispatch(self) -> int:
        return traffic.points_per_dispatch(self.mix)

    def dispatch(self, seed: int, d: int) -> dict:
        """Dispatch ``d`` of a run with ``seed``: one ``sweep`` call on one
        chunk, its summary brought to the host."""
        sw, grid = self._sweep, self.mix["grid"]
        with self.annotate("build_axes"):
            axes = sw.make_axes(
                list(traffic.grid_seeds(self.mix, seed, d)),
                list(grid["bid_mults"]), instances=list(grid["instances"]),
                policies=list(grid["policies"]))
            spec = sw.SweepSpec(axes=axes, workload=self.workload,
                                devices=self.chips)
        with self.annotate("sweep_call"):
            res = sw.sweep(spec, self.cfg)
        with self.annotate("fetch_result"):
            return flatten(res)

    def points(self, seed: int, dispatches, rows) -> dict:
        """The parameters of points ``rows`` of the given dispatches."""
        import numpy as np
        grids = [traffic.grid(self.mix, seed, d) for d in dispatches]
        return {k: np.concatenate([getattr(g, k) for g in grids])[rows]
                for k in traffic.Grid._fields}

    def reference(self, points: dict, dtype=None) -> dict:
        """The reference's summaries of ``points``, in ``dtype`` (the
        deployment's float32 by default)."""
        import jax.numpy as jnp
        dtype = jnp.float32 if dtype is None else dtype
        if dtype not in self._ref_fns:
            self._ref_fns[dtype] = self.ref.build(
                self.config, self.ticks, traffic.schedule(self.mix),
                dtype=dtype, compiler_options=NO_DUMP)
        return self.ref.run(self._ref_fns[dtype], self.config, points,
                            block=len(points["seed"]))

    def numbers(self, prog: dict, ref: dict) -> dict:
        return check_numbers(gaps(prog, ref, self.ref.COUNTS),
                             self.limits["tol"])


def run(argv, root: str, t0: float, require_chip: bool = True) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    trace = bool(args.trace)
    cell = Cell(root, args.workload, trace=trace, require_chip=require_chip)
    t_cell = time.perf_counter()

    import jax
    import numpy as np

    # Set-up: imports, device, traffic, and one dispatch that compiles (or
    # loads from the persistent cache) the one chunk shape of this cell.
    cell.dispatch(args.seed, 0)
    setup_s = time.perf_counter() - t0
    print(f"setup: imports, device and cell {t_cell - t0:.3f} s, first "
          f"dispatch {time.perf_counter() - t_cell:.3f} s", file=sys.stderr)

    ctx_trace = (jax.profiler.trace(cell.paths["trace"]) if trace
                 else contextlib.nullcontext())
    results = []
    with ctx_trace:
        start = time.perf_counter()
        while True:
            results.append(cell.dispatch(args.seed, len(results) + 1))
            if time.perf_counter() - start >= args.seconds:
                break
        window_s = time.perf_counter() - start
    n_disp = len(results)
    attempted = n_disp * cell.points_per_dispatch
    point_ticks = attempted * cell.ticks

    peak = max((dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for dev in cell.devices)
    print(f"peak_bytes_in_use {peak}", file=sys.stderr)

    # The check: a sample of the window's points against the reference.
    prog = {k: np.concatenate([r[k] for r in results]) for k in results[0]}
    del results
    finite = np.all([np.isfinite(np.asarray(v, np.float64)).reshape(
        attempted, -1).all(axis=1) for v in prog.values()], axis=0)
    failed = int(np.sum(~finite))
    rows = sample_rows(attempted, int(cell.mix["sample"]), args.seed)
    ref = cell.reference(cell.points(args.seed, range(1, n_disp + 1), rows))
    numbers = cell.numbers({k: v[rows] for k, v in prog.items()}, ref)
    checks = {k: {"value": v, "limit": cell.limits[k]}
              for k, v in numbers.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())

    device = {"platform": cell.platform, "kind": cell.devices[0].device_kind,
              "count": len(cell.devices), "memory_peak_bytes": int(peak)}
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": failed}
    if trace:
        layer_ctx, breakdown = read_trace(cell.paths, cell.chips,
                                          point_ticks, window_s)
        device.update(busy_s=layer_ctx["busy_s"], window_s=window_s)
        metrics = {}
        for m in cell.per_layer:
            reader = load_module(os.path.join(root, "bench", "layers",
                                              m["name"] + ".py"),
                                 "bench_layer_" + m["name"].replace(".", "_"))
            value = reader.read(layer_ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        out.update(metrics=metrics, device=device, breakdown=breakdown)
    else:
        values = {"point_ticks_per_s": point_ticks / window_s,
                  "setup_s": setup_s}
        out.update(metrics={m["name"]: {"value": values[m["name"]],
                                        "unit": m["unit"]}
                            for m in cell.end_to_end},
                   device=device)
    out["checks"] = checks
    print(f"dispatches {n_disp} points {attempted} window_s {window_s} "
          f"setup_s {setup_s}", file=sys.stderr)
    for k, c in checks.items():
        print(f"check {k} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


def read_trace(paths: dict, chips: int, point_ticks: int,
               window_s: float) -> tuple[dict, dict]:
    """Reduce the window's trace: device time by kind over the chips
    used, busy time averaged over them, the ten longest ops and the ten
    longest idle gaps on the first chip by the host span they fell in."""
    import glob
    from bench import trace as tr
    files = glob.glob(os.path.join(paths["trace"], "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise RuntimeError("the profiler wrote no trace")
    xplane = max(files, key=os.path.getmtime)
    indexes = tr.dump_indexes(paths["hlo"])
    per_dev = tr.device_ops(xplane, chips)
    kinds = collections.Counter()
    for ops in per_dev:
        kinds.update(tr.by_kind(ops, indexes))
    busy = [tr.busy_ns(ops) for ops in per_dev]
    spans = tr.host_spans(xplane, SPANS)
    n_ops = sum(len(ops) for ops in per_dev)
    print(f"trace: {n_ops} op events on {chips} chip(s); modules dumped "
          f"{sorted(indexes)}; modules on the trace "
          f"{sorted({op[3] for op in per_dev[0]})}; device ns by kind "
          f"{dict(kinds)}; {len(spans)} host spans", file=sys.stderr)
    ctx = {"by_kind_ns": dict(kinds), "busy_s": sum(busy) / len(busy) / 1e9,
           "window_s": window_s, "point_ticks": point_ticks, "chips": chips,
           "modules": sorted(indexes)}
    gaps_named = []
    if spans:
        lo, hi = spans[0][1], spans[-1][2]
        for a, b in tr.idle_gaps(per_dev[0], lo, hi):
            mid = (a + b) // 2
            name = next((s[0] for s in spans if s[1] <= mid < s[2]),
                        "between_spans")
            gaps_named.append((name, (b - a) / 1e9))
    gaps_named.sort(key=lambda g: -g[1])
    breakdown = {
        "device_ops": [[name, ns / 1e9] for name, ns
                       in tr.top_ops(per_dev[0], indexes, 10)],
        "idle_gaps": [[name, s] for name, s in gaps_named[:10]]}
    return ctx, breakdown


def main(argv, root: str, t0: float, require_chip: bool = True) -> int:
    try:
        return run(argv, root, t0, require_chip=require_chip)
    except Refused as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
