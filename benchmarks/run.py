"""Benchmark suite driver — auto-discovers every ``benchmarks/bench_*.py``.

Emits ``name,value,derived`` CSV rows (value is the headline number of the
artifact; ``derived`` packs the secondary columns).

Discovery replaces the old hand-maintained suite table: any module named
``bench_<suite>.py`` in this directory is picked up automatically, so a
newly added benchmark can never silently miss CI — the CI bench job runs
``python -m benchmarks.run --smoke`` instead of hand-listing steps, then
gates every ``results/BENCH_*.json`` against ``benchmarks/baselines/``
via ``check_bench_regression.py --auto``.

Each suite module exposes ``main(emit)`` — or ``main(emit, smoke=...)``
for the suites with a reduced CI mode; ``--smoke`` is forwarded to those
that accept it.  A failing suite (exception *or* a ``SystemExit`` from an
acceptance check) is reported in its ``_suite_*`` row and turns the exit
code non-zero, but never hides the remaining suites.

``--json PATH`` additionally writes a machine-readable report — one
record per suite (name, ok, wall_s, error, and ``gate``: the regression
verdict of the suite's ``results/BENCH_*.json`` against its committed
baseline, via ``check_bench_regression.gate_errors``) plus the overall
verdict — for CI artifact upload and downstream dashboards; the CSV on
stdout is unchanged.  The gate column is advisory inside this report
(CI still runs ``check_bench_regression --auto`` as its own failing
step, with attribution); suites whose artifact has no committed
baseline report ``gate: null``.

CLI:  PYTHONPATH=src python -m benchmarks.run [--smoke] [--json PATH] [suite]
"""

from __future__ import annotations

import argparse
import importlib
import inspect
import json
import pathlib
import sys
import time

from repro.launch.compile_cache import enable_compile_cache

BASELINES_DIR = pathlib.Path(__file__).resolve().parent / "baselines"


def discover() -> dict:
    """suite name → module *name*, for every ``bench_*.py`` beside this
    file.  Import happens lazily inside each suite's try/except, so one
    module with an import-time error cannot hide the remaining suites."""
    here = pathlib.Path(__file__).resolve().parent
    package = __package__ or "benchmarks"
    return {path.stem[len("bench_"):]: f"{package}.{path.stem}"
            for path in sorted(here.glob("bench_*.py"))}


def _suite_gate(started: float) -> tuple[bool | None, list[str]]:
    """Regression-gate every ``results/BENCH_*.json`` the suite that just
    ran (re)wrote, against its committed baseline.  Returns the combined
    verdict (``None`` when no refreshed artifact has a baseline) and the
    per-artifact failure messages."""
    try:
        from .check_bench_regression import gate_errors
    except ImportError:          # direct script execution
        from check_bench_regression import gate_errors
    verdict: bool | None = None
    errors: list[str] = []
    for artifact in sorted(pathlib.Path("results").glob("BENCH_*.json")):
        if artifact.stat().st_mtime < started:
            continue             # stale: written by an earlier suite/run
        baseline = BASELINES_DIR / artifact.name
        if not baseline.exists():
            continue
        try:
            current = json.loads(artifact.read_text())
            base = json.loads(baseline.read_text())
            errs = gate_errors(current, base)
        except (OSError, ValueError) as e:
            errs = [f"unreadable ({e})"]
        verdict = (verdict is not False) and not errs
        errors.extend(f"{artifact.name}: {e}" for e in errs)
    return verdict, errors


def _call_suite(module_name: str, emit, smoke: bool) -> None:
    """Import and run one suite's ``main``, forwarding ``smoke`` when it
    accepts it."""
    mod = importlib.import_module(module_name)
    sig = inspect.signature(mod.main)
    if "smoke" in sig.parameters:
        mod.main(emit, smoke=smoke)
    else:
        mod.main(emit)


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("only", nargs="?", default=None,
                    help="run a single suite (e.g. 'spot', 'tuning')")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced sizes for CI on suites that support it")
    ap.add_argument("--json", metavar="PATH", default=None,
                    help="also write a machine-readable per-suite report "
                         "(pass/fail + wall clock) to this path")
    args = ap.parse_args(argv)
    enable_compile_cache(BASELINES_DIR.parent.parent)

    suites = discover()
    if args.only is not None and args.only not in suites:
        print(f"unknown suite {args.only!r}; discovered: "
              f"{', '.join(suites)}", file=sys.stderr)
        return 2
    print("name,value,derived")

    def emit(name, value, derived=""):
        print(f"{name},{value:.6g},{derived}", flush=True)

    failures: list[str] = []
    records: list[dict] = []
    for name, module_name in suites.items():
        if args.only and args.only != name:
            continue
        t0 = time.time()
        try:
            _call_suite(module_name, emit, args.smoke)
            wall = time.time() - t0
            gate, gate_errs = _suite_gate(t0)
            status = "ok" if gate is None else f"ok;gate={'pass' if gate else 'FAIL'}"
            emit(f"_suite_{name}_wall_s", wall, status)
            records.append({"suite": name, "ok": True,
                            "wall_s": round(wall, 3), "error": None,
                            "gate": gate, "gate_errors": gate_errs})
        except (Exception, SystemExit) as e:  # a failed suite (even at
            wall = time.time() - t0           # import) must not hide the
            err = f"{type(e).__name__}:{e}"   # others
            emit(f"_suite_{name}_wall_s", wall, f"FAILED:{err}")
            records.append({"suite": name, "ok": False,
                            "wall_s": round(wall, 3), "error": err,
                            "gate": None, "gate_errors": []})
            failures.append(name)
    if args.json:
        path = pathlib.Path(args.json)
        path.parent.mkdir(parents=True, exist_ok=True)
        gates_ok = all(r["gate"] is not False for r in records)
        path.write_text(json.dumps(
            {"smoke": bool(args.smoke), "ok": not failures,
             "gates_ok": gates_ok, "suites": records}, indent=2) + "\n")
    if failures:
        print(f"benchmark suites failed: {', '.join(failures)}",
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
