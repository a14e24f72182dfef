"""Helpers for the benchmark's own tests: a checkout of the benchmark's
files at a size the CPU runs in seconds, and one run of a cell there
without the look for a chip."""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import time

from bench import harness

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tiny_root(dest: str, seeds: int = 2, bids: int = 2, policies: int = 2,
              ticks: int | None = 40, sample: int = 8) -> str:
    """``dest`` holding ``BENCHMARK.json`` and the data under ``bench/``,
    every mix cut to ``seeds`` x ``bids`` x ``policies`` points per
    dispatch and ``ticks`` ticks (``None`` keeps the mix's own)."""
    os.makedirs(dest, exist_ok=True)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), dest)
    shutil.copytree(os.path.join(REPO, "bench"), os.path.join(dest, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"),
                    dirs_exist_ok=True)
    mixes = os.path.join(dest, "bench", "mixes")
    for name in os.listdir(mixes):
        path = os.path.join(mixes, name)
        mix = harness.load_json(path)
        g = mix["grid"]
        g["seeds"] = seeds
        g["bid_mults"] = g["bid_mults"][:bids]
        g["policies"] = g["policies"][:policies]
        if ticks is not None:
            mix["ticks"] = ticks
        mix["sample"] = sample
        with open(path, "w") as fh:
            json.dump(mix, fh)
    return dest


def add_cell(root: str, cell: dict) -> None:
    """Add ``cell`` to ``root``'s ``BENCHMARK.json`` unless it is there."""
    path = os.path.join(root, "BENCHMARK.json")
    spec = harness.load_json(path)
    if cell["name"] not in {w["name"] for w in spec["workloads"]}:
        spec["workloads"].append(cell)
        with open(path, "w") as fh:
            json.dump(spec, fh)


def run_cell(root: str, workload: str, seed: int = 3_000_000_019,
             seconds: float = 0.01) -> tuple[int, dict | None, str]:
    """One run of ``workload`` in ``root`` on whatever devices JAX has:
    the exit code, the result line (or None) and standard error."""
    out, err = io.StringIO(), io.StringIO()
    with contextlib.redirect_stdout(out), contextlib.redirect_stderr(err):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", "0"],
                          root=root, t0=time.perf_counter(),
                          require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()
