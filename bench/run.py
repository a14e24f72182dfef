"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell, its deployment, traffic and
metrics are read from ``BENCHMARK.json`` and the files under ``bench/``;
see ``bench/harness.py``.  Exits with 2, and prints no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], root=ROOT, t0=T0))
