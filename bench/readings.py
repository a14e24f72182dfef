"""The readings that the limits of ``correct`` are set from.

    python3 bench/readings.py --workload <name> --seeds 12 --control 3

In one process, on the cell's own sizes: for each of ``--seeds`` seeds,
one dispatch of the timed path, a sample of its points (the mix's
``sample``) against the reference: the sound readings.  For each of
``--control`` seeds, the reference computed one precision lower than the
deployment states (bfloat16 for float32) against the reference itself:
the control, which has to fail.  One JSON line per reading.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402

# The nearest precision below the one the deployment states.
LOWER = {"float32": "bfloat16"}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=9_000_000_000)
    args = ap.parse_args(argv)
    cell = harness.Cell(ROOT, args.workload)
    import jax.numpy as jnp
    import numpy as np
    per = cell.points_per_dispatch
    k = int(cell.mix["sample"])
    for i in range(args.seeds + args.control):
        seed = args.first_seed + i
        rows = harness.sample_rows(per, k, seed)
        points = cell.points(seed, [1], rows)
        ref = cell.reference(points)
        if i < args.seeds:
            t = time.perf_counter()
            prog = cell.dispatch(seed, 1)
            kind, other = "program", {f: prog[f][rows] for f in prog}
            t_prog = time.perf_counter() - t
        else:
            low = LOWER[cell.config["precision"]]
            kind, t_prog = "control_" + low, 0.0
            other = cell.reference(points, dtype=getattr(jnp, low))
        nums = cell.numbers(other, ref)
        g = harness.gaps(other, ref, cell.ref.COUNTS)
        off = {f: int(np.sum(g[:, j] > cell.limits["tol"]))
               for j, f in enumerate(ref)}
        widest = {f: float(np.max(g[:, j])) for j, f in enumerate(ref)}
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "kind": kind, "points": len(rows), **nums,
                          "fields_off": off, "widest_gap": widest,
                          "dispatch_s": t_prog,
                          "elapsed_s": time.perf_counter() - T0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
