"""Where one sweep chunk spends its device time, from a profiler trace.

Compiles the executor's chunk program (``sweep._sweep_callable``) for the
``bench_throughput`` configuration at 4,096 grid points, runs it
once to warm up, then once more under ``jax.profiler.trace``.  Each event
on the ``XLA Ops`` line of the first TPU is joined by name with the
compiled HLO, and its device time is summed two ways:

  * by kind: an op, or a fusion whose fused computation holds one, counts
    as ``scatter``, ``gather``, ``sort`` or ``kernel`` (a Pallas custom
    call), checked in that order; anything else is ``other``;
  * by kind and source line: the innermost frame, outside site-packages,
    of the HLO stack trace of the op that gave the kind.  An op inside a
    jitted ``jax.numpy`` helper (``jnp.argsort``) keeps the source line of
    the helper's first call in the process, since JAX reuses that trace;
    the TPU compiler's scatter fusions carry no source line at all.

Shares are of the device's busy time: the union of the op intervals.  The
idle share is one minus busy over the traced call's host wall time.  The
summary goes to ``<out>/profile.json``, beside the trace and the HLO.
Refuses to run without a TPU.

  PYTHONPATH=src python -m benchmarks.profile_sweep [--out DIR]
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys
import time

POINTS = 4096
TOP = 15          # rows kept of the per-source and per-op tables
DEVICE, LINE = "/device:TPU:0", "XLA Ops"
KINDS = ("scatter", "gather", "sort", "kernel")
_OPCODE_KIND = {"scatter": "scatter", "gather": "gather", "sort": "sort",
                "custom-call": "kernel"}
_CONTAINERS = {"while", "conditional", "call"}

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_TABLE_ROW = re.compile(r"^(\d+)\s+(.*)$")
_FIELD = re.compile(r"(\w+)=(\d+)")


def _tables(hlo: str) -> dict:
    """The FileNames, FunctionNames, FileLocations and StackFrames tables
    at the head of an HLO module's text."""
    tables: dict = {}
    current = None
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            current = tables.setdefault(line, {})
            continue
        m = _TABLE_ROW.match(line)
        if current is None or not m:
            current = None
            continue
        current[int(m.group(1))] = m.group(2)
    return tables


def _frame_source(tables: dict, frame_id: int | None) -> str:
    """``file:line function`` of the innermost frame outside
    site-packages, walking out from ``frame_id``."""
    frames = tables.get("StackFrames", {})
    seen = set()
    while frame_id and frame_id in frames and frame_id not in seen:
        seen.add(frame_id)
        f = dict((k, int(v)) for k, v in _FIELD.findall(frames[frame_id]))
        loc = dict((k, int(v)) for k, v in _FIELD.findall(
            tables["FileLocations"][f["file_location_id"]]))
        path = tables["FileNames"][loc["file_name_id"]].strip('"')
        if "site-packages" not in path:
            func = tables["FunctionNames"][loc["function_name_id"]].strip('"')
            short = path.split("/repro/", 1)[-1] if "/repro/" in path else (
                os.path.basename(path))
            return f"{short}:{loc['line']} {func}"
        frame_id = f.get("parent_frame_id")
    return "unattributed"


def hlo_index(hlo: str) -> dict[str, tuple[str, str]]:
    """Map each HLO instruction name to ``(kind, source)``: the first of
    ``KINDS`` found in the op itself or, for a fusion, in its fused
    computations; ``other`` or ``container`` (a while, call or
    conditional, whose time is that of the ops inside) otherwise."""
    tables = _tables(hlo)
    comps: dict[str, list] = {}
    body = None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and body is not None:
            frame = _FRAME.search(line)
            calls = _CALLS.search(line) if m.group(2) == "fusion" else None
            body.append((m.group(1), m.group(2),
                         int(frame.group(1)) if frame else None,
                         calls.group(1) if calls else None))

    def found(opcode, frame, calls) -> dict[str, int | None]:
        """Every kind in one instruction, with the frame that gave it."""
        kinds = {}
        if opcode in _OPCODE_KIND:
            kinds[_OPCODE_KIND[opcode]] = frame
        for _, op, fr, sub in comps.get(calls, ()) if calls else ():
            for k, v in found(op, fr, sub).items():
                kinds.setdefault(k, v)
        return kinds

    index = {}
    for instrs in comps.values():
        for name, opcode, frame, calls in instrs:
            kinds = found(opcode, frame, calls)
            kind = next((k for k in KINDS if k in kinds), None)
            if kind is not None:
                index[name] = (kind, _frame_source(tables, kinds[kind]))
            elif opcode in _CONTAINERS:
                index[name] = ("container", _frame_source(tables, frame))
            else:
                index[name] = ("other", _frame_source(tables, frame))
    return index


def device_ops(xplane_path: str) -> list[tuple[str, int, int]]:
    """``(op name, start ns, duration ns)`` of every event on the first
    TPU's op line of a profiler trace."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    names = []
    for plane in pd.planes:
        names.append(plane.name)
        if plane.name != DEVICE:
            continue
        lines = {ln.name: ln for ln in plane.lines}
        if LINE not in lines:
            raise RuntimeError(f"{DEVICE} has no {LINE!r} line: "
                               f"{sorted(lines)}")
        return [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                for ev in lines[LINE].events]
    raise RuntimeError(f"no plane {DEVICE!r} in the trace: {names}")


def busy_ns(ops) -> int:
    """Length of the union of the op intervals."""
    total, end = 0, None
    for _, start, dur in sorted(ops, key=lambda o: o[1]):
        stop = start + dur
        if end is None or start >= end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def summarize(ops, index: dict, window_s: float) -> dict:
    """Device time by kind and by (kind, source), as shares of busy."""
    busy = busy_ns(ops)
    by_kind = collections.Counter()
    by_source = collections.Counter()
    per_op = collections.Counter()
    unmatched = 0
    for name, _, dur in ops:
        key = name.lstrip("%").split(" ", 1)[0]
        kind, source = index.get(key, ("unmatched", "unattributed"))
        unmatched += kind == "unmatched"
        if kind == "container":
            continue
        by_kind[kind] += dur
        by_source[(kind, source)] += dur
        per_op[key] += dur

    def rows(counter, n=None):
        return [{"key": k if isinstance(k, str) else " | ".join(k),
                 "ms": v / 1e6, "share_of_busy": v / busy}
                for k, v in counter.most_common(n)]

    return {"window_s": window_s, "busy_ms": busy / 1e6,
            "idle_share": 1.0 - busy / 1e9 / window_s,
            "events": len(ops), "unmatched_events": unmatched,
            "by_kind": rows(by_kind), "by_source": rows(by_source, TOP),
            "per_op": rows(per_op, TOP)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default="results/profile_sweep")
    args = ap.parse_args(argv)

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import numpy as np
    dev = jax.devices()[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind}",
          flush=True)
    if dev.platform != "tpu":
        print("profile_sweep: no TPU; refusing to run", file=sys.stderr)
        return 1

    from benchmarks import bench_throughput as bt
    from repro.launch.compile_cache import enable_compile_cache
    from repro.sim import paper_schedule, runner
    from repro.sim import sweep as sweep_lib
    from repro.sim import workloads as wl
    checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    enable_compile_cache(checkout)

    cfg = bt._cfg()
    schedule = paper_schedule(ttc=7500.0, arrival_gap_ticks=1)
    axes = bt._axes(range(POINTS // len(bt.POLICIES)), (2.0,))
    rest = (wl.as_jax_schedule(schedule), runner.default_params(cfg))
    fn = sweep_lib._sweep_callable(schedule, cfg, None)
    t0 = time.perf_counter()
    compiled = fn.lower(*axes, *rest).compile()
    print(f"compile_s={time.perf_counter() - t0:.3f}", flush=True)
    jax.block_until_ready(compiled(*axes, *rest))

    os.makedirs(args.out, exist_ok=True)
    hlo = compiled.as_text()
    with gzip.open(os.path.join(args.out, "chunk.hlo.txt.gz"), "wt") as f:
        f.write(hlo)
    with jax.profiler.trace(args.out):
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*axes, *rest))
        window_s = time.perf_counter() - t0
    assert all(np.all(np.isfinite(np.asarray(leaf)))
               for leaf in jax.tree.leaves(out))
    path = max(glob.glob(os.path.join(args.out, "plugins", "profile", "*",
                                      "*.xplane.pb")), key=os.path.getmtime)
    rep = summarize(device_ops(path), hlo_index(hlo), window_s)
    rep.update(points=int(axes.seed.shape[0]), device=dev.device_kind)
    with open(os.path.join(args.out, "profile.json"), "w") as f:
        json.dump(rep, f, indent=1)
    print(f"window_s={window_s:.4f} busy_ms={rep['busy_ms']:.3f} "
          f"idle_share={rep['idle_share']:.4f} events={rep['events']} "
          f"unmatched_events={rep['unmatched_events']}")
    for section in ("by_kind", "by_source", "per_op"):
        print(section)
        for r in rep[section]:
            print(f"  {r['share_of_busy']:8.4%} {r['ms']:12.3f} ms  "
                  f"{r['key']}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
