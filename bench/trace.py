"""From a profiler trace and the compiled HLO to device time by op kind.

The op-kind classification, the union of op intervals and the idle share
are copied from ``benchmarks/profile_sweep.py``, so that no change to the
program can change how the benchmark reads a trace.

* ``hlo_index`` maps each instruction of an HLO module's text to its
  kind: ``scatter``, ``gather``, ``sort`` or ``kernel`` (a custom call),
  for the op itself or, for a fusion, for the first of those kinds found
  in its fused computations, in that order; ``container`` for a while,
  call or conditional, whose time is that of the ops inside; ``other``
  for the rest.
* ``device_ops`` reads ``(op, start ns, duration ns, module)`` of every
  event on a TPU's ``XLA Ops`` line; ``module`` is the ``XLA Modules``
  event that holds the op, or ``""``.
* ``busy_ns`` is the length of the union of op intervals.
* ``by_kind`` sums device time per kind, containers left out.
"""

from __future__ import annotations

import collections
import glob
import os
import re

OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
KINDS = ("scatter", "gather", "sort", "kernel")
_OPCODE_KIND = {"scatter": "scatter", "gather": "gather", "sort": "sort",
                "custom-call": "kernel"}
_CONTAINERS = {"while", "conditional", "call"}

_COMP = re.compile(r"^(?:ENTRY\s+)?%([\w.\-]+)\s.*\{\s*$")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)


def hlo_index(hlo: str) -> dict[str, str]:
    """Map each HLO instruction name to its kind (see the module doc)."""
    comps: dict[str, list] = {}
    body = None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = _INSTR.match(line)
        if m and body is not None:
            calls = _CALLS.search(line) if m.group(2) == "fusion" else None
            body.append((m.group(1), m.group(2),
                         calls.group(1) if calls else None))

    def found(opcode, calls, depth=0) -> set:
        kinds = set()
        if opcode in _OPCODE_KIND:
            kinds.add(_OPCODE_KIND[opcode])
        if calls and depth < 16:
            for _, op, sub in comps.get(calls, ()):
                kinds |= found(op, sub, depth + 1)
        return kinds

    index = {}
    for instrs in comps.values():
        for name, opcode, calls in instrs:
            kinds = found(opcode, calls)
            kind = next((k for k in KINDS if k in kinds), None)
            if kind is not None:
                index[name] = kind
            elif opcode in _CONTAINERS:
                index[name] = "container"
            else:
                index[name] = "other"
    return index


def module_name(hlo: str) -> str:
    m = _MODULE.search(hlo)
    return m.group(1) if m else ""


def dump_indexes(dump_dir: str) -> dict[str, dict[str, str]]:
    """``{module name: hlo_index}`` of every optimized module that XLA
    dumped as text into ``dump_dir``; a module dumped twice keeps its
    newest text, that of the program compiled or loaded last."""
    paths = sorted(glob.glob(os.path.join(dump_dir,
                                          "*after_optimizations.txt")),
                   key=os.path.getmtime)
    newest: dict[str, str] = {}
    for path in paths:
        with open(path) as fh:
            text = fh.read()
        name = module_name(text)
        if name:
            newest[name] = text
    return {name: hlo_index(text) for name, text in newest.items()}


def _base(name: str) -> str:
    """``jit_pt(123)`` and ``jit_pt`` name one module."""
    return name.split("(", 1)[0].strip()


def device_ops(xplane_path: str, devices: int) -> list[list[tuple]]:
    """Per device ``/device:TPU:i`` (``i < devices``): ``(op name, start
    ns, duration ns, module name)`` of every event on its op line."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(xplane_path)
    planes = {plane.name: plane for plane in pd.planes}
    out = []
    for i in range(devices):
        plane = planes.get(f"/device:TPU:{i}")
        if plane is None:
            raise RuntimeError(f"no plane /device:TPU:{i} in the trace: "
                               f"{sorted(planes)}")
        lines = {ln.name: ln for ln in plane.lines}
        if OPS_LINE not in lines:
            raise RuntimeError(f"/device:TPU:{i} has no {OPS_LINE!r} line: "
                               f"{sorted(lines)}")
        mods = sorted((int(ev.start_ns), int(ev.start_ns + ev.duration_ns),
                       _base(ev.name))
                      for ev in (lines[MODULES_LINE].events
                                 if MODULES_LINE in lines else ()))
        ops, j = [], 0
        for ev in sorted(lines[OPS_LINE].events, key=lambda e: e.start_ns):
            start = int(ev.start_ns)
            while j < len(mods) and mods[j][1] <= start:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= start else ""
            ops.append((ev.name, start, int(ev.duration_ns), mod))
        out.append(ops)
    return out


def host_spans(xplane_path: str, names) -> list[tuple[str, int, int]]:
    """``(name, start ns, end ns)`` of every host event named in
    ``names`` (the benchmark's own ``TraceAnnotation`` spans)."""
    from jax.profiler import ProfileData
    names = set(names)
    spans = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    spans.append((ev.name, int(ev.start_ns),
                                  int(ev.start_ns + ev.duration_ns)))
    return sorted(spans, key=lambda s: s[1])


def busy_ns(ops) -> int:
    """Length of the union of the op intervals."""
    total, end = 0, None
    for op in sorted(ops, key=lambda o: o[1]):
        start, dur = op[1], op[2]
        stop = start + dur
        if end is None or start >= end:
            total += dur
            end = stop
        elif stop > end:
            total += stop - end
            end = stop
    return total


def idle_gaps(ops, lo: int, hi: int) -> list[tuple[int, int]]:
    """The intervals of ``[lo, hi)`` in which no op ran."""
    gaps, at = [], lo
    for op in sorted(ops, key=lambda o: o[1]):
        start, stop = op[1], op[1] + op[2]
        if start > at:
            gaps.append((at, min(start, hi)))
        at = max(at, stop)
        if at >= hi:
            break
    if at < hi:
        gaps.append((at, hi))
    return [(a, b) for a, b in gaps if b > a]


def kind_of(op, indexes: dict[str, dict[str, str]]) -> str:
    """The kind of one op event: from its own module's index where the
    trace names the module, else from any module that has the name."""
    key = op[0].lstrip("%").split(" ", 1)[0]
    mod = op[3] if len(op) > 3 else ""
    if mod in indexes and key in indexes[mod]:
        return indexes[mod][key]
    for idx in indexes.values():
        if key in idx:
            return idx[key]
    return "unmatched"


def by_kind(ops, indexes: dict[str, dict[str, str]]) -> collections.Counter:
    """Device ns per kind; containers left out (their time is that of the
    ops inside them)."""
    out = collections.Counter()
    for op in ops:
        kind = kind_of(op, indexes)
        if kind != "container":
            out[kind] += op[2]
    return out


def top_ops(ops, indexes, n: int = 10) -> list[tuple[str, int]]:
    """The ``n`` ops with the most device time, as ``"name (kind)"``."""
    per = collections.Counter()
    for op in ops:
        kind = kind_of(op, indexes)
        if kind != "container":
            per[f"{op[0].lstrip('%').split(' ', 1)[0]} ({kind})"] += op[2]
    return per.most_common(n)
