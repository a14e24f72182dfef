"""From a profiler trace, the compiled HLO and the program's own spans
and counters to device time per phase of the scan step, idle time per
host span of ``sweep``, and the set-up's trace and compile seconds.

The names are the benchmark's own copy of what the program emits, so
that no change to the program can change how they are read:

* ``PHASES``: the ``jax.named_scope`` of each section of the scan step
  (``sim/runner.py`` ``make_step``; ``kalman`` inside ``control``, in
  ``core/controller.py``).  ``phase_index`` maps each instruction of an
  optimized HLO module to the innermost listed phase in its ``op_name``
  path, matched on whole ``/`` segments (``jit(argsort)`` is no phase),
  else to ``rest``; a fusion to its fused root's phase, and an op the
  compiler made to the phase of the ops it was made from (see
  ``phase_index``).  A fusion whose fused computations hold ops of more
  than one phase is "mixed": its time is wholly its root's phase.
* ``SPANS``: the host spans of ``sim.sweep`` (``obs/spans.py``).
  ``span_idle_ns`` gives each idle instant of a chip to the innermost
  span that holds it, and averages over the chips.
* The set-up's counters: ``obs.spans.counters()`` as it stood when the
  process's first ``sweep`` returned, the harness's set-up dispatch.

The readers in ``bench/layers`` take what they need from ``ctx``:
``device_ops`` (per chip, as ``trace.device_ops`` reads them),
``program_spans`` (as ``trace.host_spans`` reads them), ``hlo_dir`` (the
cell's dump) and ``setup_counters``.  What ``ctx`` lacks is found the
way the harness found it: the dump directory that ``XLA_FLAGS`` names,
the newest trace beside it, and the program's counters in this process.
Where there is nothing to read (a program without the scopes, spans or
counters) a reader returns ``None``.
"""

from __future__ import annotations

import collections
import glob
import os
import re
import sys

from bench import trace as tr

PHASES = ("arrivals", "market", "faults", "billing", "execute", "control",
          "kalman", "acquire", "summary", "obs")
REST = "rest"
# The phases no metric of their own reads: ``rest_ns`` sums them.
REST_PHASES = ("arrivals", "faults", "summary", "obs", REST)
SPANS = ("sweep", "sweep.prepare", "sweep.lookup", "sweep_chunk",
         "sweep.slice_pad", "sweep.compile", "sweep.launch",
         "sweep.to_host", "sweep.write", "sweep.assemble")
INPUT_SPANS = ("sweep.prepare", "sweep.lookup", "sweep.slice_pad",
               "sweep.launch")
RESULT_SPANS = ("sweep.to_host", "sweep.assemble")
COUNTERS_MODULE = "repro.obs.spans"
UNMATCHED_LIMIT = 0.1

# Ops of a fused computation that say nothing of its phase: a constant
# the compiler hoisted keeps the scope it was first written in.
_NO_WORK = {"parameter", "constant", "iota"}
_DEPTH = 64
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%([\w.\-]+)")
_DUMP_TO = re.compile(r"--xla_dump_to=(\S+)")


def phase_of_path(op_name: str) -> str:
    """The innermost listed phase among the ``/`` segments of an
    ``op_name``, else ``rest``."""
    for seg in reversed(op_name.split("/")):
        if seg in PHASES:
            return seg
    return REST


def phase_index(hlo: str) -> tuple[dict[str, str], set[str]]:
    """``({instruction: phase}, {fusions whose computations mix
    phases})`` of an HLO module's text; the instructions are those that
    ``trace.hlo_index`` classifies, read with its patterns.

    An instruction's phase is that of its own ``op_name``.  A fusion's is
    its fused root's: the compiler names a fusion after one of the ops
    it fused, sometimes a hoisted constant's broadcast.  An op without
    an ``op_name``, or named after the call that holds the phases (the
    scan step's ``closed_call``), was made or rewritten by the compiler
    (a copy, a split reduction; on the TPU the sort and the scatter it
    makes of one ``.at[order].set``): it takes the phase of its first
    operand that has one, else of its first user in its computation that
    has one.  A loop, a call or a conditional is not looked through.
    What has none, and every op whose ``op_name`` is code outside every
    scope (the initial state, the summary of a run, the loop's own
    counter), is ``rest``."""
    comps: dict[str, dict] = {}
    roots: dict[str, str] = {}
    body = comp = None
    for line in hlo.splitlines():
        m = tr._COMP.match(line)
        if m:
            comp = m.group(1)
            body = comps.setdefault(comp, {})
            continue
        m = tr._INSTR.match(line)
        if m and body is not None:
            calls = tr._CALLS.search(line) if m.group(2) == "fusion" else None
            path = _OP_NAME.search(line)
            operands = line[m.end():].split("),", 1)[0]
            body[m.group(1)] = (
                path.group(1) if path else None,
                calls.group(1) if calls else None, m.group(2),
                _OPERAND.findall(operands))
            if line.lstrip().startswith("ROOT"):
                roots[comp] = m.group(1)
    # The paths of the calls that hold phases: an op named after one of
    # them was made by the compiler, not written there.
    holders = set()
    for instrs in comps.values():
        for path, *_ in instrs.values():
            segs = (path or "").split("/")
            holders.update("/".join(segs[:i]) for i, seg in enumerate(segs)
                           if seg in PHASES)
    for instrs in comps.values():
        for name, (path, *others) in instrs.items():
            own = (None if path is None or path in holders
                   else phase_of_path(path))
            instrs[name] = (own, *others)

    memo: dict = {}

    def resolve(comp, name, depth=0):
        """A listed phase for ``name`` in ``comp``, or None."""
        key = (comp, name)
        if key not in memo:
            memo[key] = None            # a cycle resolves to nothing
            own, calls, opcode, operands = comps[comp][name]
            found = own if own not in (None, REST) else None
            if calls in roots and depth < _DEPTH:
                found = resolve(calls, roots[calls], depth + 1) or found
            if (own is None and found is None
                    and opcode not in tr._CONTAINERS and depth < _DEPTH):
                found = next((p for p in (
                    resolve(comp, o, depth + 1) for o in operands
                    if o in comps[comp]) if p is not None), None)
            memo[key] = found
        return memo[key]

    def held(calls, depth=0) -> set:
        """The listed phases of the work ops of a fused computation."""
        phases = set()
        for own, sub, opcode, _ in comps.get(calls, {}).values():
            if own not in (None, REST) and opcode not in _NO_WORK:
                phases.add(own)
            if sub and depth < 16:
                phases |= held(sub, depth + 1)
        return phases

    index, mixed = {}, set()
    for comp, instrs in comps.items():
        users = collections.defaultdict(list)
        for name, (_, _, _, operands) in instrs.items():
            for operand in operands:
                users[operand].append(name)
        for name in reversed(list(instrs)):
            phase = resolve(comp, name)
            if phase is None and instrs[name][0] is None:
                phase = next((index[u] for u in users[name]
                              if index.get(u, REST) != REST), None)
            index[name] = REST if phase is None else phase
            calls = instrs[name][1]
            if calls and held(calls) - {index[name]}:
                mixed.add(name)
    return index, mixed


def dump_phase_indexes(dump_dir: str) -> dict[str, tuple]:
    """``{module: phase_index}`` of every optimized module dumped as text
    into ``dump_dir``, the newest text of each, as ``trace.dump_indexes``
    reads them."""
    paths = sorted(glob.glob(os.path.join(dump_dir,
                                          "*after_optimizations.txt")),
                   key=os.path.getmtime)
    newest: dict[str, str] = {}
    for path in paths:
        with open(path) as fh:
            text = fh.read()
        name = tr.module_name(text)
        if name:
            newest[name] = text
    return {name: phase_index(text) for name, text in newest.items()}


def _lookup(op, indexes: dict) -> tuple[str, bool] | None:
    """``(phase, mixed)`` of one op event, joined as ``trace.kind_of``
    joins it: its own module's index first, else any that has the name."""
    key = op[0].lstrip("%").split(" ", 1)[0]
    mod = op[3] if len(op) > 3 else ""
    first = [indexes[mod]] if mod in indexes else []
    for index, mixed in first + list(indexes.values()):
        if key in index:
            return index[key], key in mixed
    return None


def by_phase(per_dev, kinds: dict, phases: dict) -> dict:
    """Device ns over the chips, per phase; ``unmatched`` for ops the dump
    does not hold, containers left out (``trace.by_kind``'s rules), and
    ``mixed``, the ns in mixed fusions (counted in their phase too)."""
    out = collections.Counter()
    for ops in per_dev:
        for op in ops:
            kind = tr.kind_of(op, kinds)
            if kind == "container":
                continue
            found = _lookup(op, phases) if kind != "unmatched" else None
            if found is None:
                out["unmatched"] += op[2]
                continue
            out[found[0]] += op[2]
            if found[1]:
                out["mixed"] += op[2]
    return dict(out)


def span_idle_ns(per_dev, spans) -> dict[str, float]:
    """Idle ns per span name, averaged over the chips: every instant of
    ``[first span start, last span end)`` in which a chip ran no op goes
    to the innermost span holding it (the latest to start)."""
    if not spans or not per_dev:
        return {}
    bounds = sorted({s[1] for s in spans} | {s[2] for s in spans})
    segments = []
    for a, b in zip(bounds, bounds[1:]):
        holding = [s for s in spans if s[1] <= a and s[2] >= b]
        if holding:
            inner = max(holding, key=lambda s: (s[1], -s[2]))
            segments.append((a, b, inner[0]))
    idle = collections.Counter()
    for ops in per_dev:
        j = 0
        for ga, gb in tr.idle_gaps(ops, bounds[0], bounds[-1]):
            while j < len(segments) and segments[j][1] <= ga:
                j += 1
            k = j
            while k < len(segments) and segments[k][0] < gb:
                a, b, name = segments[k]
                idle[name] += min(b, gb) - max(a, ga)
                k += 1
    return {name: ns / len(per_dev) for name, ns in idle.items()}


def _trace_files() -> tuple[str, str | None] | None:
    """The dump directory that ``XLA_FLAGS`` names and the newest trace in
    the harness's trace directory beside it (``harness._paths``)."""
    dumps = _DUMP_TO.findall(os.environ.get("XLA_FLAGS", ""))
    if not dumps:
        return None
    hlo = dumps[-1]
    traces = os.path.join(os.path.dirname(os.path.dirname(hlo)), "trace")
    files = glob.glob(os.path.join(traces, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    return hlo, (max(files, key=os.path.getmtime) if files else None)


_READ = "_phases"     # the key under which ``read`` keeps what it read


def read(ctx) -> dict:
    """Everything the phase, span and set-up readers need, read once per
    ``ctx`` (the harness hands every reader the same one, and ``read``
    keeps what it read there), with its summary printed on stderr."""
    if _READ in ctx:
        return ctx[_READ]
    given, ctx = ctx, dict(ctx)
    if not all(k in ctx for k in ("device_ops", "program_spans",
                                  "hlo_dir")):
        found = _trace_files()
        if found is not None:
            ctx.setdefault("hlo_dir", found[0])
            xplane = found[1]
            if xplane is not None and "device_ops" not in ctx:
                ctx["device_ops"] = tr.device_ops(xplane, ctx["chips"])
            if xplane is not None and "program_spans" not in ctx:
                ctx["program_spans"] = tr.host_spans(
                    xplane, SPANS + _harness_spans())
    program = _program_counters()
    if "setup_counters" not in ctx and program is not None:
        ctx["setup_counters"] = program["first_sweep"]
    out = {"phase_ns": None, "span_idle_ns": None,
           "setup_counters": ctx.get("setup_counters")}
    per_dev = ctx.get("device_ops")
    if per_dev and ctx.get("hlo_dir"):
        phases = dump_phase_indexes(ctx["hlo_dir"])
        if any(set(index.values()) - {REST}
               for index, _ in phases.values()):
            out["phase_ns"] = by_phase(
                per_dev, tr.dump_indexes(ctx["hlo_dir"]), phases)
    spans = [s for s in ctx.get("program_spans") or () if s[0] in SPANS]
    if per_dev and spans:
        out["span_idle_ns"] = span_idle_ns(per_dev, spans)
    _report(ctx, out, program)
    given[_READ] = out
    return out


def _harness_spans() -> tuple:
    from bench import harness
    return tuple(harness.SPANS)


def _program_counters() -> dict | None:
    """The program's counters in this process, if it keeps any."""
    mod = sys.modules.get(COUNTERS_MODULE)
    return None if mod is None else mod.counters()


def phase_ns_per_point_tick(ctx, names) -> float | None:
    """Device ns per point-tick, summed over the chips, in ``names``."""
    by = read(ctx)["phase_ns"]
    if by is None or ctx["point_ticks"] <= 0:
        return None
    total = sum(v for k, v in by.items() if k != "mixed")
    if total <= 0 or by.get("unmatched", 0) > UNMATCHED_LIMIT * total:
        return None
    return sum(by.get(k, 0) for k in names) / ctx["point_ticks"]


def idle_share(ctx, names) -> float | None:
    """Idle time inside the spans ``names``, averaged over the chips, as a
    share of the window."""
    idle = read(ctx)["span_idle_ns"]
    if idle is None or ctx["window_s"] <= 0.0:
        return None
    return sum(idle.get(k, 0.0) for k in names) / (ctx["window_s"] * 1e9)


def setup_counter(ctx, name) -> float | None:
    """A program counter as it stood at the end of the set-up dispatch."""
    counters = read(ctx)["setup_counters"]
    return None if counters is None else counters[name]


def _report(ctx, out, program) -> None:
    """The readings behind the metrics, on stderr."""
    err = sys.stderr
    by, pt = out["phase_ns"], ctx.get("point_ticks", 0)
    if by is not None and pt > 0:
        matched = sum(v for k, v in by.items()
                      if k not in ("mixed", "unmatched"))
        per = {k: by.get(k, 0) / pt for k in PHASES + (REST,)}
        kinds = sum(v for k, v in (ctx.get("by_kind_ns") or {}).items()
                    if k != "unmatched")
        print(f"phases: device ns per point-tick {per}; the seven metrics "
              f"sum to {matched / pt!r}, the kinds to {kinds / pt!r}; "
              f"mixed-phase fusions hold "
              f"{by.get('mixed', 0) / max(matched, 1)!r} of matched device "
              f"time; unmatched {by.get('unmatched', 0) / pt!r}", file=err)
    idle = out["span_idle_ns"]
    if idle is not None:
        per_dev, spans = ctx["device_ops"], ctx["program_spans"]
        outer = span_idle_ns(per_dev, [s for s in spans
                                       if s[0] not in SPANS])
        held = sum(idle.get(k, 0.0) for k in INPUT_SPANS + RESULT_SPANS)
        call = outer.get("sweep_call", 0.0)
        print("spans: idle s per program span, averaged over chips "
              f"{_seconds(idle)}; per harness span {_seconds(outer)}; "
              "the input and result spans hold "
              f"{held / call if call else None!r} of sweep_call's",
              file=err)
    if program is not None:
        first, last = program["first_sweep"], program["last_sweep"]
        window = ({k: last[k] - first[k] for k in first}
                  if first and last else None)
        now = {k: v for k, v in program.items() if not isinstance(v, dict)}
        print(f"counters: at the end of set-up {first}; added by the "
              f"window's sweeps {window}; now {now}", file=err)


def _seconds(ns: dict) -> dict:
    return {k: v / 1e9 for k, v in sorted(ns.items())}
