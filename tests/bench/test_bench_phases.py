"""The benchmark's reduction to device time per phase of the scan step,
idle time per host span of ``sweep`` and the set-up's counters
(``bench/phases.py`` and its readers), on hand-built HLO text, ops and
spans, and on a sweep chunk compiled for the CPU."""

import os
import re
import sys

import jax
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, REPO)
from bench import harness, phases, trace as tr  # noqa: E402
from bench.layers import (acquire_ns, billing_ns, control_ns,  # noqa: E402
                          device_idle_share, elementwise_ns, execute_ns,
                          gather_scatter_ns, kalman_ns, market_ns, rest_ns,
                          setup_compile_s, setup_trace_s, sort_ns,
                          sweep_input_idle_share, sweep_result_idle_share)

PHASE_READERS = {"market": market_ns, "billing": billing_ns,
                 "execute": execute_ns, "control": control_ns,
                 "kalman": kalman_ns, "acquire": acquire_ns,
                 "rest": rest_ns}
STEP = "jit(pt)/vmap()/while/body/closed_call"


def _meta(path: str) -> str:
    return f'metadata={{op_name="{path}" stack_frame_id=3}}'


HLO = f"""HloModule jit_pt, entry_computation_layout={{()->f32[8]}}

%fused_take (p0: f32[8], p1: s32[8]) -> f32[8] {{
  %p0 = f32[8]{{0}} parameter(0)
  %p1 = s32[8]{{0}} parameter(1)
  %one = f32[] constant(1), {_meta(STEP)}
  %gather.1 = f32[8]{{0}} gather(%p0, %p1), offset_dims={{}}, {_meta(STEP + "/acquire/gather")}
  ROOT %multiply.2 = f32[8]{{0}} multiply(%gather.1, %gather.1), {_meta(STEP + "/acquire/mul")}
}}

%fused_mixed (q0: f32[8]) -> f32[8] {{
  %q0 = f32[8]{{0}} parameter(0)
  %sine.3 = f32[8]{{0}} sine(%q0), {_meta(STEP + "/market/sin")}
  ROOT %add.4 = f32[8]{{0}} add(%sine.3, %q0), {_meta(STEP + "/billing/add")}
}}

%fused_scatter_root (r0: f32[8], r1: s32[8], r2: f32[8]) -> f32[8] {{
  %r0 = f32[8]{{0}} parameter(0)
  %r1 = s32[8]{{0}} parameter(1)
  %r2 = f32[8]{{0}} parameter(2)
  %reshape.5 = f32[8]{{0}} reshape(%r2), {_meta("acquire/lt")}
  ROOT %scatter.6 = f32[8]{{0}} scatter(%r0, %r1, %reshape.5), update_window_dims={{}}
}}

%fused_zero_scatter (z0: f32[], z1: s32[8], z2: f32[8]) -> f32[8] {{
  %z0 = f32[] parameter(0)
  %broadcast.7 = f32[8]{{0}} broadcast(%z0), dimensions={{}}, {_meta(STEP)}
  %z1 = s32[8]{{0}} parameter(1)
  %z2 = f32[8]{{0}} parameter(2)
  ROOT %fusion.8 = f32[8]{{0}} fusion(%broadcast.7, %z1, %z2), kind=kCustom, calls=%fused_scatter_root, {_meta(STEP)}
}}

%body (b: f32[8]) -> f32[8] {{
  %b = f32[8]{{0}} parameter(0)
  %fusion.10 = f32[8]{{0}} fusion(%b, %b), kind=kLoop, calls=%fused_take, {_meta(STEP + "/acquire/mul")}
  %fusion.11 = f32[8]{{0}} fusion(%b), kind=kLoop, calls=%fused_mixed, {_meta(STEP + "/billing/add")}
  %sort.12 = f32[8]{{0}} sort(%b), dimensions={{0}}, {_meta(STEP + "/acquire/jit(argsort)/sort")}
  %multiply.13 = f32[8]{{0}} multiply(%b, %b), {_meta(STEP + "/control/kalman/mul")}
  %add.14 = f32[8]{{0}} add(%b, %b), {_meta(STEP + "/control/add")}
  %cosine.15 = f32[8]{{0}} cosine(%b), {_meta(STEP + "/jit(market)/vmap(execute)/cos")}
  %add.16 = f32[8]{{0}} add(%b, %b), {_meta("jit(pt)/vmap()/while/body/add")}
  %copy.17 = f32[8]{{0}} copy(%b)
  %exponential.18 = f32[8]{{0}} exponential(%b), {_meta(STEP + "/execute/exp")}
  %fusion.21 = f32[8]{{0}} fusion(%b, %b, %b), kind=kCustom, calls=%fused_zero_scatter, {_meta(STEP)}
  %copy.22 = f32[8]{{0}} copy(%b)
  %multiply.23 = f32[8]{{0}} multiply(%copy.22, %b), {_meta(STEP + "/execute/mul")}
  %sort.24 = f32[8]{{0}} sort(%fusion.10), dimensions={{0}}, {_meta(STEP)}
  %sort.25 = f32[8]{{0}} sort(%fusion.11), dimensions={{0}}, {_meta("jit(pt)/vmap(jit(argsort))/sort")}
  ROOT %negate.19 = f32[8]{{0}} negate(%b), {_meta(STEP + "/summary/neg")}
}}

ENTRY %main (a: f32[8]) -> f32[8] {{
  %a = f32[8]{{0}} parameter(0)
  ROOT %while.20 = f32[8]{{0}} while(%a), condition=%body, body=%body, {_meta("jit(pt)/vmap()/while")}
}}
"""

# Device ns of each op on one chip; a second chip runs the same ops.
DURATIONS = {"fusion.10": 4000, "fusion.11": 700, "sort.12": 200,
             "multiply.13": 30, "add.14": 20, "cosine.15": 11,
             "add.16": 5, "copy.17": 4, "exponential.18": 60,
             "negate.19": 9, "fusion.21": 500, "copy.22": 3,
             "multiply.23": 7, "sort.24": 40, "sort.25": 6,
             "while.20": 99999}


def _ops(durations=DURATIONS, module="jit_pt"):
    ops, at = [], 0
    for name, ns in durations.items():
        start = 0 if name.startswith("while") else at
        ops.append((name, start, ns, module))
        if not name.startswith("while"):
            at += ns + 10
    return ops


@pytest.fixture
def dump(tmp_path):
    (tmp_path / "module_0001.jit_pt.cpu_after_optimizations.txt").write_text(
        HLO)
    return str(tmp_path)


def _ctx(dump_dir, per_dev, point_ticks=10, spans=(), counters=None):
    kinds = tr.dump_indexes(dump_dir)
    by_kind = {}
    for ops in per_dev:
        for k, v in tr.by_kind(ops, kinds).items():
            by_kind[k] = by_kind.get(k, 0) + v
    return {"by_kind_ns": by_kind, "busy_s": 0.5, "window_s": 1.0,
            "point_ticks": point_ticks, "chips": len(per_dev),
            "modules": sorted(kinds), "device_ops": per_dev,
            "program_spans": list(spans), "hlo_dir": dump_dir,
            "setup_counters": counters}


def test_phase_index_takes_the_innermost_phase_by_whole_segments():
    index, mixed = phases.phase_index(HLO)
    assert index["fusion.10"] == "acquire"
    assert index["gather.1"] == "acquire"
    assert index["sort.12"] == "acquire"      # jit(argsort) is no phase
    assert index["multiply.13"] == "kalman"   # kalman inside control
    assert index["add.14"] == "control"
    assert index["cosine.15"] == "rest"       # jit(market), vmap(execute)
    assert index["add.16"] == "rest"          # the loop's own counter
    assert index["copy.17"] == "rest"         # no op_name at all
    assert index["while.20"] == "rest"
    assert index["fusion.11"] == "billing"    # its fused root's phase
    # Named after a hoisted broadcast, its fused scatter without an
    # op_name: the phase of the scatter's updates.
    assert index["fusion.21"] == "acquire"
    assert index["copy.22"] == "execute"      # the compiler's: its user's
    # Named after the step's call that holds the phases: the compiler's,
    # so its operand's phase; code outside every scope stays rest.
    assert index["sort.24"] == "acquire"
    assert index["sort.25"] == "rest"
    # A fused constant or broadcast keeps the scope it was written in:
    # no mixing.
    assert mixed == {"fusion.11"}
    assert set(index) == set(tr.hlo_index(HLO))


def test_phase_readers_split_the_kinds_total_disjointly(dump):
    per_dev = [_ops(), _ops()]
    ctx = _ctx(dump, per_dev)
    got = {k: r.read(ctx) for k, r in PHASE_READERS.items()}
    assert got == pytest.approx({
        "market": 0.0, "billing": 2 * 700 / 10, "execute": 2 * 70 / 10,
        "control": 2 * 20 / 10, "kalman": 2 * 30 / 10,
        "acquire": 2 * 4740 / 10, "rest": 2 * (11 + 5 + 4 + 9 + 6) / 10})
    kinds = sum(r.read(ctx) for r in (gather_scatter_ns, sort_ns,
                                      elementwise_ns))
    assert sum(got.values()) == pytest.approx(kinds, rel=1e-12)
    by = phases.read(ctx)["phase_ns"]
    assert by["mixed"] == 2 * 700


def test_phase_readers_read_nothing_over_a_tenth_unmatched(dump):
    ops = _ops() + [("fusion.99", 90000, 700, "jit_pt")]
    ctx = _ctx(dump, [ops])
    assert phases.read(ctx)["phase_ns"]["unmatched"] == 700
    assert all(r.read(ctx) is None for r in PHASE_READERS.values())
    assert gather_scatter_ns.read(ctx) is None


def test_readers_read_nothing_from_a_program_without_scopes_or_spans(
        tmp_path):
    bare = re.sub(r", metadata=\{[^}]*\}", "", HLO)
    (tmp_path / "module_0001.jit_pt.cpu_after_optimizations.txt"
     ).write_text(bare)
    ctx = _ctx(str(tmp_path), [_ops()])
    for reader in list(PHASE_READERS.values()) + [
            sweep_input_idle_share, sweep_result_idle_share,
            setup_trace_s, setup_compile_s]:
        assert reader.read(ctx) is None
    # The kinds still read as before.
    assert gather_scatter_ns.read(ctx) == pytest.approx(4500 / 10)


def test_span_idle_goes_to_the_innermost_span_averaged_over_chips():
    spans = [("sweep", 0, 100), ("sweep.prepare", 0, 20),
             ("sweep_chunk", 20, 90), ("sweep.launch", 30, 40),
             ("sweep.to_host", 40, 90), ("sweep.assemble", 90, 100)]
    chip0 = [("fusion.1", 10, 5, ""), ("fusion.2", 45, 40, "")]
    chip1 = [("fusion.1", 0, 20, ""), ("fusion.2", 40, 50, "")]
    idle = phases.span_idle_ns([chip0, chip1], spans)
    # chip 0 idles [0,10) [15,20) in prepare, [20,30) in the chunk,
    # [30,40) in launch, [40,45) [85,90) in to_host, [90,100) in
    # assemble; chip 1 only [20,30), [30,40) and [90,100).
    assert idle == pytest.approx({"sweep.prepare": 15 / 2,
                                  "sweep_chunk": 20 / 2,
                                  "sweep.launch": 20 / 2,
                                  "sweep.to_host": 10 / 2,
                                  "sweep.assemble": 20 / 2})
    ctx = {"window_s": 1e-7, "device_ops": [chip0, chip1],
           "program_spans": spans, "hlo_dir": "", "point_ticks": 1,
           "chips": 2, "setup_counters": None}
    assert sweep_input_idle_share.read(ctx) == pytest.approx(
        (7.5 + 10) / 100)
    assert sweep_result_idle_share.read(ctx) == pytest.approx(
        (5 + 10) / 100)


def test_set_up_readers_read_the_counters_at_the_end_of_set_up(dump):
    counters = {"trace_s": 2.5, "compile_s": 0.75, "compiles": 3,
                "callable_misses": 1}
    ctx = _ctx(dump, [_ops()], counters=counters)
    assert setup_trace_s.read(ctx) == 2.5
    assert setup_compile_s.read(ctx) == 0.75


def test_set_up_readers_find_the_program_counters_in_the_process(dump):
    from repro.obs import spans
    from repro.sim import sweep as sweep_lib
    from repro.sim import paper_schedule
    cell = harness.load_cell(REPO, "spot_frontier.suite")
    cfg = harness.sim_config(cell["config"], 4)
    sweep_lib.sweep(sweep_lib.SweepSpec(
        axes=sweep_lib.make_axes(
            [1], [1.5], instances=list(cell["mix"]["grid"]["instances"])),
        workload=paper_schedule(ttc=7500.0, arrival_gap_ticks=1)), cfg)
    first = spans.counters()["first_sweep"]
    ctx = _ctx(dump, [_ops()])
    del ctx["setup_counters"]
    assert setup_trace_s.read(ctx) == first["trace_s"] > 0.0
    assert setup_compile_s.read(ctx) == first["compile_s"]


def test_the_kind_readers_read_the_same_from_a_context_with_new_keys(dump):
    ctx = _ctx(dump, [_ops()])
    old = {k: v for k, v in ctx.items()
           if k not in ("device_ops", "program_spans", "hlo_dir",
                        "setup_counters")}
    for reader in (gather_scatter_ns, sort_ns, elementwise_ns,
                   device_idle_share):
        assert reader.read(ctx) == reader.read(old)
        assert reader.read(ctx) is not None


def test_the_copied_names_are_those_the_program_emits():
    src = os.path.join(REPO, "src", "repro")

    def found(path, pattern):
        with open(os.path.join(src, path)) as fh:
            return set(re.findall(pattern, fh.read()))

    scopes = (found("sim/runner.py", r'named_scope\("([^"]+)"\)')
              | found("core/controller.py", r'named_scope\("([^"]+)"\)'))
    assert scopes == set(phases.PHASES)
    emitted = (found("sim/sweep.py", r'spans\.span\(\s*"([^"]+)"')
               | found("obs/spans.py",
                       r'StepTraceAnnotation\("([^"]+)"'))
    assert emitted == set(phases.SPANS)
    assert set(phases.INPUT_SPANS + phases.RESULT_SPANS) < emitted
    from repro.obs import spans
    counters = spans.counters()
    assert {"trace_s", "compile_s"} <= set(counters)
    assert phases.COUNTERS_MODULE == spans.__name__


def test_the_trace_and_dump_are_found_where_the_harness_puts_them(
        tmp_path, monkeypatch):
    paths = harness._paths(str(tmp_path), "spot_frontier.suite")
    run = os.path.join(paths["trace"], "plugins", "profile", "2026_01_01")
    os.makedirs(run)
    xplane = os.path.join(run, "host.xplane.pb")
    open(xplane, "wb").close()
    monkeypatch.setenv("XLA_FLAGS", "--xla_dump_to=/elsewhere "
                       f"--xla_dump_to={paths['hlo']} --xla_dump_hlo_as_text")
    assert phases._trace_files() == (paths["hlo"], xplane)
    monkeypatch.setenv("XLA_FLAGS", "")
    assert phases._trace_files() is None


def test_a_compiled_sweep_chunk_names_each_phase_of_its_cell():
    """On the CPU: the cell's program at a few points and ticks holds ops
    of every phase its config runs, and the scan's loop body no compute
    op outside a phase but the loop's own counter."""
    from repro.sim import runner
    from repro.sim import sweep as sweep_lib
    cell = harness.load_cell(REPO, "spot_frontier.suite")
    cfg = harness.sim_config(cell["config"], 6)
    workload = harness.program_workload(cell["mix"])
    grid = cell["mix"]["grid"]
    axes = sweep_lib.make_axes([1, 2], list(grid["bid_mults"])[:1],
                               instances=list(grid["instances"]),
                               policies=list(grid["policies"])[:2])
    fn = sweep_lib._sweep_callable(workload, cfg, None)
    hlo = fn.lower(*axes, workload,
                   runner.default_params(cfg)).compile().as_text()
    index, _ = phases.phase_index(hlo)
    assert {"arrivals", "market", "billing", "execute", "control",
            "kalman", "acquire", "summary"} <= set(index.values())

    comps, body, meta = {}, None, {}
    for line in hlo.splitlines():
        m = tr._COMP.match(line)
        if m:
            body = comps.setdefault(m.group(1), [])
            continue
        m = tr._INSTR.match(line)
        if m and body is not None:
            calls = tr._CALLS.search(line)
            path = re.search(r'op_name="([^"]*)"', line)
            body.append((m.group(1), m.group(2),
                         calls.group(1) if calls else None))
            meta[m.group(1)] = (path.group(1) if path else None, line)
    scans = [re.search(r"body=%([\w.\-]+)", line).group(1)
             for name, (path, line) in meta.items()
             if " while(" in line and path and path.endswith("/while")
             and index[name] == "rest"]
    assert len(scans) == 1
    plumbing = {"parameter", "constant", "get-tuple-element", "tuple",
                "bitcast", "copy", "broadcast", "iota"}
    for name, opcode, calls in comps[scans[0]]:
        path = meta[name][0]
        if not path or phases.phase_of_path(path) != "rest":
            continue                        # in a phase, or the compiler's
        if opcode in plumbing or path.endswith("/while/body/add"):
            continue                        # the loop's counter
        assert opcode == "fusion", (name, path)
        held = {op for _, op, _ in comps[calls]}
        assert held <= plumbing, (name, held)
    assert jax.default_backend() == "cpu"
