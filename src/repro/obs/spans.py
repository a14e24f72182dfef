"""Host spans and compile counters of the sweep executor.

Spans: ``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` that
also keeps its own duration (``.seconds`` once it has closed), and
``chunk(i)`` marks one micro-batch of a sweep as a profiler step.  Both
write into the profiler's own trace, so a span lies on the device trace's
clock; with no profiler running an annotation records nothing.  The
executor's span names, outermost first:

  * ``sweep`` — one ``sim.sweep`` call; inside it ``sweep.prepare``
    (checks, params, schedule, fault spec, mesh), ``sweep.lookup`` (the
    cached compiled callable), then on the chunked path one
    ``sweep_chunk`` step per chunk holding ``sweep.slice_pad``,
    ``sweep.launch``, ``sweep.to_host`` and, when streaming,
    ``sweep.write``; last ``sweep.assemble`` (the chunks concatenated
    and put back on the device).  The one-device fast path has
    ``sweep.launch`` alone after the lookup.  A profiled sweep adds
    ``sweep.compile`` around its ahead-of-time compile.

Counters, kept in memory for the life of the process (``counters()``):

  * ``trace_s`` — seconds spent tracing functions to jaxprs and lowering
    them to MLIR;
  * ``compile_s`` — seconds in XLA's backend compile, which includes the
    persistent compilation cache's lookup and load;
  * ``compiles`` — the number of backend compiles (or cache loads);
  * ``callable_misses`` — compiled sweep callables built anew
    (``sim.sweep._sweep_callable`` cache misses);
  * ``sweeps`` — ``sim.sweep`` calls completed, and ``first_sweep`` /
    ``last_sweep``: the four counters above as they stood when the
    process's first and latest sweep returned (``None`` before one).

JAX reports each trace, lowering and compile through ``jax.monitoring``
as it ends, and an inner function traced while an outer one is being
traced reports inside the outer's time.  The listeners count each second
once: an event's time less the time of the events nested in it.

Like the rest of ``repro.obs`` this module imports nothing from
``repro.sim`` or ``repro.core``.
"""

from __future__ import annotations

import threading
import time

import jax

__all__ = ["span", "chunk", "counters", "count_callable_miss", "sweep_done"]

# jax.monitoring event names (``jax._src.dispatch``).
_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_COMPILE = "/jax/core/compile/backend_compile_duration"
_COUNTER_OF = {_TRACE: "trace_s", _LOWER: "trace_s", _COMPILE: "compile_s"}
_TOTALS = ("trace_s", "compile_s", "compiles", "callable_misses")


class span:
    """A named host span in the profiler's trace; ``args`` are the counts
    at this boundary (rows, chunk index).  ``seconds`` is its duration on
    the host clock once it has closed."""

    __slots__ = ("name", "args", "seconds", "_annotation", "_t0")

    def __init__(self, name: str, **args):
        self.name, self.args, self.seconds = name, args, 0.0

    def __enter__(self) -> "span":
        self._annotation = jax.profiler.TraceAnnotation(self.name,
                                                        **self.args)
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.seconds = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)


def chunk(i: int) -> jax.profiler.StepTraceAnnotation:
    """Chunk ``i`` of a sweep as a profiler step."""
    return jax.profiler.StepTraceAnnotation("sweep_chunk", step_num=int(i))


_lock = threading.Lock()
_counts: dict = {"trace_s": 0.0, "compile_s": 0.0, "compiles": 0,
                 "callable_misses": 0, "sweeps": 0, "first_sweep": None,
                 "last_sweep": None}
# Per thread, the monitored events still open: [event, seconds of the
# events that ended inside it].
_open = threading.local()


def _stack() -> list:
    if not hasattr(_open, "stack"):
        _open.stack = []
    return _open.stack


def _on_start(event: str, value, **kwargs) -> None:
    # JAX records a scalar under the event's own name as the event starts.
    if event in _COUNTER_OF:
        _stack().append([event, 0.0])


def _on_end(event: str, seconds: float, **kwargs) -> None:
    name = _COUNTER_OF.get(event)
    if name is None:
        return
    stack = _stack()
    nested = stack.pop()[1] if stack and stack[-1][0] == event else 0.0
    if stack:
        stack[-1][1] += seconds
    with _lock:
        _counts[name] += max(seconds - nested, 0.0)
        if event == _COMPILE:
            _counts["compiles"] += 1


jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_end)


def count_callable_miss() -> None:
    with _lock:
        _counts["callable_misses"] += 1


def sweep_done() -> None:
    """Note that a sweep returned: ``first_sweep``/``last_sweep``."""
    with _lock:
        snap = {k: _counts[k] for k in _TOTALS}
        _counts["sweeps"] += 1
        if _counts["first_sweep"] is None:
            _counts["first_sweep"] = snap
        _counts["last_sweep"] = snap


def counters() -> dict:
    """A snapshot of the counters (see the module doc)."""
    with _lock:
        return {k: (dict(v) if isinstance(v, dict) else v)
                for k, v in _counts.items()}
