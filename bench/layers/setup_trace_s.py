"""Seconds the set-up spent tracing functions to jaxprs and lowering them
to MLIR, by the program's own counter (``obs.spans``), as it stood when the
set-up dispatch returned."""

from bench.phases import setup_counter


def read(ctx):
    return setup_counter(ctx, "trace_s")
