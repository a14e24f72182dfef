"""Seconds the set-up spent in XLA's backend compile, the persistent
compilation cache's lookup and load included, by the program's own
counter (``obs.spans``), as it stood when the set-up dispatch returned."""

from bench.phases import setup_counter


def read(ctx):
    return setup_counter(ctx, "compile_s")
