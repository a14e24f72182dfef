"""Device ns per simulated point-tick, summed over the chips used, in every
op of the program outside the six phases with metrics of their own: the
``arrivals``, ``faults``, ``summary`` and ``obs`` phases, and what no phase
holds (the loop, the initial state, the summary of a run)."""

from bench.phases import REST_PHASES, phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, REST_PHASES)
