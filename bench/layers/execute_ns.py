"""Device ns per simulated point-tick, summed over the chips used, in the
scan step's ``execute`` phase: task execution at last instant's rates
(``runner._execute``), completions and the SLA clock."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("execute",))
