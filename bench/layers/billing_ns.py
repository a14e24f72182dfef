"""Device ns per simulated point-tick, summed over the chips used, in the
scan step's ``billing`` phase: market preemption (``billing.preempt``) and
the wall clock of boots and billing quanta (``billing.advance``)."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("billing",))
