"""Device ns per simulated point-tick, summed over the chips used, in the
scan step's ``control`` phase outside the Kalman filter bank: demand, TTC
confirmation, fair-share rates and AIMD (``controller.step``)."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("control",))
