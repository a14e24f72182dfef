"""Device ns per simulated point-tick, summed over the chips used, in the
scan step's ``market`` phase: the spot-price step (``spot.step``), the slot
price lookup ``prices[cluster.itype]`` and the CUs per slot."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("market",))
