"""Device ns per simulated point-tick, summed over the chips used, in the
scan step's ``acquire`` phase: bids, ``spot.select_type`` and the slot
ordering of ``billing.scale_to`` (``_take``)."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("acquire",))
