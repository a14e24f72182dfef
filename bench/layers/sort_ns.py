"""Device ns per simulated point-tick, summed over the chips used, in
sort ops, and fusions that hold one (the slot
``argsort`` and the compiler's sorts of scatter indices)."""

from bench.layers._kinds import ns_per_point_tick

KINDS = ("sort",)


def read(ctx):
    return ns_per_point_tick(ctx, KINDS)
