"""Device ns per simulated point-tick, summed over the chips used, in
gather and scatter ops, and fusions that hold one
(``billing._take`` and the slot price lookup)."""

from bench.layers._kinds import ns_per_point_tick

KINDS = ("gather", "scatter")


def read(ctx):
    return ns_per_point_tick(ctx, KINDS)
