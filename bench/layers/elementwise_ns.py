"""Device ns per simulated point-tick, summed over the chips used, in
every other op: the elementwise market, execution,
Kalman, fair-share and AIMD arithmetic, and custom calls."""

from bench.layers._kinds import ns_per_point_tick

KINDS = ("other", "kernel")


def read(ctx):
    return ns_per_point_tick(ctx, KINDS)
