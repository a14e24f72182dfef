"""Device ns per simulated point-tick, summed over the chips used, in the
Kalman filter bank's update (``kalman.step``, the jnp path or the Pallas
``kalman_update`` kernel), scoped ``kalman`` inside ``control``."""

from bench.phases import phase_ns_per_point_tick


def read(ctx):
    return phase_ns_per_point_tick(ctx, ("kalman",))
