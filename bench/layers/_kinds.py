"""Device time of some op kinds per simulated point-tick, summed over the
chips used.  Nothing is read where more than a tenth of the device time
could not be joined with the compiled HLO."""

UNMATCHED_LIMIT = 0.1


def ns_per_point_tick(ctx, kinds):
    by = ctx["by_kind_ns"]
    total = sum(by.values())
    if total <= 0 or ctx["point_ticks"] <= 0:
        return None
    if by.get("unmatched", 0) > UNMATCHED_LIMIT * total:
        return None
    return sum(by.get(k, 0) for k in kinds) / ctx["point_ticks"]
