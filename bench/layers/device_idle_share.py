"""Share of the traced window in which the device ran no op: the sweep
facade and the host (per-dispatch axes, padding, transfers, the result
brought to the host), averaged over the chips used."""


def read(ctx):
    if ctx["window_s"] <= 0.0 or ctx["busy_s"] <= 0.0:
        return None
    return 1.0 - ctx["busy_s"] / ctx["window_s"]
