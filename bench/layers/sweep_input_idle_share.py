"""Share of the traced window in which a chip ran no op while the host was
inside ``sweep``'s input spans: checks and set-up of the call, the lookup
of its compiled callable, slicing and padding a chunk, and the launch;
averaged over the chips used."""

from bench.phases import INPUT_SPANS, idle_share


def read(ctx):
    return idle_share(ctx, INPUT_SPANS)
