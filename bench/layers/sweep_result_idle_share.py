"""Share of the traced window in which a chip ran no op while the host was
inside ``sweep``'s result spans: each chunk's summaries brought to the
host, then concatenated and put back on the first device; averaged over
the chips used."""

from bench.phases import RESULT_SPANS, idle_share


def read(ctx):
    return idle_share(ctx, RESULT_SPANS)
