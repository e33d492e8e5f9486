"""Device: share of the traced window in which no operation ran, as the
mean over the cell's chips (1 - mean busy / window; ``trace_reduce``
averages busy time over the chips a cell asks for)."""
from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
