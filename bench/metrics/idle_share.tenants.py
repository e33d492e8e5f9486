"""Device: share of the traced window in which no operation ran on the
chip (1 - union of op intervals / window)."""
from bench.readers import idle_share


def read(ctx):
    return idle_share(ctx)
