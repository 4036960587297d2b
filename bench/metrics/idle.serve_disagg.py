"""Device idle share of the traced window: 1 - (union of the intervals in
which a program ran, averaged over the chips) / window."""
from bench import readers


def read(r):
    return readers.idle_pct(r)
