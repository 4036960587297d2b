"""Share of its roofline that the Pallas flash-attention forward reaches:
the least time of every call in the window (causal FLOPs at the bf16 peak,
or its HBM bytes at the HBM bandwidth, whichever is longer) over the calls'
device time."""
from bench import readers


def read(r):
    return readers.roofline_pct(r, ("flash_fwd",))
