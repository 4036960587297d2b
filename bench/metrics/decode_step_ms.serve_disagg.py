"""Device milliseconds per decode step: the mean device time of the
`jit_serve_decode` program runs wholly inside the window."""
from bench import spans


def read(r):
    return spans.mean_program_ms(r, "jit_serve_decode")
