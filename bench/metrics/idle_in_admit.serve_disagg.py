"""Share of the traced window in which the device runs no program while
the host is inside an admission (`serve.admit`), averaged over the chips:
the part of the idle share that the admission path holds."""
from bench import spans


def read(r):
    return spans.idle_under_pct(r, "serve.admit")
