"""Host milliseconds per admission: the mean of the engine's `serve.admit`
spans wholly inside the window (prefill, the KV to the host, its ship, the
cache insert and the first-token read of one request)."""
from bench import spans


def read(r):
    return spans.mean_ms(r, "serve.admit")
