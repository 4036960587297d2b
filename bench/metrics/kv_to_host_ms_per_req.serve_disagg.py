"""Host milliseconds per request spent bringing the prefilled KV to the
host: the mean of the engine's `serve.kv_to_host` spans wholly inside the
window (the wait for the prefill program, then the copy)."""
from bench import spans


def read(r):
    return spans.mean_ms(r, "serve.kv_to_host")
