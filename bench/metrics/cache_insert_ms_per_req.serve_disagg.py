"""Host milliseconds per request spent inserting its KV into the decode
cache: the mean of the engine's `serve.cache_insert` spans wholly inside
the window (the KV copied back to the device and the cache update)."""
from bench import spans


def read(r):
    return spans.mean_ms(r, "serve.cache_insert")
