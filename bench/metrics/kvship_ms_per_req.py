"""Host milliseconds per KV ship: the mean of the harness's `bench.ship_kv`
spans around the engine's call into `core.kvship.ship_kv` (encode, wire
accounting and decode of one request's prefilled KV)."""


def read(r):
    spans = r.trace.host_spans("bench.ship_kv")
    if not spans:
        return None
    return sum(e.dur for e in spans) / len(spans) / 1e6
