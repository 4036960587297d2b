"""The serving step's share of the chip's peak over the whole traced window:
model FLOPs of every token processed in it (prompt tokens prefilled, and
each decoded token against the keys before it), over window x peak."""
from bench import work


def read(r):
    if r.peak is None:
        return None
    c = r.counts
    flops = sum(work.prefill_flops(r.dims, int(e.stats["tokens"]))
                for e in r.trace.host_spans("bench.prefill"))
    flops += work.forward_flops(r.dims, c.get("decode_tokens", 0),
                                c.get("decode_keys", 0))
    w = r.trace.window_s
    if flops <= 0 or w <= 0:
        return None
    return 100.0 * flops / (w * r.chips * r.peak["flops"])
