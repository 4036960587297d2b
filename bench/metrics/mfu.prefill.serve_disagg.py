"""Prefill's share of the chip's peak: the FLOPs of the prompts prefilled in
the window (each `bench.prefill` span carries its length) over the device
time of the programs those spans launched, times the peak."""
from bench import readers, work


def read(r):
    if r.peak is None:
        return None
    spans = r.trace.host_spans("bench.prefill")
    secs = readers.launched_modules_s(r, "bench.prefill")
    if not spans or secs <= 0:
        return None
    flops = sum(work.prefill_flops(r.dims, int(e.stats["tokens"]))
                for e in spans)
    return 100.0 * flops / (secs * r.peak["flops"])
