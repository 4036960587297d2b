"""Shared arithmetic of the per-layer metric readers in `bench/metrics/`.

A reader gets a `Reading`: the reduced trace of the traced window, the
model's sizes, the chip's peaks and what the cell's driver counted inside
the same window.  It returns a number, or None when the window holds
nothing for it to read; a share of a roofline or a peak is never 0 for
want of data.
"""
from __future__ import annotations

import bisect
from dataclasses import dataclass, field

from bench import trace as T
from bench import work as W
from bench.weights import Dims


@dataclass
class Reading:
    trace: T.Trace
    dims: Dims
    peak: dict
    chips: int
    counts: dict = field(default_factory=dict)


def roofline_pct(r: Reading, kernels: tuple):
    """Least time of every call of `kernels` in the window over their
    device time, in percent, over all devices."""
    if r.peak is None:
        return None
    least = spent = 0.0
    for d in r.trace.devices:
        for e in d.ops:
            k = W.kernel_of(e.name)
            if k in kernels:
                least += W.least_seconds(*W.KERNEL_WORK[k](e.name), r.peak)
                spent += e.dur / 1e9
    return 100.0 * least / spent if spent > 0 else None


def idle_pct(r: Reading):
    w = r.trace.window_s
    return 100.0 * (1.0 - T.busy_s(r.trace) / w) if w > 0 else None


def launched_modules_s(r: Reading, span: str) -> float:
    """Device seconds of the programs that host spans `span` launched: for
    each span, the first program to start on each device after the span
    began (a device runs its programs in the order they were launched, and
    the serving engine has synchronised before each such span), summed."""
    out = 0.0
    for d in r.trace.devices:
        mods = sorted(d.modules, key=lambda m: m.start)
        starts = [m.start for m in mods]
        for e in r.trace.host_spans(span):
            i = bisect.bisect_left(starts, e.start)
            if i < len(mods):
                out += mods[i].dur / 1e9
    return out
