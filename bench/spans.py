"""The program's own profiler spans (`serve.*`, `kvship.*`) and named
programs, for the per-layer readers in `bench/metrics/`.

`trace.load` keeps, on the host, only the harness's `bench.*` spans and the
Python tracer's.  This module reads the program's spans from the same trace
file: the newest `.xplane.pb` under `bench_out/trace/` whose `bench.window`
span is the reading's window.  It keeps them unclipped wherever they overlap
the window, so that a reader can leave out a span cut by the window's edge.
A program that writes no such span (one older than its spans) reads as an
empty list, and every reader here then returns None.
"""
from __future__ import annotations

import glob
import os

from bench import trace as T

PREFIXES = ("serve.", "kvship.")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_last: tuple = (None, [])     # (window, spans) of the last trace read


def load(path: str) -> tuple:
    """(window, spans) of a trace file: the harness's window span as
    `trace.load` takes it (None where there is none), and every program
    span on the host's Python thread(s)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    wins: list = []
    spans: list = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for ln in plane.lines:
            if not ln.name.startswith(("python", "main")):
                continue
            for e in ln.events:
                if not e.name.startswith(PREFIXES + (T.WINDOW_SPAN,)):
                    continue
                name, stats = T._named(e.name, dict(e.stats))
                ev = T.Event(name, float(e.start_ns),
                             float(e.start_ns + e.duration_ns), stats)
                if name == T.WINDOW_SPAN:
                    wins.append(ev)
                elif name.startswith(PREFIXES):
                    spans.append(ev)
    win = (wins[0].start, wins[0].end) if wins else None
    return win, spans


def overlapping(spans: list, window: tuple) -> list:
    lo, hi = window
    return [e for e in spans if e.end > lo and e.start < hi]


def of(r, root: str = None) -> list:
    """The program spans that overlap the reading's window, unclipped, from
    the trace file under `root` (the checkout) that holds that window."""
    global _last
    window = r.trace.window
    if _last[0] != window:
        files = glob.glob(os.path.join(root or ROOT, "bench_out", "trace",
                                       "**", "*.xplane.pb"), recursive=True)
        found: list = []
        for path in sorted(files, key=os.path.getmtime, reverse=True):
            win, spans = load(path)
            if win == window:
                found = overlapping(spans, window)
                break
        _last = (window, found)
    return _last[1]


def inside(r, name: str, spans=None) -> list:
    """The `name` spans that lie wholly inside the window; one cut by the
    window's edge is left out, as its time is not all there."""
    lo, hi = r.trace.window
    spans = of(r) if spans is None else spans
    return [e for e in spans
            if e.name == name and lo <= e.start and e.end <= hi]


def mean_ms(r, name: str, spans=None):
    """Mean milliseconds of the `name` spans wholly inside the window."""
    got = inside(r, name, spans)
    return sum(e.dur for e in got) / len(got) / 1e6 if got else None


def child_ms_per_parent(r, parent: str, child: str, spans=None):
    """Mean, over the `parent` spans wholly inside the window, of the
    milliseconds of the `child` spans nested in each (one host thread, so
    a span inside another's interval is its descendant)."""
    spans = of(r) if spans is None else spans
    parents = inside(r, parent, spans)
    if not parents:
        return None
    kids = [e for e in spans if e.name == child]
    ns = sum(k.dur for p in parents for k in kids
             if p.start <= k.start and k.end <= p.end)
    return ns / len(parents) / 1e6


def idle_under_pct(r, name: str, spans=None):
    """Share of the window, in percent and averaged over the devices, in
    which the device runs no program while the host is inside a `name`
    span (clipped to the window)."""
    lo, hi = r.trace.window
    spans = of(r) if spans is None else spans
    under = T.union((max(e.start, lo), min(e.end, hi)) for e in spans
                    if e.name == name and e.end > lo and e.start < hi)
    if not under or not r.trace.devices or hi <= lo:
        return None
    idle = sum(T.total(T.subtract(under, T.busy(d)))
               for d in r.trace.devices)
    return 100.0 * idle / len(r.trace.devices) / (hi - lo)


def mean_program_ms(r, name: str):
    """Mean device milliseconds of the runs of program `name` (an "XLA
    Modules" event reads 'jit_serve_decode(1234)') wholly inside the window,
    over all devices.  `trace.load` clips programs to the window, so a run
    that touches its edge is left out."""
    lo, hi = r.trace.window
    durs = [m.dur for d in r.trace.devices for m in d.modules
            if m.name.split("(")[0] == name and lo < m.start and m.end < hi]
    return sum(durs) / len(durs) / 1e6 if durs else None
