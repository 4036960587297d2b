"""Reduction of a profiler trace (`.xplane.pb`) to what the metric readers
need: per device, the executed programs and the leaf operations inside the
traced window; on the host, the spans (the harness's `bench.*` annotations
and the Python tracer's function spans) on the same clock.

Device planes are `/device:TPU:<n>`; their "XLA Modules" line holds one event
per program run and "XLA Ops" one per operation, named by the operation's HLO
text (so shapes can be read from the name).  Times are nanoseconds from the
start of the trace, on the host's and the devices' lines alike.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all", "send", "recv")


@dataclass
class Event:
    name: str
    start: float
    end: float
    stats: dict = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class Device:
    name: str
    modules: list
    ops: list           # leaf operations (no other op inside them)
    async_ops: list     # the "Async XLA Ops" line (collectives in flight)


@dataclass
class Trace:
    window: tuple       # (start, end) ns of the harness's window span
    devices: list
    host: list          # spans on the host's Python thread(s)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def host_spans(self, name: str) -> list:
        return [e for e in self.host if e.name == name]

    def host_sorted(self) -> list:
        if getattr(self, "_sorted", None) is None:
            self._sorted = sorted(self.host, key=lambda e: e.start)
            self._starts = [e.start for e in self._sorted]
        return self._sorted

    def host_starts(self) -> list:
        self.host_sorted()
        return self._starts


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def op_kind(text: str) -> str:
    """The HLO opcode of an operation's text: '%x.1 = f32[..] fusion(...)'
    -> 'fusion'."""
    m = re.match(r"%[^=]+=\s*(\([^=]*?\)|\S+)\s+([\w-]+)\(", text)
    return m.group(2) if m else text.split(" ")[0]


def short_name(text: str) -> str:
    return text.split(" = ")[0].lstrip("%") if text.startswith("%") else text


def is_collective(text: str) -> bool:
    k = op_kind(text)
    return any(k.startswith(c) for c in COLLECTIVES)


def _clip(events, lo, hi):
    out = []
    for e in events:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out.append(Event(e.name, s, t, e.stats))
    return out


def _leaves(events: list) -> list:
    """Operations that contain no other operation of the same line (a
    `while` or `call` op spans its body's ops)."""
    ev = sorted(events, key=lambda e: (e.start, -e.end))
    out = []
    for i, e in enumerate(ev):
        nxt = ev[i + 1] if i + 1 < len(ev) else None
        if nxt is not None and nxt.start < e.end and nxt.end <= e.end:
            continue
        out.append(e)
    return out


def union(intervals) -> list:
    """Merged [(start, end)] of possibly overlapping intervals."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a: list, b: list) -> list:
    """Parts of the merged intervals `a` not covered by merged `b`."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def _named(name: str, stats: dict) -> tuple:
    """An annotation's arguments ride in its name as 'name#k=v,k=v#' where
    the profiler has not already made them stats."""
    if "#" not in name:
        return name, stats
    base, _, rest = name.partition("#")
    for kv in rest.strip("#").split(","):
        k, _, v = kv.partition("=")
        if k:
            stats.setdefault(k, v)
    return base, stats


def _events(line) -> list:
    out = []
    for e in line.events:
        name, stats = _named(e.name, dict(e.stats))
        out.append(Event(name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns), stats))
    return out


def load(path: str, whole: bool = False) -> Trace:
    """Read a trace file; everything is clipped to the harness's window
    span, or with `whole` to the span of all device programs."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    host: list = []
    devs: list = []
    for plane in pd.planes:
        lines = {ln.name: ln for ln in plane.lines}
        if plane.name.startswith("/device:TPU:"):
            devs.append((plane.name, lines))
        elif plane.name == "/host:CPU":
            for name, ln in lines.items():
                if name.startswith("python") or name.startswith("main"):
                    host += [e for e in _events(ln)
                             if e.name.startswith(("bench.", "$"))]
    wins = [e for e in host if e.name == WINDOW_SPAN]
    if whole:
        mods = [e for _, lines in devs if "XLA Modules" in lines
                for e in _events(lines["XLA Modules"])]
        lo, hi = min(e.start for e in mods), max(e.end for e in mods)
    elif wins:
        lo, hi = wins[0].start, wins[0].end
    else:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
    devices = []
    for name, lines in sorted(devs, key=lambda d: int(d[0].rsplit(":", 1)[1])):
        get = lambda n: _events(lines[n]) if n in lines else []
        devices.append(Device(
            name=name, modules=_clip(get("XLA Modules"), lo, hi),
            ops=_clip(_leaves(get("XLA Ops")), lo, hi),
            async_ops=_clip(get("Async XLA Ops"), lo, hi)))
    return Trace(window=(lo, hi), devices=devices,
                 host=_clip(host, lo, hi))


def busy(dev: Device) -> list:
    """Merged intervals in which a program ran on the device."""
    return union((e.start, e.end) for e in dev.modules)


def busy_s(tr: Trace) -> float:
    """Seconds in which a program ran, averaged over the devices."""
    if not tr.devices:
        return 0.0
    return sum(total(busy(d)) for d in tr.devices) / len(tr.devices) / 1e9


def idle_gaps(tr: Trace, dev: Device) -> list:
    lo, hi = tr.window
    return subtract([(lo, hi)], busy(dev))


def host_activity(tr: Trace, t: float, _scan: int = 20000) -> str:
    """What the host was doing at time t: the innermost harness span
    (`bench.*`) and the innermost Python function, e.g.
    'bench.admit > $kvship.py:170 _encode_decode'.  Spans on one thread
    nest, so the innermost covering span is the one that started last."""
    spans = tr.host_sorted()
    i = bisect.bisect_right(tr.host_starts(), t) - 1
    harness = func = None
    for e in spans[max(0, i - _scan):i + 1][::-1]:
        if e.end <= t or e.name == WINDOW_SPAN:
            continue
        if e.name.startswith("bench."):
            harness = harness or e.name
        else:
            func = func or e.name
        if harness and func:
            break
    return " > ".join(x for x in (harness, func) if x) or "host: no span"


def breakdown(tr: Trace, top: int = 10) -> dict:
    """Device operations that took most time, and idle time by what the
    host was doing, each summed over the devices and divided by their
    count; seconds."""
    n = max(len(tr.devices), 1)
    ops: dict = {}
    gaps: dict = {}
    for d in tr.devices:
        for e in d.ops:
            k = f"{op_kind(e.name)}:{short_name(e.name)}"
            ops[k] = ops.get(k, 0.0) + e.dur
        for s, t in idle_gaps(tr, d):
            k = host_activity(tr, (s + t) / 2)
            gaps[k] = gaps.get(k, 0.0) + (t - s)
    rank = lambda m: [[k, v / n / 1e9] for k, v in
                      sorted(m.items(), key=lambda kv: -kv[1])[:top]]
    return {"device_ops": rank(ops), "idle_gaps": rank(gaps)}
