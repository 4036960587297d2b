"""Shared benchmark helpers: WAN link models calibrated to the paper's
endpoints, and a TCP-window-aware throughput model.

The container is CPU-only, so WAN numbers are *modeled* (alpha-beta with
per-stream window caps — the mechanism MPWide exploits) and clearly labeled
as such; multi-device *measured* numbers run real collectives on fake CPU
devices in subprocesses (threads on one host: they validate behaviour and
relative effects, not absolute bandwidth).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
from dataclasses import dataclass


@dataclass(frozen=True)
class WanLink:
    """One paper endpoint pair over 'regular internet'."""
    name: str
    rtt_s: float                 # round-trip time
    capacity_Bps: float          # attainable path capacity
    per_stream_window: float     # effective TCP window per stream (bytes)
    paper_scp: tuple = (None, None)     # MB/s each direction (Table 1)
    paper_mpwide: tuple = (None, None)
    paper_zeromq: tuple = (None, None)


# Calibrated to Table 1: capacity ~= observed MPWide throughput (MPWide
# saturates the attainable path); window chosen so 1 stream ~= scp rate.
TABLE1_LINKS = [
    WanLink("London-Poznan", 24e-3, 70e6 * 1.15, 256 << 10,
            (11, 16), (70, 70), (30, 110)),
    WanLink("Poznan-Gdansk", 10e-3, 115e6 * 1.15, 128 << 10,
            (13, 21), (115, 115), (64, None)),
    WanLink("Poznan-Amsterdam", 18e-3, 55e6 * 1.15, 256 << 10,
            (32, 9.1), (55, 55), None),
]

UCL_HECTOR_RTT = 11e-3           # bloodflow coupling round-trip


def stream_throughput(link: WanLink, streams: int, efficiency: float = 1.0
                      ) -> float:
    """Bytes/s for `streams` parallel windows over one path.

    Each stream is capped at window/RTT (the TCP bandwidth-delay-product
    limit MPWide's multi-stream paths evade); the path is capped at its
    capacity.  `efficiency` models per-tool overhead (scp crypto ~0.7).
    """
    per_stream = link.per_stream_window / link.rtt_s
    return min(link.capacity_Bps, streams * per_stream) * efficiency


def run_multidev(script: str, ndev: int = 8, timeout: int = 600) -> dict:
    """Run a python snippet under N fake CPU devices; it must print one JSON
    line starting with RESULT:."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={ndev}"
    env["JAX_PLATFORMS"] = "cpu"         # fake devices; never take a chip
    env["PYTHONPATH"] = "src"
    out = subprocess.run([sys.executable, "-c", script], env=env, text=True,
                         capture_output=True, timeout=timeout, cwd=_repo_root())
    for line in out.stdout.splitlines():
        if line.startswith("RESULT:"):
            return json.loads(line[len("RESULT:"):])
    raise RuntimeError(f"no RESULT in output:\n{out.stdout[-2000:]}\n{out.stderr[-2000:]}")


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def fmt_mbs(x) -> str:
    return "-" if x is None else f"{x/1e6:.0f}" if x > 1e4 else f"{x:.0f}"
