"""The benchmark harness: finds a cell's configuration, traffic mix, limits
and metric readers by the names in `BENCHMARK.json`, checks for the chip,
runs the cell's driver and prints the result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Nothing here names a cell, a configuration or a metric: a new one is a new
entry in `BENCHMARK.json` and new files under `bench/configs/`,
`bench/traffic/`, `bench/cells/` and `bench/metrics/`.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import math
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclass
class Cell:
    """Everything one run of one cell needs, found by name."""
    root: str
    name: str
    entry: dict         # the workload's entry in BENCHMARK.json
    spec: dict          # the whole BENCHMARK.json
    config: dict        # the configuration file
    mix: dict           # the traffic file
    limits: dict        # bench/cells/<cell>.json: {"checks": {...}}
    seed: int = 0
    seconds: float = 10.0
    trace: bool = False
    t_start: float = 0.0


@dataclass
class Outcome:
    """What a driver hands back: end-to-end metrics, the numbers compared
    with the reference, the counts of its traced window and the trace."""
    metrics: dict
    checks: dict                     # name -> number compared
    attempted: int
    failed: int
    memory_peak_bytes: int
    counts: dict = field(default_factory=dict)
    trace_dir: str = ""
    notes: dict = field(default_factory=dict)


def process_start() -> float:
    """time.time() at which this process started (Linux), else now."""
    try:
        with open("/proc/self/stat") as f:
            ticks = float(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            up = float(f.read().split()[0])
        return time.time() - up + ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(root: str, name: str) -> Cell:
    spec = _json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                       f"{[w['name'] for w in spec['workloads']]}")
    cfg = next(c for c in spec["configs"] if c["name"] == entry["config"])
    here = os.path.join(root, "bench")
    limits_path = os.path.join(here, "cells", f"{name}.json")
    return Cell(root=root, name=name, entry=entry, spec=spec,
                config=_json(os.path.join(root, cfg["file"])),
                mix=_json(os.path.join(here, "traffic",
                                       f"{entry['traffic']}.json")),
                limits=_json(limits_path) if os.path.exists(limits_path)
                else {"checks": {}})


def end_to_end_names(cell: Cell) -> list:
    return [m["name"] for m in cell.spec["end_to_end"]
            if cell.name in m.get("workloads", [cell.name])]


def per_layer_entries(cell: Cell) -> list:
    e2e = set(end_to_end_names(cell))
    return [m for m in cell.spec["per_layer"]
            if cell.name in m.get("workloads", [cell.name] if m["moves"] in e2e
                                  else [])]


def reader(root: str, metric: str):
    """The `read` function of bench/metrics/<metric>.py."""
    path = os.path.join(root, "bench", "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cache_dir(root: str) -> str:
    """JAX's persistent compile cache: $JAX_COMPILATION_CACHE_DIR when set,
    else a fixed directory inside the checkout (the path is part of each
    entry's key, so it never moves)."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(root, ".bench_cache", "jax"))


def init_jax(root: str, chips: int, require_tpu: bool = True):
    """Turn on the compile cache and check the devices; returns them."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    jax.config.update("jax_compilation_cache_dir", cache_dir(root))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise NoChip(f"JAX found no TPU (platform {devs[0].platform!r})")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


class CompileCounter:
    """Counts XLA compilations and persistent-cache hits as they happen."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_hits += 1

    def total(self) -> int:
        return self.compiles + self.cache_hits


@contextlib.contextmanager
def span(name: str, **stats):
    """A host span in the profiler's trace (free when no trace is on)."""
    import jax
    with jax.profiler.TraceAnnotation(name, **stats):
        yield


class TracedTail:
    """With `--trace 1`, the profiler over the last `trace_seconds` of the
    window, inside a `bench.window` span; `on` says whether it runs."""

    def __init__(self, cell: Cell):
        self.cell = cell
        self.start_at = (float(cell.seconds)
                         - float(cell.mix.get("trace_seconds", 3.0)))
        self.dir = ""
        self.on = False
        self._span = None

    def tick(self, now: float) -> None:
        """Start the profiler once the window reaches its traced tail."""
        import jax
        if self.cell.trace and not self.dir and now >= self.start_at:
            self.dir = os.path.join(self.cell.root, "bench_out", "trace",
                                    self.cell.name)
            shutil.rmtree(self.dir, ignore_errors=True)
            jax.profiler.start_trace(self.dir)
            self._span = span("bench.window")
            self._span.__enter__()
            self.on = True

    def stop(self) -> None:
        import jax
        if self.on:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.on = False


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def judge(cell: Cell, numbers: dict) -> tuple:
    """(correct, [(name, value, limit)]): every number at or under its
    limit.  A number without a limit, or that is not finite, fails."""
    limits = cell.limits.get("checks", {})
    rows = []
    ok = bool(numbers)
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        good = (lim is not None and value is not None
                and math.isfinite(value) and value <= lim)
        ok = ok and good
        rows.append((name, value, lim))
    return ok, rows


def run_cell(cell: Cell, devices, driver) -> dict:
    """Run the driver and build the result line."""
    from bench import peaks, readers
    from bench import trace as T
    from bench.weights import dims_of
    dm = dims_of(cell.config)
    kind = devices[0].device_kind
    peak = peaks.peak_of(kind) if devices[0].platform == "tpu" else None
    out: Outcome = driver(cell, dm, devices)
    correct, rows = judge(cell, out.checks)
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out.memory_peak_bytes}
    result = {"correct": correct, "attempted": out.attempted,
              "failed": out.failed}
    if cell.trace:
        tr = T.load(T.find_xplane(out.trace_dir))
        used = tr.devices[:cell.entry["chips"]]
        tr.devices = used
        device["busy_s"] = T.busy_s(tr)
        device["window_s"] = tr.window_s
        r = readers.Reading(trace=tr, dims=dm, peak=peak,
                            chips=cell.entry["chips"], counts=out.counts)
        metrics = {}
        for m in per_layer_entries(cell):
            v = reader(cell.root, m["name"])(r)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["metrics"] = metrics
        result["breakdown"] = T.breakdown(tr)
    else:
        units = {m["name"]: m["unit"] for m in cell.spec["end_to_end"]}
        result["metrics"] = {n: {"value": out.metrics[n], "unit": units[n]}
                             for n in end_to_end_names(cell)
                             if n in out.metrics}
    result["device"] = device
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in rows}
    for k, v in out.notes.items():
        print(f"[bench] {k} {v}", file=sys.stderr)
    for n, v, lim in rows:
        print(f"check {n} {v} limit {lim}", file=sys.stderr)
    sys.stderr.flush()
    return result


def parse(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def driver_for(cell: Cell):
    kind = cell.mix["kind"]
    mod = importlib.import_module(f"bench.drive_{kind}")
    return mod.run


def main(argv, t_start: float, root: str) -> int:
    args = parse(argv)
    cell = load_cell(root, args.workload)
    cell.seed, cell.seconds, cell.trace = args.seed, args.seconds, \
        bool(args.trace)
    cell.t_start = t_start
    try:
        devices = init_jax(root, cell.entry["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    devices = devices[:cell.entry["chips"]]
    result = run_cell(cell, devices, driver_for(cell))
    print(json.dumps(result), flush=True)
    return 0
