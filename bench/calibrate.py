#!/usr/bin/env python3
"""Readings that the limits and the traffic rates are set from; the
benchmark's own runs never run this.  One process per call, on the chip.

    # open-loop knee sweep: one engine, one window per rate
    python3 bench/calibrate.py sweep --workload W --seed S --seconds 20 --rates 1,2,3
    # serving: the program's served-token gap and each control's, per seed
    python3 bench/calibrate.py serve --workload W --seeds 1,2,3 --seconds 10 --controls int8,fp8

Each reading is one JSON line on standard output, also appended to `--out`.
"""
from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from bench import harness  # noqa: E402
from bench.weights import dims_of  # noqa: E402


def emit(out, rec):
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        os.makedirs(os.path.dirname(out), exist_ok=True)
        with open(out, "a") as f:
            f.write(line + "\n")


def sweep(cell, dm, args):
    from bench import drive_serve
    counter = harness.CompileCounter()
    mesh, eng, probe = drive_serve.setup(cell, dm)
    for rate in [float(x) for x in args.rates.split(",")]:
        mix = copy.deepcopy(cell.mix)
        mix["arrivals"]["rate_per_s"] = rate
        win = drive_serve.window(cell, dm, mesh, eng, probe, counter, mix)
        eng.run_to_completion()
        emit(args.out, {"mode": "sweep", "rate_per_s": rate,
                        **win["metrics"], **win["notes"],
                        "attempted": win["attempted"],
                        "failed": win["failed"], "backlog": win["backlog"],
                        "window_s": win["window_s"],
                        "compiles": win["compiles"],
                        "late_p95": win["late_p95"]})


def serve(cell, dm, devices, args):
    from bench import drive_serve
    for seed in _seeds(args):
        c = copy.copy(cell)
        c.seed, c.t_start = seed, time.time()
        out = drive_serve.run(c, dm, devices,
                              control_modes=tuple(_list(args.controls)))
        emit(args.out, {"mode": "serve", "seed": seed, **out.checks,
                        **{k: v for k, v in out.notes.items()
                           if k.startswith("control") or k.startswith(
                               "sampled")},
                        **out.metrics})
        del out
        gc.collect()


def _list(s):
    return [x for x in (s or "").split(",") if x]


def _seeds(args):
    return [int(x) for x in _list(args.seeds)]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("what", choices=("sweep", "serve"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--rates", default="")
    ap.add_argument("--controls", default="")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    cell = harness.load_cell(ROOT, args.workload)
    cell.seed, cell.seconds, cell.t_start = args.seed, args.seconds, \
        time.time()
    devices = harness.init_jax(ROOT, cell.entry["chips"])[
        :cell.entry["chips"]]
    dm = dims_of(cell.config)
    if args.what == "sweep":
        sweep(cell, dm, args)
    else:
        serve(cell, dm, devices, args)


if __name__ == "__main__":
    main()
