import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))
os.environ["JAX_PLATFORMS"] = "cpu"     # fake devices; never take a chip
# ^ MUST precede any jax import: jax locks the device count on first init.
# (This also means: no `from __future__ import annotations` in this module.)

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this jit-lowers the real train_step / serve_step / prefill
with ShapeDtypeStruct inputs (no allocation), compiles for the production
mesh, prints memory_analysis() (proves it fits) and cost_analysis() (FLOPs /
bytes for the roofline), parses collective bytes out of the compiled HLO,
and appends everything to a JSON results file consumed by
benchmarks/roofline_report.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch llama3.2-3b --shape train_4k --mesh single
  python -m repro.launch.dryrun --all [--mesh both] [--jobs 4]   # full matrix
"""


import argparse
import json
import subprocess
import sys
import time
import traceback

import jax

from repro.configs import (SHAPES, CommConfig, RunConfig, TrainConfig,
                           cell_applicable, get_config, list_archs)
from repro.launch import roofline as rl
from repro.launch.mesh import make_production_mesh
from repro.models.param import tree_abstract
from repro.models.registry import batch_abstract


def run_cell(arch: str, shape_name: str, multi_pod: bool, comm: CommConfig,
             train: TrainConfig, verbose: bool = True) -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = cell_applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "why": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rc = RunConfig(model=cfg, shape=shape, comm=comm, train=train)
    t0 = time.time()
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            from repro.runtime.step import build_train_step
            bundle = build_train_step(rc, mesh)
            state = bundle.abstract_state()
            batch = batch_abstract(cfg, shape)
            lowered = bundle.fn.lower(state, batch)
        elif shape.kind == "prefill":
            from repro.runtime.step import build_serve_step
            bundle = build_serve_step(rc, mesh, kind="prefill")
            params = tree_abstract(bundle.param_defs)
            batch = batch_abstract(cfg, shape)
            lowered = bundle.fn.lower(params, batch)
        else:  # decode
            from repro.runtime.step import build_serve_step
            import jax.numpy as jnp
            bundle = build_serve_step(rc, mesh, kind="decode")
            params = tree_abstract(bundle.param_defs)
            cache = tree_abstract(bundle.cache_defs)
            pos = jax.ShapeDtypeStruct((), jnp.int32)
            tokens = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
            lowered = bundle.fn.lower(params, cache, pos, tokens)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    from repro.launch.hlo_analysis import xla_cost
    cost = xla_cost(compiled)
    hlo = compiled.as_text()
    # scan-aware analysis: XLA's cost_analysis counts while bodies once, so
    # layer-scanned models are undercounted ~L×; hlo_analysis multiplies by
    # parsed trip counts (see launch/hlo_analysis.py).
    hc = rl.analyze_hlo(hlo)
    chips = mesh.devices.size
    roof = rl.Roofline(
        flops=hc.flops,
        hbm_bytes=hc.bytes,
        coll=rl.CollectiveStats(ici_bytes=hc.coll_ici,
                                interpod_bytes=hc.coll_cross,
                                by_kind=hc.coll_by_kind,
                                n_ops=hc.n_coll_ops),
        chips=chips,
        model_flops=rl.model_flops_for(cfg, shape))
    xla_flops = float(cost.get("flops", 0.0))

    per_dev_bytes = (mem.argument_size_in_bytes + mem.output_size_in_bytes
                     - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "chips": chips,
        "status": "ok",
        "comm_mode": comm.mode, "streams": comm.streams,
        "chunk_mb": comm.chunk_mb, "compress": comm.compress,
        "zero1": train.zero1, "microbatches": train.microbatches,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "per_device_total": per_dev_bytes,
        },
        "xla_flops_while_once": xla_flops,
        "roofline": roof.to_dict(),
    }
    if verbose:
        print(f"[{arch} × {shape_name} × {rec['mesh']}] compile ok "
              f"({t_compile:.0f}s)")
        print(f"  memory_analysis: args={mem.argument_size_in_bytes/2**30:.2f}GiB "
              f"temp={mem.temp_size_in_bytes/2**30:.2f}GiB "
              f"out={mem.output_size_in_bytes/2**30:.2f}GiB (per device)")
        print(f"  cost_analysis: flops/chip={roof.flops:.3e} "
              f"hbm_bytes/chip={roof.hbm_bytes:.3e}")
        print(f"  collectives: ici={roof.coll.ici_bytes/2**20:.1f}MiB "
              f"interpod={roof.coll.interpod_bytes/2**20:.1f}MiB "
              f"ops={roof.coll.n_ops}")
        print(f"  roofline: compute={roof.compute_s*1e3:.2f}ms "
              f"memory={roof.memory_s*1e3:.2f}ms "
              f"collective={roof.collective_s*1e3:.2f}ms "
              f"dominant={roof.dominant} "
              f"useful_flops={roof.useful_flops_frac:.2%}")
    return rec


def append_result(path: str, rec: dict):
    data = []
    if os.path.exists(path):
        with open(path) as f:
            data = json.load(f)
    key = (rec["arch"], rec["shape"], rec["mesh"], rec.get("comm_mode"),
           rec.get("compress"), rec.get("streams"), rec.get("microbatches"))
    data = [r for r in data if (r["arch"], r["shape"], r["mesh"],
                                r.get("comm_mode"), r.get("compress"),
                                r.get("streams"), r.get("microbatches")) != key]
    data.append(rec)
    with open(path, "w") as f:
        json.dump(data, f, indent=1)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES) + [None])
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--mode", default="hierarchical",
                    choices=["flat", "hierarchical", "gateway"])
    ap.add_argument("--streams", type=int, default=32)
    ap.add_argument("--chunk-mb", type=float, default=8.0)
    ap.add_argument("--compress", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--no-autotune", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--no-zero", action="store_true")
    ap.add_argument("--all", action="store_true", help="full cell matrix (subprocess per cell)")
    ap.add_argument("--jobs", type=int, default=3)
    ap.add_argument("--out", default="results/dryrun.json")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    if args.all:
        return run_matrix(args)

    comm = CommConfig(mode=args.mode, streams=args.streams,
                      chunk_mb=args.chunk_mb, compress=args.compress,
                      autotune=not args.no_autotune)
    train = TrainConfig(zero1=not args.no_zero, microbatches=args.microbatches)
    archs = [args.arch] if args.arch else list_archs()
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    failures = 0
    for arch in archs:
        for shape in shapes:
            for m in meshes:
                try:
                    rec = run_cell(arch, shape, m == "multi", comm, train)
                except Exception as e:  # noqa: BLE001
                    traceback.print_exc()
                    rec = {"arch": arch, "shape": shape, "mesh": m,
                           "status": "error", "error": f"{type(e).__name__}: {e}"}
                    failures += 1
                append_result(args.out, rec)
    sys.exit(1 if failures else 0)


def run_matrix(args):
    """Full matrix, one subprocess per cell (isolates compiles, bounds RAM)."""
    cells = []
    for arch in list_archs():
        for shape in SHAPES:
            for m in (["single", "multi"] if args.mesh == "both" else [args.mesh]):
                cells.append((arch, shape, m))
    procs: list[tuple] = []
    failures = []
    done = 0

    def launch(cell):
        arch, shape, m = cell
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape, "--mesh", m,
               "--mode", args.mode, "--streams", str(args.streams),
               "--chunk-mb", str(args.chunk_mb), "--compress", args.compress,
               "--microbatches", str(args.microbatches), "--out", args.out]
        if args.no_zero:
            cmd.append("--no-zero")
        if args.no_autotune:
            cmd.append("--no-autotune")
        return subprocess.Popen(cmd, stdout=subprocess.PIPE,
                                stderr=subprocess.STDOUT, text=True)

    queue = list(cells)
    while queue or procs:
        while queue and len(procs) < args.jobs:
            cell = queue.pop(0)
            procs.append((cell, launch(cell), time.time()))
        still = []
        for cell, p, t0 in procs:
            if p.poll() is None:
                if time.time() - t0 > args.timeout:
                    p.kill()
                    failures.append((cell, "timeout"))
                    print(f"TIMEOUT {cell}")
                else:
                    still.append((cell, p, t0))
                continue
            done += 1
            out = p.stdout.read() if p.stdout else ""
            tail = [ln for ln in out.splitlines() if ln.strip()][-6:]
            print(f"--- [{done}/{len(cells)}] {cell} rc={p.returncode}")
            print("\n".join("    " + ln for ln in tail))
            if p.returncode != 0:
                failures.append((cell, out[-2000:]))
        procs = still
        time.sleep(2)
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells ok")
    for cell, err in failures:
        print("FAILED:", cell)
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
