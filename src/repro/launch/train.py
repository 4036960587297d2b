"""Production training launcher.

On a real cluster each host runs this with coordinator env vars set
(JAX_COORDINATOR, JAX_NUM_PROCESSES, JAX_PROCESS_ID) and the production
mesh; in this container it runs a reduced config on the local device(s).

Examples:
  python -m repro.launch.train --arch qwen1.5-0.5b --steps 100 --smoke
  python -m repro.launch.train --arch llama3.2-3b --shape train_4k \
      --mode hierarchical --streams 32 --ckpt-dir /ckpt --replica-dir /backup
  # WAN-routed with chaos: drop the direct link at step 20, self-heal
  python -m repro.launch.train --arch qwen1.5-0.5b --smoke --pods 4 \
      --route amsterdam:tokyo --backup-links --chaos-drop 20
"""
from __future__ import annotations

import argparse
import os

import jax

from repro.configs import (SHAPES, CommConfig, RunConfig, ShapeConfig,
                           TrainConfig, get_config, smoke_config)
from repro.data import DataConfig, make_pipeline
from repro.launch.cache import enable_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.runtime import Trainer


def maybe_init_distributed():
    if os.environ.get("JAX_COORDINATOR"):
        jax.distributed.initialize(
            coordinator_address=os.environ["JAX_COORDINATOR"],
            num_processes=int(os.environ["JAX_NUM_PROCESSES"]),
            process_id=int(os.environ["JAX_PROCESS_ID"]))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", default="train_4k", choices=list(SHAPES))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--mode", default="hierarchical",
                    choices=["flat", "hierarchical", "gateway"])
    ap.add_argument("--streams", type=int, default=32)
    ap.add_argument("--chunk-mb", type=float, default=8.0)
    ap.add_argument("--compress", default="none", choices=["none", "bf16", "int8"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seq-len", type=int, default=None)
    ap.add_argument("--global-batch", type=int, default=None)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--replica-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--smoke", action="store_true",
                    help="reduced model + small shapes for local devices")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pods", type=int, default=1,
                    help="pod axis of the local mesh (4 for CosmoGrid routes)")
    ap.add_argument("--route", default=None, metavar="SRC:DST",
                    help="plan the train path over the CosmoGrid testbed "
                         "(e.g. amsterdam:tokyo); needs a pod axis of 4")
    ap.add_argument("--backup-links", action="store_true",
                    help="add the tokyo-edinburgh backup to the testbed")
    ap.add_argument("--chaos-drop", type=int, default=None, metavar="STEP",
                    help="drop the route's direct link at STEP and attach "
                         "the self-healing ChaosMonitor (re-route/failover)")
    ap.add_argument("--local-steps", type=int, default=1, metavar="K",
                    help="local-SGD cadence: K local steps per site between "
                         "cross-site delta syncs (1 = fully synchronous)")
    ap.add_argument("--coordinator", default=None, metavar="SITE",
                    help="attach elastic membership (lease-based liveness, "
                         "evict/rejoin world resize) coordinated from SITE; "
                         "needs --route")
    ap.add_argument("--lease-steps", type=int, default=4,
                    help="probe failures a suspect site survives before "
                         "eviction (with --coordinator)")
    ap.add_argument("--data", default="synthetic", choices=["synthetic", "binary"])
    ap.add_argument("--data-path", default=None)
    args = ap.parse_args()

    enable_compile_cache()
    maybe_init_distributed()
    cfg = get_config(args.arch)
    if args.smoke:
        cfg = smoke_config(cfg)
    base = SHAPES[args.shape]
    seq = args.seq_len or (64 if args.smoke else base.seq_len)
    gb = args.global_batch or (8 if args.smoke else base.global_batch)
    shape = ShapeConfig(base.name, seq, gb, "train")

    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        n = len(jax.devices())
        model_par = 1
        data_par = n // args.pods
        mesh = make_local_mesh(data=data_par, model=model_par, pod=args.pods)

    route = site_groups = chaos = membership = None
    if args.route:
        from repro.core import ChaosMonitor, SiteMembership, cosmogrid_topology
        src, dst = args.route.split(":")
        topo = cosmogrid_topology(backup_links=args.backup_links)
        if args.chaos_drop is not None:
            direct = topo.link(src, dst)
            if direct is None:
                ap.error(f"--chaos-drop needs a direct {src}-{dst} link")
            topo.connect(src, dst, direct.drop(args.chaos_drop))
            chaos = ChaosMonitor(topo, src, dst)
        if args.coordinator:
            membership = SiteMembership(topo, args.coordinator,
                                        lease_steps=args.lease_steps)
        route = topo.route(src, dst)
        site_groups = topo.pod_groups()
        print(f"[train] WAN route: {route.describe()}"
              + (f"; chaos drop at step {args.chaos_drop}"
                 if args.chaos_drop is not None else "")
              + (f"; membership coordinated by {args.coordinator}"
                 if args.coordinator else ""))
    elif args.coordinator:
        ap.error("--coordinator needs --route (a multi-site topology)")

    rc = RunConfig(
        model=cfg, shape=shape,
        comm=CommConfig(mode=args.mode, streams=args.streams,
                        chunk_mb=args.chunk_mb, compress=args.compress,
                        local_steps=args.local_steps),
        train=TrainConfig(lr=args.lr, total_steps=args.steps,
                          warmup_steps=max(args.steps // 10, 1),
                          microbatches=args.microbatches))
    data = make_pipeline(DataConfig(
        vocab_size=cfg.vocab_size, seq_len=seq, global_batch=gb,
        kind=args.data, path=args.data_path))

    with jax.set_mesh(mesh):
        trainer = Trainer(rc, mesh, ckpt_dir=args.ckpt_dir,
                          replica_dir=args.replica_dir,
                          ckpt_every=args.ckpt_every,
                          route=route, site_groups=site_groups, chaos=chaos,
                          membership=membership)
        print(f"[train] {args.arch} params={cfg.param_count():,} mesh={mesh.shape} "
              f"mode={args.mode} zero={trainer.bundle.zero}"
              + (f" local_steps={args.local_steps}"
                 if args.local_steps > 1 else ""))
        print(f"[train] {trainer.init_or_restore()} at step {trainer.step}")
        hist = trainer.run(data, args.steps)
        print(f"[train] done: loss {hist[0]['loss']:.4f} -> {hist[-1]['loss']:.4f}; "
              f"stragglers flagged: {len(trainer.detector.flagged)}")
        trainer.close()


if __name__ == "__main__":
    main()
