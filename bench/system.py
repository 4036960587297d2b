"""The system under test, as the benchmark builds it.

Everything the benchmark takes from the program passes through here: the
model configuration in the program's own type and the serving engine,
built as `launch/serve.py` builds it.
"""
from __future__ import annotations

import os
import sys

from bench.weights import Dims

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
if SRC not in sys.path:
    sys.path.insert(0, SRC)


def model_config(dm: Dims):
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=dm.name, family="dense", num_layers=dm.layers, d_model=dm.d,
        num_heads=dm.heads, num_kv_heads=dm.kv_heads, head_dim=dm.head_dim,
        d_ff=dm.ff, vocab_size=dm.vocab, qkv_bias=True,
        rope_theta=dm.rope_theta, norm_eps=dm.eps, tie_embeddings=dm.tied)


def serve_run_config(dm: Dims, slots: int, cache_len: int):
    from repro.configs import CommConfig, RunConfig, ShapeConfig, TrainConfig
    return RunConfig(model=model_config(dm),
                     shape=ShapeConfig("serve", cache_len, slots, "decode"),
                     comm=CommConfig(), train=TrainConfig())


def wide_path(streams: int, compress: str):
    from repro.configs import CommConfig
    from repro.core.path import WAN_LONDON_POZNAN, WidePath
    return WidePath(axis="pod", comm=CommConfig(streams=streams,
                                                compress=compress),
                    link=WAN_LONDON_POZNAN, name="kvship")


def serving_engine(dm: Dims, mesh, params, *, mode: str, slots: int,
                   cache_len: int, streams: int, compress: str,
                   queue_limit: int):
    from repro.runtime import ServingEngine
    path = wide_path(streams, compress) if mode == "disagg" else None
    return ServingEngine(serve_run_config(dm, slots, cache_len), mesh,
                         mode=mode, path=path, params=params,
                         queue_limit=queue_limit)


def mesh_of(shape: dict):
    """`shape`: {"pod": p, "data": d} -> the program's local mesh."""
    from repro.launch.mesh import make_local_mesh
    return make_local_mesh(data=int(shape.get("data", 1)), model=1,
                           pod=int(shape.get("pod", 1)))
