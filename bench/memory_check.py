#!/usr/bin/env python3
"""Compile the serving cells' programs for a described TPU v5e and print
their memory analysis: the decode step at the cell's slots and cache length,
and the prefill at the cell's longest prompt.  Nothing runs.

    JAX_PLATFORMS=cpu python3 bench/memory_check.py

Each line gives the bytes of the arguments, the outputs and the temporaries
of one program, and what the engine holds besides (weights + cache).
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh, NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from bench import system  # noqa: E402
from bench.weights import dims_of, load_config, param_bytes  # noqa: E402


def check(cfg_file: str, slots: int, cache_len: int, prompt: int) -> dict:
    from jax.experimental import topologies
    from repro.runtime.step import build_serve_step
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    dm = dims_of(load_config(cfg_file))
    rc = system.serve_run_config(dm, slots, cache_len)
    bundle = build_serve_step(rc, mesh, kind="decode")

    def spec(specs, defs):
        from repro.models.param import is_pd_leaf
        return jax.tree.map(
            lambda pd, s: jax.ShapeDtypeStruct(
                pd.shape, jnp.dtype(pd.dtype), sharding=NamedSharding(mesh, s)),
            defs, specs, is_leaf=is_pd_leaf)

    params = spec(bundle.state_specs["params"], bundle.param_defs)
    cache = spec(bundle.state_specs["cache"], bundle.cache_defs)
    rep = NamedSharding(mesh, P())
    pos = jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep)
    tok = jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=rep)
    dec = bundle.fn.lower(params, cache, pos, tok).compile().memory_analysis()
    toks = jax.ShapeDtypeStruct((1, prompt), jnp.int32, sharding=rep)
    pre = jax.jit(lambda p, t: bundle.model.prefill(p, {"tokens": t})).lower(
        params, toks).compile().memory_analysis()
    cache_bytes = (2 * dm.layers * slots * cache_len * dm.kv_heads
                   * dm.head_dim * 2)

    def row(m):
        return {"arguments": m.argument_size_in_bytes,
                "outputs": m.output_size_in_bytes,
                "temporaries": m.temp_size_in_bytes,
                "aliased": m.alias_size_in_bytes}

    return {"config": dm.name, "slots": slots, "cache_len": cache_len,
            "weights_bytes": param_bytes(dm), "cache_bytes": cache_bytes,
            "decode": row(dec), f"prefill_{prompt}": row(pre)}


def main() -> None:
    jax.config.update("jax_enable_compilation_cache", False)
    for args in (("bench/configs/qwen1.5-0.5b.json", 32, 2048, 1024),
                 ("bench/configs/qwen2.5-14b-pp4.json", 32, 1024, 512)):
        print(json.dumps(check(os.path.join(ROOT, args[0]), *args[1:])),
              flush=True)


if __name__ == "__main__":
    main()
