"""The main-path Pallas kernels compile for a described TPU v5e chip.

Nothing runs here: the TPU compiler that ships with jaxlib compiles each
program for a chip that is described, not attached, and refuses what the
chip would refuse (tiles that break the (8,128) rule, too much VMEM, no
autodiff rule).  Every test asserts that the kernel is still in the
compiled program as a `tpu_custom_call`, i.e. that it was neither replaced
by jnp nor run in interpret mode.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and pytest-xdist workers all import
this file.  Keep these tests in this one file.
"""
from __future__ import annotations

import dataclasses
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec, SingleDeviceSharding

from repro.configs import CommConfig, RunConfig, ShapeConfig, TrainConfig, get_config
from repro.core.compress import QBLOCK
from repro.kernels import ops
from repro.models.param import is_pd_leaf
from repro.runtime.step import build_serve_step


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent cache
        # but cannot be read back without the chip: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(fn, *args, kernels=1):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert text.count("custom_call_target=\"tpu_custom_call\"") >= kernels


# qwen1.5-0.5b attention widths: 16 heads of 64
B, S, H, D = 1, 1024, 16, 64


def _attn(q, k, v):
    return ops.flash_attention(q, k, v, causal=True, impl="pallas")


def test_flash_forward_compiles(one_chip):
    qkv = [_spec(one_chip, (B, S, H, D)) for _ in range(3)]
    _assert_kernel(_attn, *qkv)


def test_flash_forward_backward_compiles(one_chip):
    qkv = [_spec(one_chip, (B, S, H, D)) for _ in range(3)]
    # value_and_grad: the forward's output stays live next to the backward
    fwd_bwd = jax.value_and_grad(
        lambda q, k, v: _attn(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    _assert_kernel(fwd_bwd, *qkv)


def test_rmsnorm_forward_backward_compiles(one_chip):
    x = _spec(one_chip, (4 * 1024, 1024))
    w = _spec(one_chip, (1024,), jnp.float32)
    fwd_bwd = jax.value_and_grad(
        lambda x, w: ops.rmsnorm(x, w, impl="pallas").astype(jnp.float32).sum(),
        argnums=(0, 1))
    _assert_kernel(fwd_bwd, x, w)


# (payload shape, block) as the three codec callers produce them
CODEC_SHAPES = {
    # kvship: one 8 MiB bf16 KV chunk flattened to 1-D
    "kvship_chunk": ((4 * 1024 * 1024,), QBLOCK),
    # kvship: the ragged tail chunk of a 37-token prompt's KV, padded
    "kvship_tail": ((24 * 37 * 16 * 64,), QBLOCK),
    # compress.quant_chunk: a stacked-layer leaf, scatter dim moved last
    "compress_leaf": ((24, 1024, 2816), QBLOCK),
    # ring: a 3-row segment of a (.., 1024) leaf, one 3-value block per row
    "ring_small_block": ((1024, 3), 3),
    # ring: a 12-row segment of an MLP leaf
    "ring_segment": ((1024, 2816, 12), 12),
}


@pytest.mark.parametrize("case", list(CODEC_SHAPES))
def test_int8_codec_compiles(one_chip, case):
    shape, block = CODEC_SHAPES[case]
    x = _spec(one_chip, shape, jnp.float32)

    def roundtrip(x):
        q, s = ops.quant_int8(x, block=block, impl="pallas")
        return ops.dequant_int8(q, s, block=block, impl="pallas")

    _assert_kernel(roundtrip, x, kernels=2)


# the serving cells' decode steps: (arch, slots, cache length), depth cut
DECODE_CELLS = {
    "mha_hd64": ("qwen1.5-0.5b", 32, 2048),     # 16 heads of 64
    "gqa_hd128": ("qwen2.5-14b", 32, 1024),     # 40 query / 8 KV heads of 128
}


def _top_level_copy_bytes(hlo: str) -> list[int]:
    """Bytes of every copy/copy-start result outside fused computations:
    the copies that write a buffer of their own."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", hlo))
    sizes, comp = [], None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?(%[\w.\-]+) ", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
            continue
        m = re.search(r"= \(?(\w+)\[([\d,]*)\]\S*\s.*?\b(copy|copy-start)\(", line)
        if comp is not None and comp not in fused and m:
            n = math.prod(int(d) for d in m.group(2).split(",") if d)
            bits = re.search(r"\d+", m.group(1))      # bf16, f32, s8; pred
            sizes.append(n * (int(bits.group()) // 8 if bits else 1))
    return sizes


@pytest.mark.parametrize("case", list(DECODE_CELLS))
def test_serve_decode_updates_cache_in_place(one_chip, case):
    """The decode step writes the new rows into the donated cache and
    copies no layer's worth of it, at the serving cells' shapes."""
    arch, slots, cache_len = DECODE_CELLS[case]
    cfg = dataclasses.replace(get_config(arch), num_layers=2)
    mesh = Mesh(np.array(list(one_chip.device_set)).reshape(1, 1),
                ("data", "model"))
    rc = RunConfig(model=cfg, shape=ShapeConfig("serve", cache_len, slots, "decode"),
                   comm=CommConfig(), train=TrainConfig())
    bundle = build_serve_step(rc, mesh, kind="decode")

    def spec(specs, defs):
        return jax.tree.map(
            lambda pd, s: jax.ShapeDtypeStruct(pd.shape, jnp.dtype(pd.dtype),
                                               sharding=NamedSharding(mesh, s)),
            defs, specs, is_leaf=is_pd_leaf)

    rep = NamedSharding(mesh, PartitionSpec())
    compiled = bundle.fn.lower(
        spec(bundle.state_specs["params"], bundle.param_defs),
        spec(bundle.state_specs["cache"], bundle.cache_defs),
        jax.ShapeDtypeStruct((slots,), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((slots, 1), jnp.int32, sharding=rep)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_serve_decode")
    layer_k = slots * cache_len * cfg.num_kv_heads * cfg.resolved_head_dim * 2
    assert max(_top_level_copy_bytes(hlo), default=0) < layer_k
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * layer_k
