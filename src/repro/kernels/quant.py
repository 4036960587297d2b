"""Blockwise int8 quant/dequant Pallas kernels: the int8 wire codec.

Every run of `block` consecutive values along the last dim gets one float32
absmax scale and travels as int8 (~3.8x fewer link bytes than f32).  The
callers are the cross-pod compression stage (core/compress.py), the int8
ring (core/ring.py, whose adaptive blocks shrink to the segment extent,
down to one value) and the KV shipper (core/kvship.py, 1-D chunks).

TPU layout: the kernels see the payload as (block, M), one quantization
block per lane column.  The absmax is then a reduction over sublanes, each
tile spans all `block` rows (the (8,128) tiling rule holds because the
block equals the array's dim) and `LANES` dense lane columns, and the
scales leave as one lane-dense (1, M) row.  This tiles for every block
size the callers produce; the ops-level wrappers do the transposes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 512     # lane columns (quantization blocks) per tile


def _quant_kernel(x_ref, q_ref, s_ref):
    x = x_ref[...].astype(jnp.float32)                   # (block, lanes)
    amax = jnp.max(jnp.abs(x), axis=0, keepdims=True)
    scale = jnp.where(amax > 0, amax * (1.0 / 127.0), 1.0)   # as ref.py
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    q_ref[...] = q.astype(jnp.int8)
    s_ref[...] = scale


def _dequant_kernel(q_ref, s_ref, o_ref):
    q = q_ref[...].astype(jnp.float32)
    o_ref[...] = (q * s_ref[...]).astype(o_ref.dtype)


def _tiling(M: int):
    lanes = M if M <= LANES else LANES
    return (pl.cdiv(M, lanes),), lanes


def quant_int8_cols(x: jax.Array, *, interpret: bool = False):
    """x: (block, M) -> (int8 (block, M), f32 scales (1, M)); one
    quantization block per column."""
    block, M = x.shape
    grid, lanes = _tiling(M)
    return pl.pallas_call(
        _quant_kernel,
        grid=grid,
        in_specs=[pl.BlockSpec((block, lanes), lambda j: (0, j))],
        out_specs=[
            pl.BlockSpec((block, lanes), lambda j: (0, j)),
            pl.BlockSpec((1, lanes), lambda j: (0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((block, M), jnp.int8),
            jax.ShapeDtypeStruct((1, M), jnp.float32),
        ],
        interpret=interpret,
    )(x)


def dequant_int8_cols(q: jax.Array, s: jax.Array, *, dtype=jnp.float32,
                      interpret: bool = False) -> jax.Array:
    """q: int8 (block, M), s: f32 (1, M) -> (block, M) of `dtype`."""
    block, M = q.shape
    grid, lanes = _tiling(M)
    return pl.pallas_call(
        _dequant_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block, lanes), lambda j: (0, j)),
            pl.BlockSpec((1, lanes), lambda j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block, lanes), lambda j: (0, j)),
        out_shape=jax.ShapeDtypeStruct((block, M), dtype),
        interpret=interpret,
    )(q, s)
