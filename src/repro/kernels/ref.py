"""Pure-jnp oracles for every Pallas kernel.

These are the correctness references: naive, materializing, obviously-right
implementations.  Kernel tests sweep shapes/dtypes and assert_allclose
against these.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True,
                        window: Optional[int] = None,
                        scale: Optional[float] = None) -> jax.Array:
    """Naive attention. q: (B,Sq,H,D); k,v: (B,Sk,KH,D) with H % KH == 0.

    `window` is a sliding-attention width (queries attend to the last
    `window` keys, inclusive of self). Causal offset assumes Sq == Sk or a
    pure-decode Sq==1 suffix.
    """
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    g = H // KH
    if scale is None:
        scale = D ** -0.5
    qf = q.astype(jnp.float32) * scale
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    qf = qf.reshape(B, Sq, KH, g, D)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kf)          # (B,KH,g,Sq,Sk)
    qpos = jnp.arange(Sq)[:, None] + (Sk - Sq)           # align ends
    kpos = jnp.arange(Sk)[None, :]
    mask = jnp.ones((Sq, Sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, vf)
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def rmsnorm_ref(x: jax.Array, w: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def quant_int8_ref(x: jax.Array, block: int = 256):
    """Blockwise absmax int8 quantization along the last dim.

    Returns (q: int8 same shape, scales: float32 shape[..., n/block]).
    """
    *lead, n = x.shape
    if n % block:
        raise ValueError(f"quant_int8_ref: last dim {n} must be a multiple "
                         f"of block {block}")
    xb = x.astype(jnp.float32).reshape(*lead, n // block, block)
    amax = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
    # amax * (1/127), not amax / 127: XLA rewrites a division by a constant
    # into this product under jit, so the product keeps eager, jitted and
    # kernel evaluations bit-identical
    scale = jnp.where(amax > 0, amax * (1.0 / 127.0), 1.0)
    q = jnp.clip(jnp.round(xb / scale), -127, 127).astype(jnp.int8)
    return q.reshape(*lead, n), scale.squeeze(-1)


def dequant_int8_ref(q: jax.Array, scales: jax.Array, block: int = 256,
                     dtype=jnp.float32) -> jax.Array:
    *lead, n = q.shape
    qb = q.astype(jnp.float32).reshape(*lead, n // block, block)
    x = qb * scales[..., None]
    return x.reshape(*lead, n).astype(dtype)
