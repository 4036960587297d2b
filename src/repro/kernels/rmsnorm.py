"""Fused RMSNorm Pallas kernel — bandwidth-bound row kernel.

Grid over row tiles; each program normalizes (block_rows, d) in VMEM.
Differentiable: the forward is the kernel, the backward the jnp rule in
:func:`_rmsnorm_bwd`.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _rmsnorm_kernel(x_ref, w_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps)
    o_ref[...] = (y * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)


def _rmsnorm_call(x, w, eps, block_rows, interpret):
    R, d = x.shape
    br = min(block_rows, R)
    pr = (-R) % br
    if pr:
        x = jnp.pad(x, ((0, pr), (0, 0)))
    out = pl.pallas_call(
        functools.partial(_rmsnorm_kernel, eps=eps),
        grid=((R + pr) // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((R + pr, d), x.dtype),
        interpret=interpret,
    )(x, w)
    return out[:R]


_rmsnorm = jax.custom_vjp(_rmsnorm_call, nondiff_argnums=(2, 3, 4))


def _rmsnorm_fwd(x, w, eps, block_rows, interpret):
    return _rmsnorm_call(x, w, eps, block_rows, interpret), (x, w)


def _rmsnorm_bwd(eps, block_rows, interpret, res, g):
    """y = x * r * w with r = rsqrt(mean(x^2) + eps) per row, so with
    xh = x * r: dw = sum_rows(g * xh), dx = r * (g*w - xh * mean(g*w*xh))."""
    x, w = res
    xf = x.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xh = xf * r
    gf = g.astype(jnp.float32)
    gw = gf * w.astype(jnp.float32)
    dx = r * (gw - xh * jnp.mean(gw * xh, axis=-1, keepdims=True))
    dw = jnp.sum(gf * xh, axis=0)
    return dx.astype(x.dtype), dw.astype(w.dtype)


_rmsnorm.defvjp(_rmsnorm_fwd, _rmsnorm_bwd)


def rmsnorm_rows(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
                 block_rows: int = 256, interpret: bool = False) -> jax.Array:
    """x: (R, d); w: (d,)."""
    return _rmsnorm(x, w, float(eps), block_rows, interpret)
