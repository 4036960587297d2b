"""Flash attention Pallas TPU kernel (causal + sliding-window, GQA-aware).

Layout: q (B, H, Sq, D), k/v (B, KH, Sk, D).  Grid (B*H, Sq/bq, Sk/bk) with
the k-block dimension innermost; online-softmax running stats live in VMEM
scratch across k-blocks.  Block sizes are MXU-aligned (multiples of 128 on
the sequence dims; D is the lane dim and is padded by Mosaic if needed).

VMEM working set per program ≈ (bq + 2*bk) * D * 2B + bq*bk*4B + bq*D*4B —
with bq=bk=512, D=128 that is ~1.7 MiB, comfortably inside the ~16 MiB VMEM.

Backward: a `jax.custom_vjp` whose forward is this kernel (then also
writing each row's log-sum-exp) and whose backward is the explicit jnp rule
:func:`flash_attention_bwd`, until a Pallas backward kernel exists.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, *refs,
                  scale: float, causal: bool, window: Optional[int],
                  block_q: int, block_k: int, seq_q: int, seq_k: int,
                  with_lse: bool):
    lse_ref = refs[0] if with_lse else None
    acc_ref, m_ref, l_ref = refs[-3:]
    qi = pl.program_id(1)
    kj = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (bq, D)
    k = k_ref[0].astype(jnp.float32)                     # (bk, D)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) \
        + (seq_k - seq_q)
    kpos = kj * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < seq_k
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                  # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)                               # (bq, bk)
    alpha = jnp.exp(m_prev - m_new)                      # (bq, 1)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    m_ref[...] = m_new
    v = v_ref[0].astype(jnp.float32)                     # (bk, D)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * alpha + pv

    @pl.when(kj == nk - 1)
    def _fin():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                  # fully-masked rows
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        if with_lse:
            lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_call(q, k, v, *, group, causal, window, scale, block_q, block_k,
                interpret, with_lse):
    """The forward kernel; with `with_lse` it also returns the per-row
    log-sum-exp (BH, Sq) of the scaled, masked scores."""
    BH, Sq, D = q.shape
    BKH, Sk, _ = k.shape
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    # pad sequence dims to block multiples (masked out by kpos < seq_k)
    pq = (-Sq) % bq
    pk = (-Sk) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    grid = (BH, (Sq + pq) // bq, (Sk + pk) // bk)
    kern = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        block_q=bq, block_k=bk, seq_q=Sq, seq_k=Sk, with_lse=with_lse)
    out_specs = [pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0))]
    out_shape = [jax.ShapeDtypeStruct((BH, Sq + pq, D), q.dtype)]
    if with_lse:
        out_specs.append(pl.BlockSpec((1, bq, 1), lambda b, i, j: (b, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((BH, Sq + pq, 1), jnp.float32))
    outs = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b // group, j, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    o = outs[0][:, :Sq]
    return (o, outs[1][:, :Sq, 0]) if with_lse else (o, None)


def flash_attention_bwd(q, k, v, o, lse, do, *, group, causal, window, scale,
                        block_k):
    """(dq, dk, dv) of the flash forward, from its output `o` and row
    log-sum-exp `lse` (BH, Sq): the FlashAttention-2 backward written in jnp
    as a scan over k blocks, so only one (Sq, block_k) score tile per head
    is live.  Per block, with P = exp(scale*q k^T - lse) under the mask:
    dV = P^T dO, dS = P * (dO V^T - rowsum(dO * O)), dQ += scale * dS K,
    dK = scale * dS^T Q.  GQA: the `group` q heads of a kv head sum into
    its dK/dV."""
    BH, Sq, D = q.shape
    BKH, Sk, _ = k.shape
    f32 = jnp.float32
    qg = q.astype(f32).reshape(BKH, group, Sq, D)
    dog = do.astype(f32).reshape(BKH, group, Sq, D)
    delta = jnp.sum(dog * o.astype(f32).reshape(BKH, group, Sq, D), axis=-1)
    lse = lse.reshape(BKH, group, Sq, 1)
    bk = min(block_k, Sk)
    pk = (-Sk) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0)))
    nb = (Sk + pk) // bk
    ks = jnp.moveaxis(k.reshape(BKH, nb, bk, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(BKH, nb, bk, D), 1, 0)
    qpos = jnp.arange(Sq) + (Sk - Sq)

    def step(dq, inp):
        kb, vb, j0 = inp
        kb = kb.astype(f32)
        vb = vb.astype(f32)
        kpos = j0 + jnp.arange(bk)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.einsum("bgqd,bkd->bgqk", qg, kb) * scale
        p = jnp.where(mask, jnp.exp(s - lse), 0.0)
        dv = jnp.einsum("bgqk,bgqd->bkd", p, dog)
        dp = jnp.einsum("bgqd,bkd->bgqk", dog, vb)
        ds = p * (dp - delta[..., None]) * scale
        dk = jnp.einsum("bgqk,bgqd->bkd", ds, qg)
        return dq + jnp.einsum("bgqk,bkd->bgqd", ds, kb), (dk, dv)

    dq, (dk, dv) = jax.lax.scan(step, jnp.zeros_like(qg),
                                (ks, vs, jnp.arange(nb) * bk))
    dk = jnp.moveaxis(dk, 0, 1).reshape(BKH, nb * bk, D)[:, :Sk]
    dv = jnp.moveaxis(dv, 0, 1).reshape(BKH, nb * bk, D)[:, :Sk]
    return (dq.reshape(BH, Sq, D).astype(q.dtype), dk.astype(k.dtype),
            dv.astype(v.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=tuple(range(3, 10)))
def _flash(q, k, v, group, causal, window, scale, block_q, block_k,
           interpret):
    return _flash_call(q, k, v, group=group, causal=causal, window=window,
                       scale=scale, block_q=block_q, block_k=block_k,
                       interpret=interpret, with_lse=False)[0]


def _flash_fwd(q, k, v, group, causal, window, scale, block_q, block_k,
               interpret):
    o, lse = _flash_call(q, k, v, group=group, causal=causal, window=window,
                         scale=scale, block_q=block_q, block_k=block_k,
                         interpret=interpret, with_lse=True)
    return o, (q, k, v, o, lse)


def _flash_bwd(group, causal, window, scale, block_q, block_k, interpret,
               res, do):
    q, k, v, o, lse = res
    return flash_attention_bwd(q, k, v, o, lse, do, group=group,
                               causal=causal, window=window, scale=scale,
                               block_k=block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention_bhsd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                         group: int = 1,
                         causal: bool = True,
                         window: Optional[int] = None,
                         scale: Optional[float] = None,
                         block_q: int = 512,
                         block_k: int = 512,
                         interpret: bool = False) -> jax.Array:
    """q: (B*H, Sq, D), k/v: (B*KH, Sk, D) with H == KH*group.

    GQA is handled index-map-side: q program `b` reads k/v row `b // group`
    (standard head order h -> h // group), so k/v are never materialized
    per-q-head.  Differentiable: the forward is the kernel (which then also
    writes the row log-sum-exp) and the backward is
    :func:`flash_attention_bwd`.
    """
    BH = q.shape[0]
    BKH = k.shape[0]
    if BH != BKH * group:
        raise ValueError(f"flash attention: q heads {BH} != kv heads {BKH} "
                         f"* group {group}")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    return _flash(q, k, v, group, causal, window, float(scale), block_q,
                  block_k, interpret)
