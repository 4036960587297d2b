"""Jit-friendly dispatch wrappers around the Pallas kernels.

On TPU the Pallas path runs; on other backends a memory-efficient pure-jnp
implementation lowers instead, so the CPU dry-run HLO has bounded working
sets (the kv-block-scan below is the jnp mirror of the flash kernel's
online softmax).  `impl=` overrides for tests: "pallas_interpret" executes
the actual kernel body in Python on CPU.  Kernel calls run per shard
(`sharding.per_shard`), since GSPMD cannot partition a Mosaic kernel; flash
attention and rmsnorm are differentiable through their custom VJPs.
"""
from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import quant as _q
from repro.kernels import ref as _ref
from repro.kernels import rmsnorm as _rn
from repro.sharding import TP_AXIS, axis_size, constrain, per_shard


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _tp_if_divides(n: int) -> Optional[str]:
    """TP_AXIS when a kernel's independent dim of extent `n` splits evenly
    over it (the kernel then runs on its shard), else None (replicated)."""
    return TP_AXIS if n % axis_size(TP_AXIS) == 0 else None


def attn_shard_mode(B: int, KH: int = 0) -> Optional[str]:
    """How to shard attention internals over the TP axis (beyond-paper).

    Without constraints GSPMD replicates the (B,KH,g,Sq,bk) score tensors
    whenever head counts don't divide the TP axis — TBs of all-gather per
    step on 40/24-head archs.  Preference order:

      "heads"  KV heads divisible by TP: classic head parallelism — zero
               collective bytes in both directions (MHA archs).
      "batch"  local batch divisible by TP: embarrassingly parallel too.
      "seq"    fallback: shard the query-sequence dim (always divisible);
               k/v stay replicated, costing dK/dV partial-sum all-reduces
               in backward (measured in §Perf P1/P2).

    REPRO_ATTN_SP=0 restores the unconstrained baseline for comparison.
    """
    if os.environ.get("REPRO_ATTN_SP", "1") != "1":
        return None
    tp = axis_size(TP_AXIS)
    if tp <= 1:
        return None
    if KH and KH % tp == 0:
        return "heads"
    return "batch" if (B % tp == 0 and B >= tp) else "seq"


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _attention_kvscan(q, k, v, *, causal, window, scale, block_k=None):
    """Online-softmax scan over KV blocks: O(Sq*D) live memory, GQA-aware.

    q: (B,Sq,H,D); k,v: (B,Sk,KH,D).
    """
    if block_k is None:
        block_k = int(os.environ.get("REPRO_ATTN_BK", "1024"))  # memory knob
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    g = H // KH
    bk = min(block_k, Sk)
    pk = (-Sk) % bk
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nb = (Sk + pk) // bk
    qf = (q.astype(jnp.float32) * scale).reshape(B, Sq, KH, g, D)
    mode = attn_shard_mode(B, KH)
    if mode == "batch":
        qf = constrain(qf, TP_AXIS, None, None, None, None)
    elif mode == "seq":
        qf = constrain(qf, None, TP_AXIS, None, None, None)
    elif mode == "heads":
        qf = constrain(qf, None, None, TP_AXIS, None, None)
    ks = jnp.moveaxis(k.reshape(B, nb, bk, KH, D), 1, 0)
    vs = jnp.moveaxis(v.reshape(B, nb, bk, KH, D), 1, 0)
    if mode == "batch":
        ks = constrain(ks, None, TP_AXIS, None, None, None)
        vs = constrain(vs, None, TP_AXIS, None, None, None)
    elif mode == "heads":
        ks = constrain(ks, None, None, None, TP_AXIS, None)
        vs = constrain(vs, None, None, None, TP_AXIS, None)
    qpos = jnp.arange(Sq) + (Sk - Sq)

    def step(carry, inp):
        acc, m, l = carry
        kb, vb, j0 = inp
        s = jnp.einsum("bqkgd,bskd->bkgqs", qf, kb.astype(jnp.float32))
        if mode == "batch":
            s = constrain(s, TP_AXIS, None, None, None, None)
        elif mode == "seq":
            s = constrain(s, None, None, None, TP_AXIS, None)
        elif mode == "heads":
            s = constrain(s, None, TP_AXIS, None, None, None)
        kpos = j0 + jnp.arange(bk)
        mask = kpos[None, :] < Sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window is not None:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, None, None], s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m, m_cur)
        # guard rows that are still fully masked (m_new == -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.exp(jnp.where(jnp.isfinite(s), s - m_safe, -jnp.inf))
        p = jnp.where(jnp.isnan(p), 0.0, p)
        alpha = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
        l2 = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc2 = acc * alpha + jnp.einsum("bkgqs,bskd->bkgqd", p, vb.astype(jnp.float32))
        return (acc2, m_new, l2), None

    acc0 = jnp.zeros((B, KH, g, Sq, D), jnp.float32)
    m0 = jnp.full((B, KH, g, Sq, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KH, g, Sq, 1), jnp.float32)
    if mode == "batch":
        acc0 = constrain(acc0, TP_AXIS, None, None, None, None)
        m0 = constrain(m0, TP_AXIS, None, None, None, None)
        l0 = constrain(l0, TP_AXIS, None, None, None, None)
    elif mode == "seq":
        acc0 = constrain(acc0, None, None, None, TP_AXIS, None)
        m0 = constrain(m0, None, None, None, TP_AXIS, None)
        l0 = constrain(l0, None, None, None, TP_AXIS, None)
    elif mode == "heads":
        acc0 = constrain(acc0, None, TP_AXIS, None, None, None)
        m0 = constrain(m0, None, TP_AXIS, None, None, None)
        l0 = constrain(l0, None, TP_AXIS, None, None, None)
    (acc, _, l), _ = jax.lax.scan(
        step, (acc0, m0, l0), (ks, vs, jnp.arange(nb) * bk))
    l = jnp.where(l == 0.0, 1.0, l)
    o = (acc / l).astype(q.dtype)                        # (B,KH,g,Sq,D)
    o = jnp.moveaxis(o.reshape(B, H, Sq, D), 1, 2)       # (B,Sq,H,D)
    if mode == "batch":
        o = constrain(o, TP_AXIS, None, None, None)
    elif mode == "seq":
        o = constrain(o, None, TP_AXIS, None, None)
    elif mode == "heads":
        o = constrain(o, None, None, TP_AXIS, None)
    return o


def _attention_causal_blocked(q, k, v, *, causal, window, scale, block_q=None,
                              block_k=None):
    """Beyond-baseline CPU/HLO impl: unrolled lower-triangular q-blocks.

    Each q block attends only to k[: (i+1)*bq] (static slice), so compiled
    HLO FLOPs follow the causal triangle (~2x fewer than the rectangle the
    kv-scan computes).  Falls back to kvscan when non-causal.
    """
    if block_q is None:
        block_q = int(os.environ.get("REPRO_ATTN_BQ", "2048"))
    if block_k is None:
        block_k = int(os.environ.get("REPRO_ATTN_BK", "1024"))
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    if not causal or Sq != Sk or Sq % block_q:
        return _attention_kvscan(q, k, v, causal=causal, window=window,
                                 scale=scale, block_k=block_k)
    outs = []
    for i in range(Sq // block_q):
        lo, hi = i * block_q, (i + 1) * block_q
        klo = 0 if window is None else max(0, lo - (window - 1))
        klo = (klo // block_k) * block_k
        outs.append(_attention_kvscan(
            q[:, lo:hi], k[:, klo:hi], v[:, klo:hi],
            causal=True, window=window, scale=scale, block_k=block_k))
    return jnp.concatenate(outs, axis=1)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    window: Optional[int] = None,
                    scale: Optional[float] = None,
                    impl: str = "auto",
                    block_q: int = 512,
                    block_k: int = 512) -> jax.Array:
    """Batched multi-head attention. q: (B,Sq,H,D); k,v: (B,Sk,KH,D)."""
    B, Sq, H, D = q.shape
    _, Sk, KH, _ = k.shape
    if H % KH:
        raise ValueError(f"attention: q heads {H} must be a multiple of "
                         f"kv heads {KH} (GQA group size)")
    g = H // KH
    if scale is None:
        scale = D ** -0.5
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "causal_blocked"
    if impl == "ref":
        return _ref.flash_attention_ref(q, k, v, causal=causal, window=window, scale=scale)
    if impl == "kvscan":
        return _attention_kvscan(q, k, v, causal=causal, window=window, scale=scale,
                                 block_k=block_k)
    if impl == "causal_blocked":
        return _attention_causal_blocked(q, k, v, causal=causal, window=window,
                                         scale=scale)
    if impl in ("pallas", "pallas_interpret"):
        q3 = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
        k3 = k.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
        v3 = v.transpose(0, 2, 1, 3).reshape(B * KH, Sk, D)
        kern = functools.partial(
            _fa.flash_attention_bhsd, group=g, causal=causal, window=window,
            scale=scale, block_q=block_q, block_k=block_k,
            interpret=(impl == "pallas_interpret"))
        # heads over TP when whole kv heads (and their q groups) split evenly
        hd = (_tp_if_divides(B * KH), None, None)
        o3 = per_shard(kern, (hd, hd, hd), hd)(q3, k3, v3)
        return o3.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
    raise ValueError(f"unknown impl {impl!r}")


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

def rmsnorm(x: jax.Array, w: jax.Array, *, eps: float = 1e-5,
            impl: str = "auto") -> jax.Array:
    """x: (..., d); w: (d,)."""
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.rmsnorm_ref(x, w, eps)
    lead = x.shape[:-1]
    d = x.shape[-1]
    x2 = x.reshape(-1, d)
    kern = functools.partial(_rn.rmsnorm_rows, eps=eps,
                             interpret=(impl == "pallas_interpret"))
    rows = (_tp_if_divides(x2.shape[0]), None)
    return per_shard(kern, (rows, (None,)), rows)(x2, w).reshape(*lead, d)


# ---------------------------------------------------------------------------
# int8 blockwise quantization (cross-pod compression)
# ---------------------------------------------------------------------------

def quant_int8(x: jax.Array, *, block: int = 256, impl: str = "auto"):
    """x: (..., n) with n % block == 0 -> (int8, f32 scales (..., n/block)).

    Raises ValueError (not a bare assert) on a ragged trailing dim: callers
    must pad to the block size first (compress.quant_chunk does), and layer-
    bucketed slicing makes ragged trailing dims easy to hit by accident.
    """
    n_last = x.shape[-1] if x.ndim else 0
    if x.ndim == 0 or n_last % block != 0:
        raise ValueError(
            f"quant_int8: leaf of shape {tuple(x.shape)} has trailing dim "
            f"{n_last}, not divisible by block={block}; pad the trailing "
            f"dim to a multiple of the quantization block (see "
            f"repro.core.compress.quant_chunk)")
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.quant_int8_ref(x, block)
    lead = x.shape[:-1]
    n = x.shape[-1]
    # kernel layout (block, M): one quantization block per lane column
    xt = x.reshape(-1, block).T
    cols = (None, _tp_if_divides(xt.shape[1]))
    kern = functools.partial(_q.quant_int8_cols,
                             interpret=(impl == "pallas_interpret"))
    q, s = per_shard(kern, (cols,), (cols, cols))(xt)
    return q.T.reshape(*lead, n), s.reshape(*lead, n // block)


def dequant_int8(q: jax.Array, s: jax.Array, *, block: int = 256,
                 dtype=jnp.float32, impl: str = "auto"):
    if impl == "auto":
        impl = "pallas" if _on_tpu() else "ref"
    if impl == "ref":
        return _ref.dequant_int8_ref(q, s, block, dtype)
    lead = q.shape[:-1]
    n = q.shape[-1]
    qt = q.reshape(-1, block).T
    cols = (None, _tp_if_divides(qt.shape[1]))
    kern = functools.partial(_q.dequant_int8_cols, dtype=dtype,
                             interpret=(impl == "pallas_interpret"))
    x = per_shard(kern, (cols, cols), cols)(qt, s.reshape(1, -1))
    return x.T.reshape(*lead, n)
