"""Shared neural-net layers: norms, RoPE, attention (train + cached decode),
SwiGLU MLP, chunked cross-entropy.

Conventions:
  * activations are (B, S, ...) with B local under the manual-DP shard_map;
    sharding constraints mention only GSPMD-visible axes (usually "model").
  * attention params: wq (d, n_q), wk/wv (d, n_kv), wo (n_q, d), optional
    bq/bk/bv; n_q = H*Dh and n_kv = KH*Dh are the fused head dims (always
    divisible by the TP axis, unlike raw head counts).
"""
from __future__ import annotations

import functools
import math
import os
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.kernels import ops
from repro.sharding import TP_AXIS, constrain


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rms_norm(x: jax.Array, w: jax.Array, eps: float) -> jax.Array:
    return ops.rmsnorm(x, w, eps=eps)


def _rms_fwd(x, w, eps):
    return rms_norm(x, w, eps), (x, w)


def _rms_bwd(eps, res, g):
    """Hand-written backward returning dx in the INPUT dtype.

    Autodiff of the f32-upcast reference keeps the activation cotangent in
    f32, doubling every backward activation all-reduce/all-gather; measured
    in §Perf P5 this was most of the residual collective traffic.
    """
    x, w = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    wf = w.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    r = jax.lax.rsqrt(var + eps)
    xhat = xf * r
    gw = gf * wf
    dx = (gw - xhat * jnp.mean(gw * xhat, axis=-1, keepdims=True)) * r
    dw = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    return dx.astype(x.dtype), dw.astype(w.dtype)


rms_norm.defvjp(_rms_fwd, _rms_bwd)


def sinusoidal_positions(positions: jax.Array, d: int) -> jax.Array:
    """Absolute sinusoidal embeddings (whisper-style). positions: (S,)."""
    half = d // 2
    freqs = jnp.exp(-math.log(10_000.0) * jnp.arange(half, dtype=jnp.float32) / max(half - 1, 1))
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, S, H, D); positions: (S,) absolute positions, or (B, S)
    per-sequence positions (continuous batching: each slot sits at its own
    depth)."""
    B, S, H, D = x.shape
    half = D // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[..., None] * freqs  # (S|B,S, half)
    if ang.ndim == 2:
        cos = jnp.cos(ang)[None, :, None, :]
        sin = jnp.sin(ang)[None, :, None, :]
    else:
        cos = jnp.cos(ang)[:, :, None, :]
        sin = jnp.sin(ang)[:, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:2 * half]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    if 2 * half < D:  # odd head dims (not used by assigned archs, kept safe)
        rot = jnp.concatenate([rot, xf[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


class AttnDims(NamedTuple):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    rope_theta: float
    window: Optional[int]
    causal: bool = True


def _qkv_constrain(t: jax.Array, mode: Optional[str]) -> jax.Array:
    """(B,S,h,D) constraint consistent with the attention shard mode —
    conflicting head constraints trigger SPMD involuntary remat."""
    if mode == "batch":
        return constrain(t, TP_AXIS, None, None, None)
    if mode == "seq":
        return constrain(t, None, TP_AXIS, None, None)
    return constrain(t, None, None, TP_AXIS, None)   # heads / legacy


def _project_qkv(p, x, dims: AttnDims, positions: Optional[jax.Array]):
    from repro.kernels.ops import attn_shard_mode
    B, S, _ = x.shape
    H, KH, Dh = dims.num_heads, dims.num_kv_heads, dims.head_dim
    mode = attn_shard_mode(B, KH)
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    q = _qkv_constrain(q.reshape(B, S, H, Dh), mode)
    # k/v: batch-sharded in batch mode; in seq mode every rank needs the
    # full K/V (q-slices attend everywhere) — leave unconstrained so GSPMD
    # gathers once rather than fighting a head constraint.
    k = k.reshape(B, S, KH, Dh)
    v = v.reshape(B, S, KH, Dh)
    if mode != "seq":
        k = _qkv_constrain(k, mode)
        v = _qkv_constrain(v, mode)
    if dims.rope_theta and positions is not None:
        q = apply_rope(q, positions, dims.rope_theta)
        k = apply_rope(k, positions, dims.rope_theta)
    return q, k, v


def attention(p: dict, x: jax.Array, dims: AttnDims, *,
              positions: Optional[jax.Array] = None,
              kv_x: Optional[jax.Array] = None,
              kv_positions: Optional[jax.Array] = None) -> jax.Array:
    """Full-sequence attention (train / prefill). Cross-attention when
    kv_x is given (whisper decoder): k/v projected from kv_x, non-causal."""
    B, S, d = x.shape
    H, KH, Dh = dims.num_heads, dims.num_kv_heads, dims.head_dim
    if kv_x is None:
        q, k, v = _project_qkv(p, x, dims, positions)
        causal, window = dims.causal, dims.window
    else:
        Skv = kv_x.shape[1]
        q = (x @ p["wq"]).reshape(B, S, H, Dh)
        q = constrain(q, None, None, TP_AXIS, None)
        k = (kv_x @ p["wk"]).reshape(B, Skv, KH, Dh)
        v = (kv_x @ p["wv"]).reshape(B, Skv, KH, Dh)
        k = constrain(k, None, None, TP_AXIS, None)
        v = constrain(v, None, None, TP_AXIS, None)
        if dims.rope_theta and positions is not None:
            q = apply_rope(q, positions, dims.rope_theta)
            if kv_positions is not None:
                k = apply_rope(k, kv_positions, dims.rope_theta)
        causal, window = False, None
    o = ops.flash_attention(q, k, v, causal=causal, window=window)
    o = _qkv_constrain(o, ops.attn_shard_mode(B, KH))
    out = o.reshape(B, S, H * Dh) @ p["wo"]
    return constrain(out, None, None, None)


def cache_slot(pos: jax.Array, W: int, ring: bool) -> jax.Array:
    """The cache slot a token at absolute position `pos` is written to: its
    ring slot under a sliding window, else its position (the last slot
    once the cache is full)."""
    return jnp.mod(pos, W) if ring else jnp.minimum(pos, W - 1)


def decode_attention(p: dict, x: jax.Array, dims: AttnDims, *,
                     k_cache: jax.Array, v_cache: jax.Array,
                     pos: jax.Array,
                     ring: bool = False) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against a cache that it only reads.

    x: (B, 1, d); k_cache/v_cache: (B, W, KH, Dh), the tokens before this
    one.  `pos` is the number of tokens already in the cache (the new
    token's absolute position) — a scalar when every row sits at the same
    depth, or a (B,) vector when the serving tier's continuous batcher has
    each slot at its own depth.  When `ring` (sliding window), the cache is
    a ring buffer of width W and keys were rope'd at insertion; otherwise
    W == max_len and slot i == position i.

    The new token's key and value are not written here: the token is
    scored against the cache with its own slot (`cache_slot`) masked, and
    its own score and value term join the softmax — the same sums as
    attending after writing the row.  The caller writes the returned rows
    after every read of its cache (`put_kv_rows`), so the write is in place.
    Returns (attn_out (B,1,n_q), k_row, v_row); rows (B, KH, Dh) in the
    cache's dtype.
    """
    B, _, _ = x.shape
    H, KH, Dh = dims.num_heads, dims.num_kv_heads, dims.head_dim
    W = k_cache.shape[1]
    g = H // KH
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    q = q.reshape(B, 1, H, Dh)
    k = k.reshape(B, 1, KH, Dh)
    v = v.reshape(B, 1, KH, Dh)
    vec = getattr(pos, "ndim", 0) >= 1       # per-sequence positions (B,)
    if dims.rope_theta:
        if vec:
            ppos = pos.astype(jnp.int32).reshape(B, 1)
        else:
            ppos = jnp.full((1,), pos, jnp.int32)
        q = apply_rope(q, ppos, dims.rope_theta)
        k = apply_rope(k, ppos, dims.rope_theta)
    k_row = k[:, 0].astype(k_cache.dtype)
    v_row = v[:, 0].astype(v_cache.dtype)

    qf = (q.astype(jnp.float32) * Dh ** -0.5).reshape(B, 1, KH, g, Dh)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qf, k_cache.astype(jnp.float32))  # (B,KH,g,1,W)
    s = constrain(s, None, None, None, None, TP_AXIS)
    s_own = jnp.einsum("bqkgd,bkd->bkgq", qf, k_row.astype(jnp.float32))[..., None]
    idx = jnp.arange(W)
    pb = pos[:, None] if vec else jnp.reshape(pos, (1, 1))      # (B|1, 1)
    if ring:
        # slot j holds absolute position pos - ((pos - j) mod W); valid iff >= 0
        valid = (pb - jnp.mod(pb - idx[None, :], W)) >= 0
    else:
        valid = idx[None, :] <= pb                                  # (B|1, W)
    valid = valid & (idx[None, :] != cache_slot(pb, W, ring))      # own slot: s_own
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    p_attn = jax.nn.softmax(jnp.concatenate([s, s_own], axis=-1), axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p_attn[..., :W], v_cache.astype(jnp.float32))
    o = o + jnp.einsum("bkgq,bkd->bqkgd", p_attn[..., W], v_row.astype(jnp.float32))
    o = o.reshape(B, 1, H * Dh).astype(x.dtype)
    return o @ p["wo"], k_row, v_row


def put_kv_rows(cache: jax.Array, rows: jax.Array, pos: jax.Array,
                ring: bool = False) -> jax.Array:
    """Write one new token's rows of every layer into a stacked cache.

    cache: (L, B, W, KH, Dh); rows: (L, B, KH, Dh), row b going to slot
    `cache_slot(pos[b])` (one slot for all when `pos` is a scalar).  One
    `dynamic_update_slice` per batch row (one in all for a scalar `pos`),
    in place on a donated cache.  Call it outside the layer loop: there a
    row write, which arrives head_dim-minor, makes the TPU compiler relay
    the whole carried stack out where the device keeps the slots minor
    (head_dim 64).
    """
    W = cache.shape[2]
    rows = rows.astype(cache.dtype)[:, :, None]                  # (L,B,1,KH,Dh)
    slot = cache_slot(pos, W, ring)
    if getattr(pos, "ndim", 0) == 0:
        return jax.lax.dynamic_update_slice(cache, rows, (0, 0, slot, 0, 0))
    for b in range(cache.shape[1]):
        cache = jax.lax.dynamic_update_slice(cache, rows[:, b:b + 1],
                                             (0, b, slot[b], 0, 0))
    return cache


def swiglu(p: dict, x: jax.Array) -> jax.Array:
    h = jax.nn.silu(x @ p["gate"]) * (x @ p["up"])
    h = constrain(h, None, None, TP_AXIS)
    return constrain(h @ p["down"], None, None, None)


def chunked_ce_loss(x: jax.Array, head: jax.Array, labels: jax.Array, *,
                    mask: Optional[jax.Array] = None,
                    chunk: Optional[int] = None) -> tuple[jax.Array, jax.Array]:
    """Cross-entropy without materializing full (B,S,V) logits.

    Scans over sequence chunks; each chunk's logits are rematerialized in the
    backward pass (jax.checkpoint), bounding live logits to (B,chunk,V).
    Returns (sum_loss, sum_count) — caller normalizes (and psums over DP).
    """
    B, S, d = x.shape
    if chunk is None:
        chunk = int(os.environ.get("REPRO_CE_CHUNK", "512"))  # memory knob
    chunk = min(chunk, S)
    pad = (-S) % chunk
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))
        mpad = jnp.pad(mask if mask is not None else jnp.ones((B, S), bool),
                       ((0, 0), (0, pad)))
    else:
        mpad = mask if mask is not None else jnp.ones((B, S), bool)
    nc = (S + pad) // chunk
    xs = jnp.moveaxis(x.reshape(B, nc, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)
    ms = jnp.moveaxis(mpad.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def chunk_loss(xc, lc, mc):
        # matmul stays bf16 (XLA accumulates f32 internally) and the upcast
        # happens AFTER: the head cotangent and its cross-chunk accumulation
        # then stay bf16 — the f32 (d,V) grad was gigabytes (§Perf P5)
        logits = (xc @ head).astype(jnp.float32)          # (B,chunk,V)
        logits = constrain(logits, None, None, TP_AXIS)
        lse = jax.nn.logsumexp(logits, axis=-1)
        # one-hot contraction, NOT take_along_axis: gathering along the
        # vocab-sharded dim makes GSPMD all-gather the logits (GBs/layer);
        # the masked sum stays local and all-reduces two scalars per token.
        onehot = (lc[..., None] == jnp.arange(logits.shape[-1])[None, None, :])
        gold = jnp.sum(jnp.where(onehot, logits, 0.0), axis=-1)
        nll = (lse - gold) * mc
        return jnp.sum(nll), jnp.sum(mc)

    def body(carry, inp):
        sl, sc = carry
        l, c = chunk_loss(*inp)
        return (sl + l, sc + c.astype(jnp.float32)), None

    (sum_loss, count), _ = jax.lax.scan(body, (jnp.float32(0.0), jnp.float32(0.0)),
                                        (xs, ls, ms))
    return sum_loss, count
