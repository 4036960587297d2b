"""Plain reference of the dense Qwen2 decoder, in float32 jax.numpy.

It follows the published architecture (RMSNorm, rotary embeddings on half
splits, grouped-query attention with biased q/k/v, SwiGLU, tied or untied
head) and imports nothing of the system under test.  Weights are read in
the bfloat16 they are stored in and each layer is upcast inside the layer
scan, so a float32 copy of the whole model never exists.  Matrix products
run at `precision="highest"`.

`mode` selects the arithmetic of every weight product: "f32" is the
reference; "int8" (symmetric levels) and "fp8" (float8_e4m3fn) are the
controls, the next precision below the bfloat16 that the configurations
state: both operands rounded, per row of the activations and per column of
the weights, and their product accumulated wide.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.weights import Dims

HI = jax.lax.Precision.HIGHEST
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _fake_quant(x, axis, mode):
    """x rounded to `mode` (symmetric int8 levels, or float8_e4m3fn), scaled
    per slice along `axis`, returned in float32."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    top = 127.0 if mode == "int8" else _F8_MAX
    s = jnp.where(amax > 0, amax / top, 1.0)
    if mode == "int8":
        return jnp.clip(jnp.round(x / s), -127, 127) * s
    return (x / s).astype(_F8).astype(jnp.float32) * s


def matmul(x, w, mode: str):
    """x (..., k) @ w (k, n), float32 out.  The controls round both
    operands (x per row, w per column) and multiply the rounded values
    exactly, as an int8 or fp8 unit with a wide accumulator would."""
    w = w.astype(jnp.float32)
    if mode != "f32":
        if mode not in ("int8", "fp8"):
            raise ValueError(f"unknown arithmetic {mode!r}")
        x, w = _fake_quant(x, -1, mode), _fake_quant(w, 0, mode)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def rope(x, theta):
    """x (B, S, H, D): rotate the two halves of each head."""
    S, D = x.shape[1], x.shape[-1]
    half = D // 2
    freqs = theta ** (-np.arange(half, dtype=np.float32) / half)
    ang = np.arange(S, dtype=np.float32)[:, None] * freqs[None, :]
    cos = jnp.asarray(np.cos(ang))[None, :, None, :]
    sin = jnp.asarray(np.sin(ang))[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def attention(q, k, v, dm: Dims):
    """Causal grouped-query attention over the whole sequence."""
    B, S = q.shape[:2]
    g = dm.heads // dm.kv_heads
    q = q.reshape(B, S, dm.kv_heads, g, dm.head_dim) * dm.head_dim ** -0.5
    s = jnp.einsum("bqkgd,bskd->bkgqs", q, k, precision=HI)
    mask = np.tril(np.ones((S, S), bool))
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v, precision=HI)
    return o.reshape(B, S, dm.nq)


def block(x, lp, dm: Dims, mode: str):
    B, S, _ = x.shape
    a = lp["attn"]
    f32 = lambda t: t.astype(jnp.float32)
    h = rms_norm(x, lp["ln1"], dm.eps)
    q = matmul(h, a["wq"], mode) + f32(a["bq"])
    k = matmul(h, a["wk"], mode) + f32(a["bk"])
    v = matmul(h, a["wv"], mode) + f32(a["bv"])
    q = rope(q.reshape(B, S, dm.heads, dm.head_dim), dm.rope_theta)
    k = rope(k.reshape(B, S, dm.kv_heads, dm.head_dim), dm.rope_theta)
    v = v.reshape(B, S, dm.kv_heads, dm.head_dim)
    x = x + matmul(attention(q, k, v, dm), a["wo"], mode)
    h = rms_norm(x, lp["ln2"], dm.eps)
    fp = lp["ffn"]
    u = jax.nn.silu(matmul(h, fp["gate"], mode)) * matmul(h, fp["up"], mode)
    return x + matmul(u, fp["down"], mode)


def hidden(params, tokens, dm: Dims, mode: str):
    """Final-norm hidden states (B, S, d) of a whole sequence."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    body = partial(block, dm=dm, mode=mode)
    x, _ = jax.lax.scan(lambda c, lp: (body(c, lp), None), x,
                        params["blocks"])
    return rms_norm(x, params["ln_f"], dm.eps)


def head_of(params, dm: Dims):
    return params["embed"].T if dm.tied else params["head"]


def logits(params, x, dm: Dims, mode: str = "f32", blocks: int = 8):
    """(…, d) -> (…, V) float32, the head taken in `blocks` vocab slices so
    that no float32 copy of the whole head exists."""
    head = head_of(params, dm)
    V = head.shape[1]
    n = -(-V // blocks)
    pad = n * blocks - V
    if pad:
        head = jnp.pad(head, ((0, 0), (0, pad)))
    hs = head.reshape(head.shape[0], blocks, n).transpose(1, 0, 2)
    out = jax.lax.map(lambda h: matmul(x, h, mode), hs)   # (blocks, ..., n)
    out = jnp.moveaxis(out, 0, -2).reshape(*x.shape[:-1], blocks * n)
    return out[..., :V]


# ---------------------------------------------------------------------------
# serving: gaps of served tokens below the reference's best
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("dm", "mode"))
def served_gaps(params, seq, start, served, dm: Dims, mode: str = "f32"):
    """One request: `seq` (T,) holds its prompt and served tokens, padded at
    the end; `served` (n,) its served tokens, -1 past its end; the prompt
    has `start` tokens.  At positions start-1 .. start+n-2 returns (the gap
    of the served token below the reference's best logit, the gap of the
    token that `mode` puts first), each (n,), -1 where nothing was served.
    Padding lies after every position read, so causality keeps it out."""
    T = seq.shape[0]
    n = served.shape[0]
    pos = jnp.clip(start - 1 + jnp.arange(n), 0, T - 1)
    xh = hidden(params, seq[None], dm, "f32")[0]
    ref = logits(params, xh[pos], dm, "f32")                  # (n, V)
    best = jnp.max(ref, axis=-1)
    tok = jnp.clip(served, 0, dm.vocab - 1)
    got = jnp.take_along_axis(ref, tok[:, None], axis=-1)[:, 0]
    valid = served >= 0
    gap_served = jnp.where(valid, best - got, -1.0)
    if mode == "f32":
        return gap_served, gap_served
    xc = hidden(params, seq[None], dm, mode)[0]
    top = jnp.argmax(logits(params, xc[pos], dm, mode), axis=-1)
    ctl = jnp.take_along_axis(ref, top[:, None], axis=-1)[:, 0]
    return gap_served, jnp.where(valid, best - ctl, -1.0)
