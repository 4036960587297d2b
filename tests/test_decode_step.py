"""`Transformer.decode_step` against an independent plain reference.

The reference is written from the model's definition, not from its code: a
Python loop over layers and batch rows, in float64 numpy, that writes each
row's new key and value at its slot first and then attends over the slots
that hold a position at or before the new token.  The model runs in
float32, with a random cache (so that stale and valid slots both show), and
must match the reference's logits and return the cache with exactly the new
rows changed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_config
from repro.models import build_model
from repro.models.param import tree_init

B = 4


def _rms(x, w, eps):
    return x / np.sqrt(np.mean(x * x, axis=-1, keepdims=True) + eps) * w


def _rope(x, pos, theta):
    """Rotate-half RoPE. x: (B, heads, Dh); pos: (B,)."""
    half = x.shape[-1] // 2
    ang = pos[:, None] * theta ** (-np.arange(half) / half)     # (B, half)
    cos, sin = np.cos(ang)[:, None], np.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


def _sinusoid(pos, d):
    half = d // 2
    ang = pos[:, None] * np.exp(-np.log(10_000.0) * np.arange(half) / max(half - 1, 1))
    return np.concatenate([np.sin(ang), np.cos(ang)], axis=-1)


def _softmax_attend(q, keys, vals):
    """q: (Dh,); keys/vals: (n, Dh) -> (Dh,)."""
    s = keys @ q / np.sqrt(q.shape[-1])
    p = np.exp(s - s.max())
    return (p / p.sum()) @ vals


def _reference(cfg, params, cache, pos, tokens):
    P = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    k_all = np.array(cache["k"], np.float64)
    v_all = np.array(cache["v"], np.float64)
    H, KH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    W = k_all.shape[2]
    ring = cfg.sliding_window is not None
    pos = np.broadcast_to(np.asarray(pos), (B,)).astype(np.int64)
    x = P["embed"][np.asarray(tokens)[:, 0]]                    # (B, d)
    if not cfg.rope_theta:
        x = x + _sinusoid(pos, cfg.d_model)
    for layer in range(cfg.num_layers):
        lp = jax.tree.map(lambda a: a[layer], P["blocks"])
        a = lp["attn"]
        h = _rms(x, lp["ln1"], cfg.norm_eps)
        q, k, v = h @ a["wq"], h @ a["wk"], h @ a["wv"]
        if "bq" in a:
            q, k, v = q + a["bq"], k + a["bk"], v + a["bv"]
        q, k, v = q.reshape(B, H, Dh), k.reshape(B, KH, Dh), v.reshape(B, KH, Dh)
        if cfg.rope_theta:
            q, k = _rope(q, pos, cfg.rope_theta), _rope(k, pos, cfg.rope_theta)
        o = np.zeros((B, H, Dh))
        for b in range(B):
            slot = pos[b] % W if ring else min(pos[b], W - 1)
            k_all[layer, b, slot] = k[b]
            v_all[layer, b, slot] = v[b]
            j = np.arange(W)
            # the absolute position each slot holds once the row is written
            held = pos[b] - (pos[b] - j) % W if ring else j
            valid = (held >= 0) & (held <= pos[b])
            for hd in range(H):
                kh = hd // (H // KH)
                o[b, hd] = _softmax_attend(q[b, hd], k_all[layer, b, valid, kh],
                                           v_all[layer, b, valid, kh])
        x = x + o.reshape(B, H * Dh) @ a["wo"]
        if cfg.encoder_layers:
            xa = lp["xattn"]
            h = _rms(x, lp["lnx"], cfg.norm_eps)
            q = (h @ xa["wq"]).reshape(B, H, Dh)
            xk, xv = np.asarray(cache["xk"][layer], np.float64), np.asarray(cache["xv"][layer], np.float64)
            o = np.stack([np.stack([_softmax_attend(q[b, hd], xk[b, :, hd // (H // KH)],
                                                    xv[b, :, hd // (H // KH)])
                                    for hd in range(H)]) for b in range(B)])
            x = x + o.reshape(B, H * Dh) @ xa["wo"]
        f = lp["ffn"]
        h = _rms(x, lp["ln2"], cfg.norm_eps)
        g = h @ f["gate"]
        x = x + (g / (1 + np.exp(-g)) * (h @ f["up"])) @ f["down"]
    x = _rms(x, P["ln_f"], cfg.norm_eps)
    head = P["embed"].T if cfg.tie_embeddings else P["head"]
    return x @ head, k_all, v_all


# (arch, config changes, cache length, pos)
CASES = {
    # every row at one depth: one dynamic_update_slice for all rows
    "scalar_pos": ("qwen1.5-0.5b", {}, 16, 7),
    # continuous batching: rows at their own depths, one in the last slot
    # and one past the end of the cache (written to the last slot)
    "vector_pos": ("qwen1.5-0.5b", {}, 16, [0, 5, 15, 19]),
    # a ring of 8 slots that two rows have wrapped
    "sliding_ring": ("h2o-danube-3-4b", {"sliding_window": 8}, 16, [3, 8, 13, 7]),
    # grouped-query attention: 4 query heads over 2 KV heads
    "gqa": ("qwen2.5-14b", {"num_kv_heads": 2}, 16, [2, 9, 15, 11]),
    # whisper's decoder: cross-attention K/V read-only, sinusoidal positions
    "cross_attention": ("whisper-medium", {}, 16, [1, 6, 15, 4]),
}


@pytest.mark.parametrize("case", list(CASES))
def test_decode_step_matches_reference(case):
    arch, changes, max_len, pos = CASES[case]
    cfg = dataclasses.replace(smoke_config(get_config(arch)), **changes)
    model = build_model(cfg)
    rng = np.random.default_rng(0)
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    # random weights around the init (biases and norm weights included)
    params = jax.tree.map(
        lambda a: f32(np.asarray(a, np.float32)
                      + 0.05 * rng.standard_normal(a.shape).astype(np.float32)),
        tree_init(model.param_defs(), seed=0))
    cache = {name: f32(rng.standard_normal(pd.shape))
             for name, pd in model.cache_defs(B, max_len).items()}
    pos = jnp.asarray(pos, jnp.int32)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (B, 1)), jnp.int32)

    want_logits, want_k, want_v = _reference(cfg, params, cache, pos, tokens)
    logits, new = jax.jit(model.decode_step)(params, dict(cache), pos, tokens)

    np.testing.assert_allclose(np.asarray(logits)[:, 0], want_logits,
                               rtol=2e-4, atol=2e-4)
    for name, want in (("k", want_k), ("v", want_v)):
        got = np.asarray(new[name], np.float64)
        changed = want != np.asarray(cache[name], np.float64)
        # exactly one row per batch row per layer was written...
        assert changed.any(axis=(3, 4)).sum() == cfg.num_layers * B
        # ...to the reference's values, and nothing else moved
        np.testing.assert_allclose(got[changed], want[changed], rtol=1e-5, atol=1e-5)
        np.testing.assert_array_equal(got[~changed], np.asarray(cache[name])[~changed])
    for name in ("xk", "xv"):
        if name in cache:
            np.testing.assert_array_equal(np.asarray(new[name]), np.asarray(cache[name]))
