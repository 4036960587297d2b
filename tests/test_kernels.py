"""Pallas kernels vs pure-jnp oracles: hypothesis sweeps over shapes/dtypes.

Kernels execute with interpret=True (the kernel body runs in Python on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    settings.register_profile("kernels", max_examples=20, deadline=None)
    settings.load_profile("kernels")
except ImportError:  # property tests skip; deterministic tests still run
    from conftest import given, st  # noqa: F401

from repro.kernels import ops, ref


def _rand(key, shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32)
    return x.astype(dtype)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@given(
    B=st.sampled_from([1, 2]),
    S=st.sampled_from([16, 33, 64, 128]),
    kh=st.sampled_from([(4, 4), (4, 2), (6, 3), (8, 1)]),
    D=st.sampled_from([16, 32, 64]),
    causal=st.booleans(),
    window=st.sampled_from([None, 24]),
    dtype=st.sampled_from([jnp.float32, jnp.bfloat16]),
)
def test_flash_attention_matches_ref(B, S, kh, D, causal, window, dtype):
    H, KH = kh
    q = _rand(0, (B, S, H, D), dtype)
    k = _rand(1, (B, S, KH, D), dtype)
    v = _rand(2, (B, S, KH, D), dtype)
    want = ref.flash_attention_ref(q, k, v, causal=causal, window=window)
    got = ops.flash_attention(q, k, v, causal=causal, window=window,
                              impl="pallas_interpret", block_q=32, block_k=32)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@given(
    S=st.sampled_from([32, 96]),
    impl=st.sampled_from(["kvscan", "causal_blocked"]),
    window=st.sampled_from([None, 16]),
)
def test_jnp_attention_impls_match_ref(S, impl, window):
    q = _rand(3, (2, S, 4, 32), jnp.float32)
    k = _rand(4, (2, S, 2, 32), jnp.float32)
    v = _rand(5, (2, S, 2, 32), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
    got = ops.flash_attention(q, k, v, causal=True, window=window, impl=impl,
                              block_q=32, block_k=16)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_flash_decode_suffix():
    """Sq=1 against a longer KV (decode-style alignment)."""
    q = _rand(6, (2, 1, 4, 32), jnp.float32)
    k = _rand(7, (2, 77, 2, 32), jnp.float32)
    v = _rand(8, (2, 77, 2, 32), jnp.float32)
    want = ref.flash_attention_ref(q, k, v, causal=True)
    got = ops.flash_attention(q, k, v, causal=True, impl="pallas_interpret",
                              block_q=16, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_fully_masked_rows_are_zero_not_nan():
    """Window smaller than the gap: padded rows must not produce NaN."""
    q = _rand(9, (1, 8, 2, 16), jnp.float32)
    k = _rand(10, (1, 8, 2, 16), jnp.float32)
    v = _rand(11, (1, 8, 2, 16), jnp.float32)
    got = ops.flash_attention(q, k, v, causal=True, window=1,
                              impl="pallas_interpret", block_q=8, block_k=8)
    assert np.isfinite(np.asarray(got)).all()


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@given(R=st.integers(1, 70), d=st.sampled_from([32, 128, 384]),
       dtype=st.sampled_from([jnp.float32, jnp.bfloat16]))
def test_rmsnorm_matches_ref(R, d, dtype):
    x = _rand(12, (R, d), dtype)
    w = _rand(13, (d,), jnp.float32)
    want = ref.rmsnorm_ref(x, w)
    got = ops.rmsnorm(x, w, impl="pallas_interpret")
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# int8 quant
# ---------------------------------------------------------------------------

@given(R=st.integers(1, 40), nb=st.integers(1, 4),
       scale=st.floats(1e-3, 1e3))
def test_quant_roundtrip_error_bound(R, nb, scale):
    n = nb * 256
    x = _rand(14, (R, n), jnp.float32) * scale
    q, s = ops.quant_int8(x, impl="pallas_interpret")
    y = ops.dequant_int8(q, s, impl="pallas_interpret")
    # blockwise absmax quantization error <= amax/127 per block (+eps)
    xb = np.asarray(x).reshape(R, nb, 256)
    bound = np.abs(xb).max(-1, keepdims=True) / 127 * 1.001 + 1e-8
    err = np.abs(np.asarray(y).reshape(R, nb, 256) - xb)
    assert (err <= bound).all()


@given(R=st.integers(1, 20))
def test_quant_kernel_matches_ref_exactly(R):
    x = _rand(15, (R, 512), jnp.float32)
    qk, sk = ops.quant_int8(x, impl="pallas_interpret")
    qr, sr = ref.quant_int8_ref(x)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr), rtol=1e-6)


def test_quant_zero_block():
    x = jnp.zeros((3, 256), jnp.float32)
    q, s = ops.quant_int8(x, impl="pallas_interpret")
    y = ops.dequant_int8(q, s, impl="pallas_interpret")
    assert np.asarray(y).sum() == 0 and np.isfinite(np.asarray(s)).all()


# ---------------------------------------------------------------------------
# gradients: kernel forward, jnp backward (custom_vjp)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,Sq,Sk,H,KH,D,causal,window", [
    (2, 64, 64, 4, 2, 16, True, None),     # GQA
    (1, 37, 37, 4, 4, 32, True, 24),       # ragged blocks, sliding window
    (1, 40, 40, 6, 3, 16, False, None),    # non-causal
    (2, 1, 77, 4, 2, 32, True, None),      # decode-style suffix
])
def test_flash_attention_grad_matches_ref(B, Sq, Sk, H, KH, D, causal,
                                          window):
    q = _rand(16, (B, Sq, H, D), jnp.float32)
    k = _rand(17, (B, Sk, KH, D), jnp.float32)
    v = _rand(18, (B, Sk, KH, D), jnp.float32)
    w = _rand(19, (B, Sq, H, D), jnp.float32)

    def loss(impl):
        def f(q, k, v):
            o = ops.flash_attention(q, k, v, causal=causal, window=window,
                                    impl=impl, block_q=32, block_k=32)
            return jnp.sum(o * w)
        return jax.grad(f, argnums=(0, 1, 2))

    got = loss("pallas_interpret")(q, k, v)
    want = loss("ref")(q, k, v)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("R,d,dtype", [(70, 384, jnp.float32),
                                       (33, 128, jnp.bfloat16)])
def test_rmsnorm_grad_matches_ref(R, d, dtype):
    x = _rand(20, (R, d), dtype)
    w = _rand(21, (d,), jnp.float32)
    g = _rand(22, (R, d), jnp.float32)

    def grads(impl):
        return jax.grad(lambda x, w: jnp.sum(
            ops.rmsnorm(x, w, impl=impl).astype(jnp.float32) * g),
            argnums=(0, 1))(x, w)

    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-4
    for a, b in zip(grads("pallas_interpret"), grads("ref")):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=tol, rtol=tol)


# (payload shape, block) of the codec's callers, at CPU-sized extents
@pytest.mark.parametrize("shape,block", [
    ((24 * 37 * 16 * 64,), 256),   # kvship: a KV chunk flattened to 1-D
    ((5, 2, 512), 256),            # compress.quant_chunk: leaf, dim last
    ((1500, 3), 3),                # ring: one 3-value block per row
    ((64, 40, 12), 12),            # ring: a 12-row segment
    ((40, 1), 1),                  # ring: single-value blocks
])
def test_quant_kernel_bit_identical_to_ref(shape, block):
    x = _rand(23, shape, jnp.float32) * 3
    qk, sk = ops.quant_int8(x, block=block, impl="pallas_interpret")
    qr, sr = ref.quant_int8_ref(x, block)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    yk = ops.dequant_int8(qk, sk, block=block, impl="pallas_interpret")
    yr = ref.dequant_int8_ref(qr, sr, block)
    np.testing.assert_array_equal(np.asarray(yk), np.asarray(yr))
