"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles, in
interpret mode on the CPU.  Interpret mode does not check TPU tiling or
VMEM limits; ``tests/test_tpu_compile.py`` compiles the main-path kernels
for a described v5e chip."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import decode_attention_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.moe_gmm import moe_gmm_pallas
from repro.kernels.ref import (
    chunked_attention,
    decode_attention_ref,
    moe_gmm_ref,
    naive_attention,
    rwkv6_ref,
)
from repro.kernels.rwkv6 import rwkv6_pallas


def _rand(key, shape, dtype):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("S,H,KV,D", [(128, 4, 4, 64), (256, 4, 2, 64), (128, 8, 1, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_sweep(S, H, KV, D, dtype, causal, window):
    B = 2
    q = _rand(0, (B, S, H, D), dtype)
    k = _rand(1, (B, S, KV, D), dtype)
    v = _rand(2, (B, S, KV, D), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 block_q=64, block_k=64, interpret=True)
    ref = naive_attention(q, k, v, causal=causal, window=window)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


@pytest.mark.parametrize("S,H,KV,D", [(128, 4, 4, 64), (256, 4, 2, 64)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 64), (False, 0)])
def test_flash_attention_grad_parity(S, H, KV, D, causal, window):
    """The custom_vjp backward (recompute through the chunked oracle) gives
    the gradients of the oracle itself, alongside a kernel forward."""
    B = 2
    q = _rand(0, (B, S, H, D), jnp.float32)
    k = _rand(1, (B, S, KV, D), jnp.float32)
    v = _rand(2, (B, S, KV, D), jnp.float32)
    ct = _rand(3, (B, S, H, D), jnp.float32)

    def kernel_loss(q, k, v):
        out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                     block_q=64, block_k=64, interpret=True)
        return jnp.sum(out * ct)

    def ref_loss(q, k, v):
        return jnp.sum(naive_attention(q, k, v, causal=causal, window=window) * ct)

    val, grads = jax.value_and_grad(kernel_loss, argnums=(0, 1, 2))(q, k, v)
    ref_val, ref_grads = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), atol=1e-5, rtol=1e-5)
    for g, rg in zip(grads, ref_grads):
        np.testing.assert_allclose(np.asarray(g), np.asarray(rg), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("W,H,KV,D", [(256, 8, 2, 64), (512, 4, 4, 128), (128, 8, 8, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_sweep(W, H, KV, D, dtype):
    B = 2
    q = _rand(0, (B, 1, H, D), dtype)
    kc = _rand(1, (B, W, KV, D), dtype)
    vc = _rand(2, (B, W, KV, D), dtype)
    valid = jnp.arange(W) < (W * 3) // 4
    out = decode_attention_pallas(q, kc, vc, valid, block_w=64, interpret=True)
    ref = decode_attention_ref(q, kc, vc, valid)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_decode_attention_all_invalid_returns_zeros():
    """Regression: an all-False valid mask used to yield garbage (the online
    softmax saw uniform exp(0) mass over masked slots); empty rows must
    produce exactly zero output in both the oracle and the kernel."""
    B, W, H, KV, D = 2, 128, 4, 2, 64
    q = _rand(0, (B, 1, H, D), jnp.float32)
    kc = _rand(1, (B, W, KV, D), jnp.float32)
    vc = _rand(2, (B, W, KV, D), jnp.float32)
    valid = jnp.zeros((W,), bool)
    ref = decode_attention_ref(q, kc, vc, valid)
    out = decode_attention_pallas(q, kc, vc, valid, block_w=64, interpret=True)
    assert np.asarray(ref).shape == (B, 1, H, D)
    np.testing.assert_array_equal(np.asarray(ref), 0.0)
    np.testing.assert_array_equal(np.asarray(out), 0.0)


@pytest.mark.parametrize("W,H,KV,D", [(128, 4, 2, 64), (256, 8, 8, 64)])
def test_decode_attention_per_sequence_valid(W, H, KV, D):
    """[B, W] ragged masks: each sequence attends its own prefix; one row is
    fully masked (mid-reset lane) and must come back as zeros."""
    B = 4
    q = _rand(0, (B, 1, H, D), jnp.float32)
    kc = _rand(1, (B, W, KV, D), jnp.float32)
    vc = _rand(2, (B, W, KV, D), jnp.float32)
    lengths = jnp.array([W // 4, W, 1, 0])
    valid = jnp.arange(W)[None, :] < lengths[:, None]
    out = decode_attention_pallas(q, kc, vc, valid, block_w=64, interpret=True)
    ref = decode_attention_ref(q, kc, vc, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    np.testing.assert_array_equal(np.asarray(out[3]), 0.0)
    # Per-row parity against a single-sequence call with a [W] mask.
    for b in range(B - 1):
        solo = decode_attention_pallas(
            q[b : b + 1], kc[b : b + 1], vc[b : b + 1], valid[b],
            block_w=64, interpret=True,
        )
        np.testing.assert_allclose(
            np.asarray(out[b]), np.asarray(solo[0]), atol=2e-5, rtol=2e-5
        )


def test_decode_attention_shared_valid_broadcasts():
    """A [W] mask must mean the same thing as the equivalent [B, W] mask."""
    B, W, H, KV, D = 3, 128, 4, 4, 64
    q = _rand(0, (B, 1, H, D), jnp.float32)
    kc = _rand(1, (B, W, KV, D), jnp.float32)
    vc = _rand(2, (B, W, KV, D), jnp.float32)
    valid1 = jnp.arange(W) < 77
    valid2 = jnp.broadcast_to(valid1[None], (B, W))
    for fn in (decode_attention_ref, lambda *a: decode_attention_pallas(
            *a, block_w=64, interpret=True)):
        a = np.asarray(fn(q, kc, vc, valid1))
        b = np.asarray(fn(q, kc, vc, valid2))
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("T,H,N,chunk", [(64, 2, 32, 16), (128, 4, 64, 64), (96, 1, 16, 32)])
def test_rwkv6_kernel_sweep(T, H, N, chunk):
    B = 2
    r = _rand(0, (B, T, H, N), jnp.float32) * 0.5
    k = _rand(1, (B, T, H, N), jnp.float32) * 0.5
    v = _rand(2, (B, T, H, N), jnp.float32) * 0.5
    w = jax.nn.sigmoid(_rand(3, (B, T, H, N), jnp.float32)) * 0.5 + 0.5
    u = _rand(4, (H, N), jnp.float32) * 0.1
    out, st = rwkv6_pallas(r, k, v, w, u, chunk=chunk, interpret=True)
    ref_out, ref_st = rwkv6_ref(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(st), np.asarray(ref_st), atol=1e-4, rtol=1e-4)


def test_rwkv6_chunked_ref_matches_plain():
    B, T, H, N = 1, 128, 2, 16
    r = _rand(0, (B, T, H, N), jnp.float32)
    k = _rand(1, (B, T, H, N), jnp.float32)
    v = _rand(2, (B, T, H, N), jnp.float32)
    w = jax.nn.sigmoid(_rand(3, (B, T, H, N), jnp.float32))
    u = _rand(4, (H, N), jnp.float32)
    o1, s1 = rwkv6_ref(r, k, v, w, u, chunk=0)
    o2, s2 = rwkv6_ref(r, k, v, w, u, chunk=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


@pytest.mark.parametrize(
    "sizes,D,F", [([64, 128, 64], 32, 64), ([128, 0, 128, 64], 64, 128)]
)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm_sweep(sizes, D, F, dtype):
    E = len(sizes)
    T = sum(sizes)
    x = _rand(0, (T, D), dtype)
    w = _rand(1, (E, D, F), dtype)
    gs = jnp.array(sizes)
    out = moe_gmm_pallas(x, w, gs, block_m=64, block_n=64, interpret=True)
    ref = moe_gmm_ref(x, w, gs)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=tol, rtol=tol
    )


def test_chunked_attention_matches_naive():
    B, S, H, KV, D = 1, 160, 4, 2, 32
    q = _rand(0, (B, S, H, D), jnp.float32)
    k = _rand(1, (B, S, KV, D), jnp.float32)
    v = _rand(2, (B, S, KV, D), jnp.float32)
    out = chunked_attention(q, k, v, causal=True, chunk=64)  # non-divisible S
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-5)
