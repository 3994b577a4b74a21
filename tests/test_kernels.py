"""Pallas kernels vs pure-jnp oracles (interpret mode), shape/dtype sweeps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import build_lut, factorize_error, get_multiplier
from repro.kernels.err_matmul.ops import err_matmul
from repro.kernels.err_matmul.ref import err_matmul_ref
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.lut_matmul.ops import lut_matmul
from repro.kernels.lut_matmul.ref import lut_matmul_ref
from repro.kernels.quantize.ops import quantize_op
from repro.kernels.quantize.ref import quantize_ref

MULT = get_multiplier("mul8s_1L2H")
LUT = jnp.asarray(build_lut(MULT))
LR = factorize_error(MULT, 8)


@pytest.mark.parametrize("shape", [(8, 16, 8), (128, 128, 128), (130, 70, 50),
                                   (1, 257, 3), (256, 8, 384)])
def test_lut_matmul_shapes(shape):
    M, K, N = shape
    rng = np.random.default_rng(M * K + N)
    a = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int32)
    out = lut_matmul(a, w, LUT, 128, interpret=True)
    ref = lut_matmul_ref(a, w, LUT.reshape(-1), 128, 256)
    assert jnp.array_equal(out, ref)


@given(m=st.integers(1, 40), k=st.integers(1, 50), n=st.integers(1, 30))
@settings(max_examples=10)
def test_lut_matmul_hypothesis(m, k, n):
    rng = np.random.default_rng(m * 1000 + k * 10 + n)
    a = jnp.asarray(rng.integers(-128, 128, (m, k)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (k, n)), jnp.int32)
    out = lut_matmul(a, w, LUT, 128, interpret=True)
    ref = lut_matmul_ref(a, w, LUT.reshape(-1), 128, 256)
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("shape", [(8, 16, 8), (128, 128, 128), (130, 70, 50)])
def test_err_matmul_shapes(shape):
    M, K, N = shape
    rng = np.random.default_rng(K)
    a = jnp.asarray(rng.integers(-128, 128, (M, K)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (K, N)), jnp.int32)
    f, g = jnp.asarray(LR.f), jnp.asarray(LR.g)
    out = err_matmul(a, w, f, g, 128, interpret=True)
    ref = err_matmul_ref(a, w, f, g, 128)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-2)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,softcap", [
    (True, None, None), (True, 64, None), (True, None, 30.0),
    (False, None, None)])
def test_flash_attention(dtype, causal, window, softcap):
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(4, 256, 32)), dtype)
    k = jnp.asarray(rng.normal(size=(4, 256, 32)), dtype)
    v = jnp.asarray(rng.normal(size=(4, 256, 32)), dtype)
    out = flash_attention(q[:, None].transpose(0, 1, 2, 3).reshape(1, 4, 256, 32),
                          k.reshape(1, 4, 256, 32), v.reshape(1, 4, 256, 32),
                          causal=causal, window=window, softcap=softcap,
                          bq=128, bk=64, interpret=True)
    ref = attention_ref(q, k, v, causal=causal, window=window, softcap=softcap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out.reshape(4, 256, 32), np.float32),
                               np.asarray(ref, np.float32), rtol=tol, atol=tol)


def test_flash_attention_gqa():
    rng = np.random.default_rng(1)
    q = jnp.asarray(rng.normal(size=(2, 8, 128, 16)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, 2, 128, 16)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, 2, 128, 16)), jnp.float32)
    out = flash_attention(q, k, v, causal=True, bq=64, bk=64, interpret=True)
    kk = jnp.repeat(k, 4, 1).reshape(16, 128, 16)
    vv = jnp.repeat(v, 4, 1).reshape(16, 128, 16)
    ref = attention_ref(q.reshape(16, 128, 16), kk, vv, causal=True)
    np.testing.assert_allclose(np.asarray(out).reshape(16, 128, 16),
                               np.asarray(ref), rtol=1e-5, atol=1e-5)


@given(n=st.integers(1, 5000), bits=st.sampled_from([4, 8]))
@settings(max_examples=10)
def test_quantize_kernel(n, bits):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(size=(n,)) * 3, jnp.float32)
    out = quantize_op(x, 0.05, 2.0, bits=bits, interpret=True)
    ref = quantize_ref(x, 0.05, 2.0, bits=bits)
    assert jnp.array_equal(out, ref)


def test_quantize_kernel_2d():
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(33, 77)), jnp.float32)
    assert jnp.array_equal(quantize_op(x, 0.02, -1.0, bits=8),
                           quantize_ref(x, 0.02, -1.0, bits=8))
