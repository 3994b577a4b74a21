"""Affine quantization properties (hypothesis) + STE gradients."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, strategies as st

from repro.core.quantization import (QParams, acu_operand, affine_qparams,
                                     dequantize, fake_quantize, quantize,
                                     symmetric_qparams)

floats = st.floats(-100.0, 100.0, allow_nan=False, width=32,
                   allow_subnormal=False)


@given(x=st.lists(floats, min_size=1, max_size=64),
       bits=st.sampled_from([4, 8, 12]))
def test_quant_dequant_error_bound(x, bits):
    """Round-trip error <= scale/2 inside the clip range."""
    x = jnp.asarray(x, jnp.float32)
    amax = float(jnp.max(jnp.abs(x)))
    qp = symmetric_qparams(jnp.float32(max(amax, 1e-6)), bits)
    err = jnp.abs(dequantize(quantize(x, qp), qp) - x)
    assert float(err.max()) <= float(qp.scale) / 2 + 1e-6


@given(bits=st.sampled_from([4, 8, 12]))
def test_zero_is_exact(bits):
    """Affine quantization must represent 0.0 exactly (padding correctness)."""
    qp = affine_qparams(jnp.float32(-3.0), jnp.float32(5.0), bits)
    z = dequantize(quantize(jnp.zeros(4), qp), qp)
    assert float(jnp.abs(z).max()) == 0.0


@given(lo=st.floats(-50.0, -0.001953125, width=32, allow_subnormal=False),
       hi=st.floats(0.001953125, 50.0, width=32, allow_subnormal=False))
def test_affine_range_covered(lo, hi):
    qp = affine_qparams(jnp.float32(lo), jnp.float32(hi), 8)
    x = jnp.asarray([lo, hi, 0.0], jnp.float32)
    back = dequantize(quantize(x, qp), qp)
    # zero_point rounding adds up to scale/2 on top of value rounding
    assert float(jnp.abs(back - x).max()) <= float(qp.scale) * 1.51


def test_per_channel_weights(rng):
    w = jnp.asarray(rng.normal(size=(16, 8)) * np.array([1e-3] * 4 + [10.0] * 4)[None, :],
                    jnp.float32)
    from repro.core.calibration import calibrate_weight
    qp = calibrate_weight(w, 8, axis=1)
    assert qp.scale.shape == (8,)
    err = jnp.abs(dequantize(quantize(w, qp), qp) - w)
    # per-channel: each channel's error bounded by its own scale/2
    assert float(err[:, :4].max()) < 1e-4
    assert float(err[:, 4:].max()) < float(qp.scale[4:].max()) / 2 + 1e-6


def test_acu_operand_shifts_zero_point():
    qp = QParams(scale=jnp.float32(0.1), zero_point=jnp.float32(3.0), bits=8)
    q = quantize(jnp.asarray([0.0]), qp)
    assert int(acu_operand(q, qp)[0]) == 0  # real 0 -> integer operand 0


def test_ste_gradient():
    qp = symmetric_qparams(jnp.float32(1.0), 8)

    def f(x):
        return fake_quantize(x, qp).sum()

    g = jax.grad(f)(jnp.asarray([0.5, -0.3, 5.0, -5.0]))
    np.testing.assert_allclose(np.asarray(g), [1.0, 1.0, 0.0, 0.0])


def test_fake_quant_matches_quant_dequant(rng):
    x = jnp.asarray(rng.normal(size=(32,)), jnp.float32)
    qp = symmetric_qparams(jnp.float32(2.0), 8)
    np.testing.assert_allclose(np.asarray(fake_quantize(x, qp)),
                               np.asarray(dequantize(quantize(x, qp), qp)),
                               rtol=1e-6, atol=1e-6)
