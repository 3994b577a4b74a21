"""Affine quantization (paper §3.2).

``real = scale * (code - zero_point)`` — eq. (1) of the paper with
``A = scale``, ``B = -scale*zero_point``. Arbitrary bitwidth; per-tensor or
per-channel granularity (weights per-channel, activations per-tensor, per the
paper / Krishnamoorthi whitepaper).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class QParams:
    """Quantization parameters for one tensor.

    ``scale``/``zero_point`` are scalars (per-tensor) or vectors broadcast
    along ``axis`` (per-channel). ``zero_point`` lives in *code* space; the
    integer fed to the ACU is ``code - zero_point`` (paper eq. 2), so symmetric
    quantization has ``zero_point == 0``.
    """

    scale: Array
    zero_point: Array
    bits: int
    axis: Optional[int] = None  # channel axis for per-channel, None = per-tensor

    @property
    def lo(self) -> int:
        return -(1 << (self.bits - 1))

    @property
    def hi(self) -> int:
        return (1 << (self.bits - 1)) - 1

    def _expand(self, x: Array, v: Array) -> Array:
        if self.axis is None:
            return v
        shape = [1] * x.ndim
        shape[self.axis] = -1
        return jnp.reshape(v, shape)


_PIN_INT = {2: jnp.int16, 4: jnp.int32, 8: jnp.int64}


@jax.custom_jvp
def pin_rounding(x: Array) -> Array:
    """Identity that pins its input to one canonical set of float roundings.

    XLA fuses value-producing chains into consumers differently in
    differently-structured programs (flat jit vs shard_map-partitioned vs
    eager) — reassociating scale chains, contracting multiply+add into FMA —
    and those 1-ulp differences break bitwise reproducibility between the
    single-device and mesh-sharded ACU routes. Two layers of defense — an int
    bitcast round-trip plus ``optimization_barrier`` — because neither alone
    is load-bearing everywhere: the SPMD partitioner strips the barrier from
    sharded programs and the simplifier can fold the bitcast pair. Together
    they pin every GEMM+dequant route bitwise across eager/jit/mesh (see
    docs/sharding.md for the one residual caveat: bias-add FMA contraction
    in partitioned programs). Gradients pass straight through (custom_jvp —
    neither primitive differentiates in this jax version)."""
    i = _PIN_INT.get(jnp.dtype(x.dtype).itemsize)
    if i is not None and jnp.issubdtype(x.dtype, jnp.floating):
        x = jax.lax.bitcast_convert_type(
            jax.lax.bitcast_convert_type(x, i), x.dtype)
    return jax.lax.optimization_barrier(x)


@pin_rounding.defjvp
def _pin_rounding_jvp(primals, tangents):
    return pin_rounding(primals[0]), tangents[0]


def symmetric_qparams(calib_max: Array, bits: int, axis: Optional[int] = None) -> QParams:
    """Symmetric quantizer from a calibrated absolute max."""
    hi = (1 << (bits - 1)) - 1
    scale = pin_rounding(jnp.maximum(jnp.asarray(calib_max, jnp.float32), 1e-12) / hi)
    return QParams(scale=scale, zero_point=jnp.zeros_like(scale), bits=bits, axis=axis)


def inline_symmetric_scale(amax: Array, bits: int) -> Array:
    """Per-tensor symmetric scale for *in-graph* calibration.

    The approximate backward computes its operand amaxes inside the very
    program it differentiates, so the scale expression itself must compile
    identically in every context. :func:`symmetric_qparams` divides by
    ``hi``, and XLA's SPMD pipeline rewrites that constant division into a
    reciprocal multiply while eager / flat-jit modules keep the true divide
    — a 1-ulp context dependence that lands *upstream* of the pinned result,
    where ``pin_rounding`` cannot undo it. Writing the reciprocal multiply
    explicitly (the reciprocal folds to the same f32 constant everywhere)
    makes eager, flat jit, and SPMD-partitioned programs agree bitwise.
    Note the value may differ from ``symmetric_qparams(...).scale`` by 1 ulp
    — that is fine (any consistent scale is a valid quantizer); what matters
    is that every route sees the *same* one.
    """
    hi = (1 << (bits - 1)) - 1
    inv = jnp.float32(1.0) / jnp.float32(hi)   # folded at trace time
    return pin_rounding(
        jnp.maximum(jnp.asarray(amax, jnp.float32), 1e-12) * inv)


def affine_qparams(xmin: Array, xmax: Array, bits: int, axis: Optional[int] = None) -> QParams:
    """Affine quantizer from calibrated (min, max)."""
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    xmin = jnp.minimum(jnp.asarray(xmin, jnp.float32), 0.0)
    xmax = jnp.maximum(jnp.asarray(xmax, jnp.float32), 0.0)
    scale = pin_rounding(jnp.maximum((xmax - xmin) / (hi - lo), 1e-12))
    zp = jnp.clip(jnp.round(lo - xmin / scale), lo, hi)
    return QParams(scale=scale, zero_point=zp, bits=bits, axis=axis)


def quantize(x: Array, qp: QParams) -> Array:
    """real -> int code (int32 container, values within [lo, hi])."""
    s = qp._expand(x, qp.scale)
    z = qp._expand(x, qp.zero_point)
    q = jnp.round(x / s + z)
    return jnp.clip(q, qp.lo, qp.hi).astype(jnp.int32)


def dequantize(q: Array, qp: QParams) -> Array:
    s = qp._expand(q, qp.scale)
    z = qp._expand(q, qp.zero_point)
    return (q.astype(jnp.float32) - z) * s


def acu_operand(q: Array, qp: QParams) -> Array:
    """Integer operand the approximate hardware multiplier sees:
    ``code - zero_point`` (paper eq. 2)."""
    z = qp._expand(q, qp.zero_point)
    return (q - z.astype(jnp.int32)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# fake quantization with straight-through estimator (QAT, paper §3.2.1)
# ---------------------------------------------------------------------------

@jax.custom_vjp
def fake_quant(x: Array, scale: Array, zero_point: Array, lo: float, hi: float) -> Array:
    q = jnp.clip(jnp.round(x / scale + zero_point), lo, hi)
    return (q - zero_point) * scale


def _fq_fwd(x, scale, zero_point, lo, hi):
    y = fake_quant(x, scale, zero_point, lo, hi)
    in_range = (x / scale + zero_point >= lo) & (x / scale + zero_point <= hi)
    return y, in_range


def _fq_bwd(in_range, g):
    # STE: pass gradient through inside the clip range, zero outside.
    return (jnp.where(in_range, g, 0.0), None, None, None, None)


fake_quant.defvjp(_fq_fwd, _fq_bwd)


def fake_quantize(x: Array, qp: QParams) -> Array:
    """Fake-quantize with STE (differentiable); broadcast per-channel params."""
    s = qp._expand(x, qp.scale)
    z = qp._expand(x, qp.zero_point)
    return fake_quant(x, s, z, float(qp.lo), float(qp.hi))
