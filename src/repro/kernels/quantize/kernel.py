"""Pallas TPU kernel: fused affine quantize (scale / shift / round / clip).

The paper reports ~10% overhead from per-layer quantize/dequantize; fusing the
whole affine pipeline into one VMEM pass removes the intermediate HBM round
trips. Elementwise, so the BlockSpec just tiles rows.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(x_ref, s_ref, z_ref, o_ref, *, lo: int, hi: int):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[0]
    z = z_ref[0]
    q = jnp.clip(jnp.round(x / s + z), lo, hi)
    o_ref[...] = q.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("bits", "block", "interpret"))
def quantize_kernel(x: jnp.ndarray, scale: jnp.ndarray, zero_point: jnp.ndarray,
                    *, bits: int = 8, block: int = 1024,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Per-tensor affine quantization of a flattened tensor.

    x: (N,) float; scale/zero_point: scalars as shape-(1,) arrays.
    """
    (n,) = x.shape
    block = min(block, n)
    assert n % block == 0, (n, block)
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    return pl.pallas_call(
        functools.partial(_kernel, lo=lo, hi=hi),
        name="quantize_kernel",
        grid=(n // block,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((1,), lambda i: (0,)),
            pl.BlockSpec((1,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(x, scale, zero_point)
