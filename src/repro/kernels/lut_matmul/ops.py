"""jit'd public wrapper for the LUT GEMM kernel: pads to tile multiples."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lut_gather import lut_planes, pad_lut, round_up

from .kernel import lut_matmul_kernel


def lut_matmul(a: jnp.ndarray, w: jnp.ndarray, lut: jnp.ndarray, offset: int,
               *, interpret: bool | None = None) -> jnp.ndarray:
    """LUT-gather GEMM with automatic tile selection / zero-padding.

    ``lut`` may be (n_codes, n_codes) or flattened. Padding uses code 0, whose
    LUT row/col contributes ``LUT[off, off]`` per padded k — subtracted after.
    """
    tab, _ = pad_lut(lut)
    M, K = a.shape
    _, N = w.shape
    # M tiles shrink to the 8-row multiple above a short M; N and K pad to
    # the 128-lane width of the LUT-GEMM core
    bm = min(128, round_up(M, 8))
    pm, pk, pn = (-M) % bm, (-K) % 128, (-N) % 128
    if pm or pk or pn:
        a = jnp.pad(a, ((0, pm), (0, pk)))
        w = jnp.pad(w, ((0, pk), (0, pn)))
    out = lut_matmul_kernel(a, w, tab, offset=offset, n_planes=lut_planes(lut),
                            bm=bm, interpret=interpret)
    if pk:
        out = out - pk * tab[offset, offset]
    return out[:M, :N]
