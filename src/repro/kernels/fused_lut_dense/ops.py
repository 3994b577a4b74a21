"""jit'd public wrapper for the fused quantize->LUT-GEMM->dequant kernel.

Pads every dim to a tile multiple. Padding is exact end to end:

* activation k-pad uses 0.0, which the in-kernel quantizer maps to the
  zero-point and hence to shifted code 0 (``affine_qparams`` clips the
  zero-point into the code range, so ``clip(round(z), lo, hi) == z``);
* weight k-pad uses shifted code 0 directly;
* each padded k therefore contributes ``LUT[off, off] = M[0, 0]`` per output,
  which the kernel subtracts from the int32 accumulator *before* dequant
  (float-space correction would break bit-exactness vs the unpadded oracle).
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lut_gather import round_up, table_operands

from .kernel import fused_lut_bwd_kernel, fused_lut_dense_kernel


def _tiles(M: int, K: int, N: int, bm: int, bk: int):
    """Tile sizes and the padding that makes every dim a tile multiple.

    M tiles cap at 128 and shrink to the 8-row multiple above a short M
    (decode batches); N tiles are the 128-lane width of the LUT-GEMM core's
    gathers; K pads to 128 and runs as one grid step when the whole row
    strip fits VMEM comfortably, otherwise in a k-tile that divides it."""
    bm = min(bm, 128, round_up(M, 8))
    bn = 128
    pm, pk, pn = (-M) % bm, (-K) % 128, (-N) % bn
    kp = K + pk
    bk = kp if kp <= 512 else (bk if kp % bk == 0 else 128)
    return (bm, bk, bn), (pm, pk, pn)


def fused_lut_dense(x: jnp.ndarray, wq: jnp.ndarray, lut: jnp.ndarray,
                    offset: int, x_scale, x_zp, w_scale, *, bits: int = 8,
                    bm: int = 128, bk: int = 256,
                    interpret: bool | None = None,
                    emit_acc: bool = False) -> jnp.ndarray:
    """Fused approximate dense forward.

    ``x``: (M, K) float activations; ``wq``: (K, N) shifted int weight codes
    (``code - zero_point``); ``lut`` may be (n_codes, n_codes) or flattened;
    ``x_scale``/``x_zp``: per-tensor activation qparams; ``w_scale``: scalar
    or (N,) per-output-channel weight scale; ``bits``: activation code width
    (clip range), which may be narrower than the ACU's operand width.
    Returns (M, N) float32, bit-exact vs quantize -> LUT GEMM -> dequant.

    ``emit_acc=True`` skips the in-kernel dequant and returns the raw (M, N)
    int32 accumulator (tile padding still corrected in integer space) — the
    mesh contraction-sharded route psums these partials across K shards and
    dequantizes once after the collective.
    """
    tab, n_planes, m00 = table_operands(lut, offset)
    M, K = x.shape
    _, N = wq.shape
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1)
    xz = jnp.asarray(x_zp, jnp.float32).reshape(1)
    ws = jnp.broadcast_to(jnp.asarray(w_scale, jnp.float32).reshape(1, -1),
                          (1, N))
    (bm, bk, bn), (pm, pk, pn) = _tiles(M, K, N, bm, bk)
    if pm or pk or pn:
        x = jnp.pad(x, ((0, pm), (0, pk)))
        wq = jnp.pad(wq, ((0, pk), (0, pn)))
        ws = jnp.pad(ws, ((0, 0), (0, pn)))
    out = fused_lut_dense_kernel(x, wq, tab, xs, xz, m00, ws, offset=offset,
                                 lo=lo, hi=hi, k_pad=pk, n_planes=n_planes,
                                 bm=bm, bk=bk, bn=bn, interpret=interpret,
                                 emit_acc=emit_acc)
    return out[:M, :N]


def fused_lut_bwd(a: jnp.ndarray, b: jnp.ndarray, lut: jnp.ndarray,
                  offset: int, a_scale, b_scale, *, bits: int = 8,
                  bm: int = 128, bk: int = 256,
                  interpret: bool | None = None,
                  emit_acc: bool = False) -> jnp.ndarray:
    """Fused approximate backward GEMM: quantize BOTH float operands
    in-kernel (per-tensor symmetric, zero-point 0), LUT-gather GEMM, int32
    accumulate, single combined-scale dequant ``acc * (sa * sb)``.

    ``a``: (M, K) float; ``b``: (K, N) float — the incoming gradient and the
    saved fake-quantized residual (in either operand order, depending on
    which grad GEMM this is). Zero padding quantizes to code 0 under a
    symmetric quantizer, so each padded k contributes ``LUT[off, off] =
    M[0, 0]`` — subtracted from the accumulator in integer space exactly like
    the forward. ``emit_acc=True`` returns the raw int32 accumulator for the
    mesh contraction-sharded route (psum, correct once, dequant after).
    """
    tab, n_planes, m00 = table_operands(lut, offset)
    M, K = a.shape
    _, N = b.shape
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    sa = jnp.asarray(a_scale, jnp.float32).reshape(1)
    sb = jnp.asarray(b_scale, jnp.float32).reshape(1)
    (bm, bk, bn), (pm, pk, pn) = _tiles(M, K, N, bm, bk)
    if pm or pk or pn:
        a = jnp.pad(a, ((0, pm), (0, pk)))
        b = jnp.pad(b, ((0, pk), (0, pn)))
    out = fused_lut_bwd_kernel(a, b, tab, sa, sb, m00, offset=offset, lo=lo,
                               hi=hi, k_pad=pk, n_planes=n_planes, bm=bm,
                               bk=bk, bn=bn, interpret=interpret,
                               emit_acc=emit_acc)
    return out[:M, :N]
