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

from repro.kernels.lut_gather import LANES, round_up, table_operands

from .kernel import (DEFAULT_VMEM, fused_lut_bwd_kernel,
                     fused_lut_dense_kernel, gemm_vmem_bytes)


TILE_CAP = 640           # most rows or columns of one output tile


def _widths(dim: int) -> list[int]:
    """The multiples of 128 up to ``TILE_CAP`` that divide the 128-padded
    ``dim``."""
    d = round_up(dim, LANES)
    return [t for t in range(LANES, TILE_CAP + 1, LANES) if d % t == 0]


def tiles(M: int, K: int, N: int, planes: int) -> tuple[int, int, int]:
    """The (bm, bk, bn) tile the dense forward and backward kernels run for
    an (M, K) x (K, N) GEMM with a table of ``planes`` digit planes.

    The core builds its gathered planes once per tile column chunk and its
    one-hot once per row block, so wide tiles spread that work over more
    MXU products: ``bm`` and ``bn`` are multiples of 128 up to ``TILE_CAP``
    that divide the padded dims, the pair of largest area (then rows) whose
    VMEM model fits the default scoped VMEM. With the 2-plane tables of
    the registered multipliers that is the widest of each. A short M
    (decode, M <= 128) keeps one tile of ``round_up(M, 8)`` rows. K runs
    as one grid step up to 512, otherwise in 256- or 128-wide steps that
    divide it."""
    kp = round_up(K, LANES)
    bk = kp if kp <= 512 else (256 if kp % 256 == 0 else LANES)
    rows = [round_up(M, 8)] if M <= LANES else _widths(M)
    _, bm, bn = max((bm * bn, bm, bn) for bm in rows for bn in _widths(N)
                    if gemm_vmem_bytes(bm, bk, bn, planes) <= DEFAULT_VMEM)
    return bm, bk, bn


def _padding(M: int, K: int, N: int, tile) -> tuple[int, int, int]:
    bm, _, bn = tile
    return (-M) % bm, (-K) % LANES, (-N) % bn


def fused_lut_dense(x: jnp.ndarray, wq: jnp.ndarray, lut: jnp.ndarray,
                    offset: int, x_scale, x_zp, w_scale, *, bits: int = 8,
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
    bm, bk, bn = tile = tiles(M, K, N, n_planes)
    pm, pk, pn = _padding(M, K, N, tile)
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
    bm, bk, bn = tile = tiles(M, K, N, n_planes)
    pm, pk, pn = _padding(M, K, N, tile)
    if pm or pk or pn:
        a = jnp.pad(a, ((0, pm), (0, pk)))
        b = jnp.pad(b, ((0, pk), (0, pn)))
    out = fused_lut_bwd_kernel(a, b, tab, sa, sb, m00, offset=offset, lo=lo,
                               hi=hi, k_pad=pk, n_planes=n_planes, bm=bm,
                               bk=bk, bn=bn, interpret=interpret,
                               emit_acc=emit_acc)
    return out[:M, :N]
