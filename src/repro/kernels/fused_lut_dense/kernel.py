"""Pallas TPU kernel: fused quantize -> LUT-gather GEMM -> affine dequant.

One ``pallas_call`` for the whole approximate dense forward:

``out[m, n] = xs * ws[n] * sum_k LUT[q(x[m, k]) - xz + off, wq[k, n] + off]``

with ``q(x) = clip(round(x / xs + xz), lo, hi)`` — the per-tile activation
quantizer. Compared to the unfused pipeline (``kernels/quantize`` ->
``kernels/lut_matmul`` -> jnp dequant) this removes two HBM round-trips per
layer: the (M, K) int32 activation-code tensor and the (M, N) int32
accumulator never leave VMEM. The weight side stays pre-quantized (codes are
produced once per layer, not once per tile), matching the paper's "LUTs are
populated once" regime.

The padded (R, L) product table is pinned in VMEM for the whole grid; each
(bm, bk) x (bk, bn) tile streams its contraction in 128-wide pieces,
quantizes each activation piece on the VPU and runs the shared LUT-GEMM core
(:mod:`repro.kernels.lut_gather`: lane gathers of the table at the weight
codes, then an exact one-hot int8 matmul on the MXU), accumulating int32
into a persistent VMEM scratch tile. The final K step applies the affine
dequant (per-tensor activation scale x per-channel weight scale row) and
writes the float32 output tile — the only HBM store. Scalars (activation
scale and zero-point, ``M[0, 0]``) live in SMEM.

K-padding correction happens *in integer space* (``k_pad * M[0, 0]``
subtracted from the accumulator before dequant) so padded shapes stay
bit-exact vs the unpadded oracle — a float-space correction after dequant
would not round-trip exactly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lut_gather import (LANES, ONEHOT_WIDTH, ROW_BLOCK, SMEM,
                                      lut_gemm_streamed)
from repro.kernels.runtime import resolve_interpret, round_apart

# scoped VMEM Mosaic gives a kernel call that asks for none (a v5e core has
# 128 MiB); the tile picker (``ops.tiles``) keeps :func:`gemm_vmem_bytes`
# under it
DEFAULT_VMEM = 16 << 20


def gemm_vmem_bytes(bm: int, bk: int, bn: int, planes: int) -> int:
    """Working-set bytes of one dense forward or backward kernel at a tile
    for a 256-code table of ``planes`` digit planes and the widest operands
    either kernel takes, float32. Counts the pipelined blocks twice (double
    buffering), the table, the accumulator scratch and the loop-carried
    accumulator with its update, the piece's codes and the live
    intermediates of one contraction chunk of the core
    (:mod:`repro.kernels.lut_gather`): the gathered int8 planes of every
    column chunk and one row block's one-hot."""
    codes, onehot = 256, ONEHOT_WIDTH
    return (2 * 4 * (bm * bk + bk * bn + bm * bn + 8 * bn)   # x, w, out, scale
            + 2 * 4 * codes * codes                           # the table
            + 3 * 4 * bm * bn                                 # accumulators
            + 4 * LANES * (bm + bn)                           # a and b codes
            + planes * codes * codes                          # packed planes
            + planes * onehot * bn                            # gathered planes
            + (1 + 4) * ROW_BLOCK * onehot)                   # one-hot


def _finish(acc_ref, o_ref, m00_ref, scale, *, k_pad: int, emit_acc: bool):
    """Last K step: integer-space K-pad correction, then the raw accumulator
    (``emit_acc``) or one combined-scale dequant."""
    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _dequant():
        acc = acc_ref[...]
        if k_pad:  # padded k entries each contributed LUT[off, off] = M[0, 0]
            acc = acc - k_pad * m00_ref[0]
        if emit_acc:
            # mesh contraction sharding: partial int32 accumulators leave the
            # kernel, psum across K shards, dequant once after the collective
            o_ref[...] = acc
        else:
            # one combined-scale multiply: a * xs * ws chains get reassociated
            # by the XLA simplifier under shard_map, breaking bit-exactness
            o_ref[...] = acc.astype(jnp.float32) * scale()


def _kernel(x_ref, w_ref, lut_ref, xs_ref, xz_ref, m00_ref, ws_ref, o_ref,
            acc_ref, *, offset: int, lo: int, hi: int, k_pad: int,
            n_planes: int, emit_acc: bool):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = xs_ref[0]                                 # per-tensor activation scale
    xz = xz_ref[0]                                 # activation zero-point (code)
    bm, bk = x_ref.shape
    bn = w_ref.shape[1]

    def load_a(k0):                                # shifted codes, index space
        x = x_ref[:, pl.ds(k0, LANES)].astype(jnp.float32)
        q = jnp.clip(jnp.round(x / xs + xz), lo, hi).astype(jnp.int32)
        return q - xz.astype(jnp.int32) + offset

    def load_b(k0):
        return w_ref[pl.ds(k0, LANES), :].astype(jnp.int32) + offset

    acc_ref[...] += lut_gemm_streamed(load_a, load_b, bk // LANES, lut_ref,
                                      m=bm, n=bn, n_planes=n_planes)
    _finish(acc_ref, o_ref, m00_ref, lambda: xs * ws_ref[...], k_pad=k_pad,
            emit_acc=emit_acc)


def _bwd_kernel(a_ref, b_ref, lut_ref, as_ref, bs_ref, m00_ref, o_ref,
                acc_ref, *, offset: int, lo: int, hi: int, k_pad: int,
                n_planes: int, emit_acc: bool):
    """Backward flavor: BOTH operands arrive as float residuals and are
    quantized in-kernel with per-tensor *symmetric* scales (zero-point 0 —
    gradients are zero-centred, and a zp-free quantizer keeps the combined
    dequant a single scale multiply). Everything downstream is the forward
    kernel verbatim: shifted-code LUT-GEMM core, int32 accumulate,
    integer-space K-pad correction, one combined-scale dequant."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    sa = as_ref[0]                                 # per-tensor symmetric scales
    sb = bs_ref[0]
    bm, bk = a_ref.shape
    bn = b_ref.shape[1]

    def load_a(k0):
        af = a_ref[:, pl.ds(k0, LANES)].astype(jnp.float32)
        return jnp.clip(jnp.round(af / sa), lo, hi).astype(jnp.int32) + offset

    def load_b(k0):
        bf = b_ref[pl.ds(k0, LANES), :].astype(jnp.float32)
        return jnp.clip(jnp.round(bf / sb), lo, hi).astype(jnp.int32) + offset

    acc_ref[...] += lut_gemm_streamed(load_a, load_b, bk // LANES, lut_ref,
                                      m=bm, n=bn, n_planes=n_planes)
    _finish(acc_ref, o_ref, m00_ref, lambda: sa * sb, k_pad=k_pad,
            emit_acc=emit_acc)


def _gemm_call(kernel, lhs, rhs, operands, specs, *, name: str, bm: int,
               bk: int, bn: int, interpret, emit_acc: bool):
    """The shared ``pallas_call``, named ``name``: (i, j, k) grid over
    (M, N, K) tiles, the whole padded table resident, int32 accumulator
    scratch."""
    M, K = lhs.shape
    _, N = rhs.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 and bk % LANES == 0, (
        f"shape {(M, K, N)} not divisible by tile {(bm, bk, bn)}")
    lut = operands[0]
    out = pl.pallas_call(
        kernel,
        name=name,
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(lut.shape, lambda i, j, k: (0, 0)),
            *specs,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N),
                                       jnp.int32 if emit_acc else jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(lhs, rhs, *operands)
    return round_apart(out, resolve_interpret(interpret))


@functools.partial(jax.jit, static_argnames=("offset", "lo", "hi", "k_pad",
                                             "n_planes", "bm", "bk", "bn",
                                             "interpret", "emit_acc"))
def fused_lut_bwd_kernel(a: jnp.ndarray, b: jnp.ndarray, lut: jnp.ndarray,
                         a_scale: jnp.ndarray, b_scale: jnp.ndarray,
                         m00: jnp.ndarray, *, offset: int, lo: int, hi: int,
                         k_pad: int = 0, n_planes: int = 4, bm: int = 128,
                         bk: int = 128, bn: int = 128,
                         interpret: bool | None = None,
                         emit_acc: bool = False) -> jnp.ndarray:
    """a: (M, K) float; b: (K, N) float; both quantized in-kernel with the
    per-tensor symmetric scales ``a_scale``/``b_scale`` (shape-(1,) f32);
    lut: the (R, L) padded table; m00: shape-(1,) int32 ``LUT[off, off]``.
    Returns (M, N) float32 — or the raw int32 accumulator with
    ``emit_acc=True`` (the sharded contraction route psums those partials
    and dequantizes once after the collective)."""
    return _gemm_call(
        functools.partial(_bwd_kernel, offset=offset, lo=lo, hi=hi,
                          k_pad=k_pad, n_planes=n_planes, emit_acc=emit_acc),
        a, b, (lut, a_scale, b_scale, m00), [SMEM, SMEM, SMEM],
        name="fused_lut_bwd_kernel", bm=bm, bk=bk, bn=bn, interpret=interpret,
        emit_acc=emit_acc)


@functools.partial(jax.jit, static_argnames=("offset", "lo", "hi", "k_pad",
                                             "n_planes", "bm", "bk", "bn",
                                             "interpret", "emit_acc"))
def fused_lut_dense_kernel(x: jnp.ndarray, wq: jnp.ndarray, lut: jnp.ndarray,
                           x_scale: jnp.ndarray, x_zp: jnp.ndarray,
                           m00: jnp.ndarray, w_scale_row: jnp.ndarray, *,
                           offset: int, lo: int, hi: int, k_pad: int = 0,
                           n_planes: int = 4, bm: int = 128, bk: int = 128,
                           bn: int = 128, interpret: bool | None = None,
                           emit_acc: bool = False) -> jnp.ndarray:
    """x: (M, K) float; wq: (K, N) shifted int weight codes; lut: the (R, L)
    padded table; x_scale/x_zp: shape-(1,) f32; m00: shape-(1,) int32
    ``LUT[off, off]``; w_scale_row: (1, N) f32. Returns (M, N) float32 — or
    the raw (M, N) int32 accumulator with ``emit_acc=True`` (sharded
    contraction: the caller psums partials across K shards and dequantizes
    after)."""
    return _gemm_call(
        functools.partial(_kernel, offset=offset, lo=lo, hi=hi, k_pad=k_pad,
                          n_planes=n_planes, emit_acc=emit_acc),
        x, wq, (lut, x_scale, x_zp, m00, w_scale_row),
        [SMEM, SMEM, SMEM, pl.BlockSpec((1, bn), lambda i, j, k: (0, j))],
        name="fused_lut_dense_kernel", bm=bm, bk=bk, bn=bn, interpret=interpret,
        emit_acc=emit_acc)
