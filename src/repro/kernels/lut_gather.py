"""The shared LUT-GEMM core every approximate Pallas kernel runs.

``out[m, n] = sum_k LUT[a[m, k], b[k, n]]`` for index-space codes ``a`` (M, K)
and ``b`` (K, N), in a form Mosaic lowers. A TPU vector gather only moves
data along the 128 lanes of one vreg (``take_along_axis(..., axis=1)`` with
source and indices of the same shape), so the 2-D table lookup is split into
a lane gather and an exact one-hot matmul:

1. **column gather (VPU)** — for each contraction index ``k`` the table is
   gathered along its lanes at ``b[k, :]``: ``G_k[c, n] = LUT[c, b[k, n]]``.
   Every table row ``c`` sits on its own sublane and the indices are the same
   on all of them, so each 128-lane chunk of the table is one lane gather
   per vreg; chunks above the first are selected by the index's high bits.
2. **one-hot row select (MXU)** — ``acc[m, n] += sum_c [a[m, k] == c] *
   G_k[c, n]``: a matmul of the one-hot rows of ``a`` against ``G_k``.
   ``kc`` contraction indices are stacked into one ``(M, kc*R) x (kc*R, N)``
   product.

The matmul is exact: ``G`` is split into signed base-256 digits
(``n_planes`` of them, each in [-128, 127]) and every plane runs as an
int8 x int8 -> int32 product, so each output picks exactly its ``kc`` table
entries with no rounding anywhere. ``sum_p plane_p << 8p`` rebuilds the
int32 sum (modulo 2^32, like every int32 accumulator in this package), so
the result is bit-identical to the plain ``jnp.take`` oracle on any backend
and in interpret mode.

Tables arrive padded by :func:`pad_lut` to ``(R, L)`` with ``R`` and ``L``
multiples of 128; padded entries are never indexed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ONEHOT_WIDTH = 2048        # kc * R lanes of one stacked one-hot operand
# the kernels' scalar operands (scales, zero-points, M[0, 0], row extents,
# page tables) sit whole in SMEM
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def pad_lut(lut) -> tuple[jnp.ndarray, int]:
    """(n, n) or flattened (n*n,) table -> ((R, L) int32 zero-padded to
    128-multiples, n_codes)."""
    lut = jnp.asarray(lut)
    n_codes = int(round(lut.size ** 0.5)) if lut.ndim == 1 else lut.shape[0]
    lut = lut.reshape(n_codes, n_codes).astype(jnp.int32)
    r = round_up(n_codes, LANES)
    if r != n_codes:
        lut = jnp.pad(lut, ((0, r - n_codes), (0, r - n_codes)))
    return lut, n_codes


def lut_planes(lut) -> int:
    """How many signed base-256 digits the table's entries need (1-4).

    ``p`` digits in [-128, 127] reach exactly ``[-128 s, 127 s]`` with
    ``s = (256**p - 1) / 255``. Four cover every int32: a top digit that
    leaves int8 wraps by ``2**32``, which the int32 sum drops anyway. Read
    from the table's values when they are concrete; a traced table gets
    all four."""
    try:
        tab = np.asarray(lut, np.int64)
    except jax.errors.TracerArrayConversionError:
        return 4
    lo, hi = int(tab.min()), int(tab.max())
    for p in (1, 2, 3):
        span = (256 ** p - 1) // 255
        if -128 * span <= lo and hi <= 127 * span:
            return p
    return 4


def table_operands(lut, offset: int):
    """The kernels' table operands: the padded (R, L) table, its digit-plane
    count, and ``M[0, 0] = LUT[off, off]`` as a shape-(1,) int32 (the
    integer every zero-code pad entry contributes)."""
    tab, _ = pad_lut(lut)
    return tab, lut_planes(lut), tab[offset, offset].reshape(1)


def _digits(g, n_planes: int):
    """int32 -> ``n_planes`` int8 digits with ``g == sum_p d_p << 8p``."""
    out = []
    for p in range(n_planes):
        if p == n_planes - 1:
            d = g
        else:
            d = ((g + 128) & 255) - 128
            g = (g - d) >> 8
        out.append(d.astype(jnp.int8))
    return out


def _gather_cols(lut, b_rows):
    """``G[j*R + c, n] = lut[c, b_rows[j, n]]`` for (kc, 128) index rows:
    the kc row blocks of the table gathered along its lanes, stacked."""
    r, l = lut.shape
    kc = b_rows.shape[0]
    idx = jnp.broadcast_to(b_rows[:, None, :], (kc, r, LANES)
                           ).reshape(kc * r, LANES)
    lane = idx & (LANES - 1)

    def chunk(c):
        tab = lut[:, c * LANES:(c + 1) * LANES]
        tab = jnp.broadcast_to(tab[None], (kc, r, LANES)).reshape(kc * r,
                                                                  LANES)
        return jnp.take_along_axis(tab, lane, axis=1)

    g = chunk(0)
    for c in range(1, l // LANES):
        g = jnp.where((idx >> 7) == c, chunk(c), g)
    return g


def lut_gemm(a, b, lut, *, n_planes: int):
    """``out[m, n] = sum_k lut[a[m, k], b[k, n]]`` as int32.

    ``a``: (M, K) and ``b``: (K, N) int32 codes in table index space;
    ``lut``: the (R, L) padded table, a value or a ref (:func:`pad_lut`).
    Static shapes only: the contraction is unrolled in chunks of
    ``ONEHOT_WIDTH // R`` indices, each one stacked gather and one matmul
    per digit plane. ``N`` is either below 128 (indices are lane-padded
    with code 0 and the result sliced back) or a multiple of 128."""
    m, k = a.shape
    n = b.shape[1]
    r = lut.shape[0]
    if n < LANES:
        b = jnp.concatenate([b, jnp.zeros((k, LANES - n), jnp.int32)], axis=1)
    assert b.shape[1] % LANES == 0, b.shape
    kc = max(1, ONEHOT_WIDTH // r)
    cols = []
    for n0 in range(0, b.shape[1], LANES):
        acc = jnp.zeros((m, LANES), jnp.int32)
        for k0 in range(0, k, kc):
            w = min(kc, k - k0)
            codes = jax.lax.broadcasted_iota(jnp.int32, (m, w, r), 2)
            a_c = a[:, k0:k0 + w][:, :, None]
            onehot = (a_c == codes).astype(jnp.int8).reshape(m, w * r)
            g = _gather_cols(lut, b[k0:k0 + w, n0:n0 + LANES])
            for p, d in enumerate(_digits(g, n_planes)):
                part = jnp.dot(onehot, d, preferred_element_type=jnp.int32)
                acc = acc + (part << (8 * p) if p else part)
        cols.append(acc)
    out = cols[0] if len(cols) == 1 else jnp.concatenate(cols, axis=1)
    return out[:, :n] if n < LANES else out


def lut_gemm_streamed(load_a, load_b, n_chunks: int, lut, *, m: int, n: int,
                      n_planes: int):
    """:func:`lut_gemm` over a contraction streamed from refs in 128-wide
    aligned pieces: ``load_a(k0)`` -> (m, 128) codes, ``load_b(k0)`` ->
    (128, n) codes, ``k0`` a multiple of 128. A ``fori_loop`` walks the
    pieces, so the kernel body does not grow with the contraction length."""
    def body(i, acc):
        k0 = pl.multiple_of(i * LANES, LANES)
        return acc + lut_gemm(load_a(k0), load_b(k0), lut, n_planes=n_planes)

    return jax.lax.fori_loop(0, n_chunks, body, jnp.zeros((m, n), jnp.int32))
