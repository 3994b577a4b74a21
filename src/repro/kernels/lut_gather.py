"""The shared LUT-GEMM core every approximate Pallas kernel runs.

``out[m, n] = sum_k LUT[a[m, k], b[k, n]]`` for index-space codes ``a`` (M, K)
and ``b`` (K, N), in a form Mosaic lowers. A TPU vector gather only moves
data along the 128 lanes of one vreg (``take_along_axis(..., axis=1)`` with
source and indices of the same shape), so the 2-D table lookup is split into
a lane gather and an exact one-hot matmul:

1. **column gather (VPU)** — for each contraction index ``k`` the table is
   gathered along its lanes at ``b[k, :]``: ``G_k[c, n] = LUT[c, b[k, n]]``.
   The indices are the same on every table row, so the gather runs on the
   table's int8 digit planes packed four rows to a 32-bit word: one lane
   gather per packed vreg moves 32 table rows; chunks of 128 table columns
   above the first are selected by the index's high bits.
2. **one-hot row select (MXU)** — ``acc[m, n] += sum_c [a[m, k] == c] *
   G_k[c, n]``: a matmul of the one-hot rows of ``a`` against ``G_k``,
   each one-hot row block a lane broadcast of ``a[:, k]`` compared with the
   code iota. ``kc`` contraction indices are stacked into one
   ``(M, kc*R) x (kc*R, N)`` product.

The matmul is exact: the table is split once per call into signed base-256
digits (``n_planes`` of them, each in [-128, 127]) and every plane runs as
an int8 x int8 -> int32 product, so each output picks exactly its ``kc``
table entries with no rounding anywhere. ``sum_p plane_p << 8p`` rebuilds
the int32 sum (modulo 2^32, like every int32 accumulator in this package),
so the result is bit-identical to the plain ``jnp.take`` oracle on any
backend and in interpret mode; integer sums are associative, so the order
in which chunks and blocks accumulate does not matter.

Each chunk of ``kc`` contraction indices builds its operands once for the
whole (M, N) output block: the gathered planes once per 128-lane column
chunk, shared by every row, and the one-hot once per ``ROW_BLOCK`` rows,
shared by every column chunk. A larger output block therefore spreads the
expansion over more MXU work (the dense kernels' tile choice,
``fused_lut_dense/ops.tiles``). A ragged caller (the grouped expert kernel)
passes the block's live row count: the gathered planes are still built
once for the block, and the one-hots and products run only for the row
blocks that hold a live row.

Tables arrive padded by :func:`pad_lut` to ``(R, L)`` with ``R`` and ``L``
multiples of 128; padded entries are never indexed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
ONEHOT_WIDTH = 2048        # kc * R lanes of one stacked one-hot operand
ROW_BLOCK = 128            # rows of one one-hot operand
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


def _packed_planes(lut, n_planes: int):
    """The table's ``n_planes`` signed digit planes, each packed four row
    blocks to a 32-bit word: byte ``i`` of word ``(q, l)`` is the digit of
    table entry ``(i * R/4 + q, l)``. (R // 4, L) int32 arrays."""
    rw = lut.shape[0] // 4
    out = []
    for d in _digits(lut[...], n_planes):
        d = d.astype(jnp.int32)
        word = d[:rw] & 255
        for i in range(1, 4):
            byte = d[i * rw:(i + 1) * rw]
            word = word | ((byte & 255) << (8 * i) if i < 3 else byte << 24)
        out.append(word)
    return out


def _gather_planes(packed, b_rows):
    """``G_p[j*R + c, n] = plane_p[c, b_rows[j, n]]``: the int8 (w*R, 128)
    gathered block of each packed plane for (w, 128) index rows. One lane
    gather of a packed (R/4, 128) block fetches four table row blocks; the
    shifts unpack them."""
    rw, l = packed[0].shape
    lane = b_rows & (LANES - 1)
    high = b_rows >> 7
    out = []
    for tab in packed:
        blocks = []
        for j in range(b_rows.shape[0]):
            idx = jnp.broadcast_to(lane[j:j + 1], (rw, LANES))
            g = jnp.take_along_axis(tab[:, :LANES], idx, axis=1)
            for c in range(1, l // LANES):
                hit = jnp.broadcast_to(high[j:j + 1] == c, (rw, LANES))
                g = jnp.where(hit, jnp.take_along_axis(
                    tab[:, c * LANES:(c + 1) * LANES], idx, axis=1), g)
            blocks += [(g << (24 - 8 * i)) >> 24 for i in range(3)]
            blocks.append(g >> 24)
        out.append(jnp.concatenate(blocks, axis=0).astype(jnp.int8))
    return out


def _onehot(col, r: int):
    """(rows, 128) codes, every lane of a row equal, -> the (rows, r) int8
    one-hot of that code."""
    codes = jax.lax.broadcasted_iota(jnp.int32, col.shape, 1)
    return jnp.concatenate([(col == codes + c).astype(jnp.int8)
                            for c in range(0, r, LANES)], axis=1)


def _accumulate(acc, a_col, b_rows, packed, r: int, live_rows=None):
    """Add one chunk of ``w`` contraction indices to the block accumulators
    ``acc[i][c]`` (row block ``i``, 128-lane column chunk ``c``).
    ``a_col(i, j)`` gives row block ``i`` of the chunk's ``j``-th code
    column broadcast across 128 lanes; ``b_rows`` is the chunk's (w, N)
    codes. The gathered planes serve every row block, each one-hot every
    column chunk. With a traced ``live_rows`` (at least 1), row block ``i``
    above the first runs only where ``i * ROW_BLOCK < live_rows``; the
    others keep their accumulators."""
    planes = [_gather_planes(packed, b_rows[:, n0:n0 + LANES])
              for n0 in range(0, b_rows.shape[1], LANES)]

    def row_block(i, row):
        onehot = jnp.concatenate([_onehot(a_col(i, j), r)
                                  for j in range(b_rows.shape[0])], axis=1)
        new = []
        for blk, digits in zip(row, planes):
            for p, d in enumerate(digits):
                part = jnp.dot(onehot, d, preferred_element_type=jnp.int32)
                blk = blk + (part << (8 * p) if p else part)
            new.append(blk)
        return new

    out = []
    for i, row in enumerate(acc):
        if live_rows is None or i == 0:
            out.append(row_block(i, row))
        else:
            out.append(jax.lax.cond(i * ROW_BLOCK < live_rows,
                                    functools.partial(row_block, i),
                                    lambda row: row, row))
    return out


def _piece(acc, a, b_rows, packed, r: int, k: int, live_rows=None):
    """Add the first ``k`` contraction indices of a 128-wide piece: ``a``
    (M, 128) codes and ``b_rows(kt, w)``, the piece's (w, N) code rows from
    a traced ``kt``. A ``fori_loop`` walks the chunks of ``ONEHOT_WIDTH //
    R`` indices (a shorter tail chunk runs after it), so the program holds
    one chunk whatever the contraction length; a chunk takes its code
    columns of ``a`` by lane gathers at the traced index. ``live_rows`` as
    in :func:`_accumulate`."""
    kc = ONEHOT_WIDTH // r
    rows = _row_blocks(a.shape[0])

    def chunk(t, acc, w=kc):
        kt = pl.multiple_of(t * kc, kc)

        def a_col(i, j):
            r0, size = rows[i]
            at = jnp.full((size, LANES), kt + j, jnp.int32)
            return jnp.take_along_axis(a[r0:r0 + size], at, axis=1)

        return _accumulate(acc, a_col, b_rows(kt, w), packed, r, live_rows)

    acc = jax.lax.fori_loop(0, k // kc, chunk, acc)
    return chunk(k // kc, acc, k % kc) if k % kc else acc


def _pick_rows(b, r: int):
    """``b_rows`` of :func:`_piece` for a (128, N) value of codes below
    ``r``: exact int8 one-hot matmuls select the rows, one per signed
    base-256 digit of the codes."""
    digits = _digits(b, 1 if r <= LANES else 2)

    def rows(kt, w):
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                 (32, LANES))
        pick = (iota(1) == kt + iota(0)).astype(jnp.int8)
        out = 0
        for p, d in enumerate(digits):
            part = jnp.dot(pick, d, preferred_element_type=jnp.int32)
            out = out + (part << (8 * p) if p else part)
        return out[:w]

    return rows


def _row_blocks(m: int) -> list[tuple[int, int]]:
    """(first row, rows) of each one-hot row block of ``m`` rows."""
    return [(r0, min(ROW_BLOCK, m - r0)) for r0 in range(0, m, ROW_BLOCK)]


def _zeros(m: int, n: int):
    return [[jnp.zeros((size, LANES), jnp.int32) for _ in range(0, n, LANES)]
            for _, size in _row_blocks(m)]


def _assemble(acc):
    return jnp.concatenate([jnp.concatenate(row, axis=1) for row in acc],
                           axis=0)


def lut_gemm(a, b, lut, *, n_planes: int):
    """``out[m, n] = sum_k lut[a[m, k], b[k, n]]`` as int32.

    ``a``: (M, K) and ``b``: (K, N) int32 codes in table index space;
    ``lut``: the (R, L) padded table, a value or a ref (:func:`pad_lut`).
    Static shapes only; K runs in 128-wide pieces. ``N`` is either below
    128 (indices are lane-padded with code 0 and the result sliced back) or
    a multiple of 128."""
    m, k = a.shape
    n = b.shape[1]
    r = lut.shape[0]
    if n < LANES:
        b = jnp.concatenate([b, jnp.zeros((k, LANES - n), jnp.int32)], axis=1)
    assert b.shape[1] % LANES == 0, b.shape
    packed = _packed_planes(lut, n_planes)
    acc = _zeros(m, b.shape[1])
    for k0 in range(0, k, LANES):
        w = min(LANES, k - k0)
        a_p, b_p = a[:, k0:k0 + w], b[k0:k0 + w]
        if w < LANES:                    # pad entries are never selected
            a_p = jnp.pad(a_p, ((0, 0), (0, LANES - w)))
            b_p = jnp.pad(b_p, ((0, LANES - w), (0, 0)))
        acc = _piece(acc, a_p, _pick_rows(b_p, r), packed, r, w)
    out = _assemble(acc)
    return out[:, :n] if n < LANES else out


def lut_gemm_streamed(load_a, load_b, n_pieces: int, lut, *, m: int, n: int,
                      n_planes: int, live_rows=None):
    """:func:`lut_gemm` over a contraction streamed from refs in 128-wide
    aligned pieces: ``load_a(k0)`` -> (m, 128) codes, ``load_b(k0)`` ->
    (128, n) codes, ``k0`` a multiple of 128; ``n`` a multiple of 128. A
    ``fori_loop`` walks the pieces, so the kernel body does not grow with
    the contraction length.

    ``live_rows``, a traced int32 of at least 1, bounds the rows that
    matter: the one-hots and products of each ``ROW_BLOCK``-row block
    past it are skipped, and those rows come back 0, while the gathered
    planes are still built once for all of the ``m`` rows. ``None`` runs
    every row block."""
    r = lut.shape[0]
    packed = _packed_planes(lut, n_planes)

    def piece(i, acc):
        k0 = pl.multiple_of(i * LANES, LANES)
        return _piece(acc, load_a(k0), _pick_rows(load_b(k0), r), packed, r,
                      LANES, live_rows)

    return _assemble(jax.lax.fori_loop(0, n_pieces, piece, _zeros(m, n)))
