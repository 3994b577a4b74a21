"""Pallas TPU kernels: patch-streaming im2col -> quantize -> LUT-GEMM -> dequant.

One ``pallas_call`` for the whole approximate conv2d forward, in two spatial
flavours that share one tap-accumulate core (:func:`_acc_taps`):

* **whole-image** (:func:`fused_lut_conv_kernel`) — the PR 3 kernel. The
  BlockSpec index maps stream whole padded *images* (the raw input bytes, no
  duplication) into VMEM and keep them resident across the ``(i, j)``
  sub-grid. Bounded to images whose working set fits the VMEM budget.
* **spatially tiled** (:func:`fused_lut_conv_tiled_kernel`) — the PR 4
  kernel that lifts that bound. The grid runs over *output-row bands*; per
  band only the ``(bh-1)*stride + (kh-1)*dilation + 1`` halo'd input rows
  are resident. Pallas block index maps are block-granular, so the
  overlapping halo windows are expressed by passing the padded image
  ``n_copies`` times with row-shifted index maps (``i``, ``i+1``, ...,
  each a ``bh*stride``-row block): band ``i`` sees rows ``[i*S, (i +
  n_copies)*S)`` which cover its halo'd window, and consecutive bands
  re-stream only the ~1 halo block they share — never the whole image,
  never the ``kh*kw``-times-larger patch tensor.

The eager conv path materialized the (N*Ho*Wo, C*kh*kw) im2col patch tensor
in HBM before handing it to ``fused_lut_dense`` — an HBM round-trip
``kh*kw`` times larger than the input itself. Here the patch tensor never
exists anywhere. Per image (whole-image) or per band (tiled) the float block
is quantized ONCE into a persistent int32 VMEM scratch at the first ``j``
step, so the quantizer runs per input pixel — not per patch entry, which
duplicates every pixel up to ``kh*kw`` times in the im2col formulation.
Each grid step then loops over the ``kh*kw`` taps:

1. **tap window slice (VPU)** — a strided ``lax.slice`` of the resident code
   rows picks the ``(C, bh, Wo)`` window for tap ``(u, v)`` under
   (stride, dilation); transposed to a ``(bh*Wo, C)`` operand tile.
2. **LUT gathers** — the (2^b, 2^b) product table is pinned in VMEM for the
   whole grid (same trick as ``fused_lut_dense``); gathers run in ``inner``-
   channel sub-slices against the tap's ``(C, bn)`` weight-code slab.
3. **int32 accumulate** — taps and channel chunks add associatively, so the
   accumulator equals the im2col GEMM's bit for bit, in any order — which is
   also why *any* spatial tiling (whole image, in-kernel bands, mesh-level
   band shards) produces bit-identical outputs.
4. **affine dequant** — ``acc * (x_scale * w_scale[n])``, the same single
   combined-scale multiply as ``fused_lut_dense``; the f32 output strip is
   the only HBM store. ``emit_acc=True`` skips it and emits the raw int32
   accumulator for the channel-contraction-sharded route.

Channel padding (C up to a multiple of ``inner``) feeds shifted code 0
through every tap, contributing ``kh*kw * LUT[off, off] = kh*kw * M[0, 0]``
per padded channel per output; the correction is subtracted *in integer
space* before dequant (``c_pad_corr``), exactly like the K-pad correction in
the dense kernel. Spatial (SAME) padding needs NO correction: the im2col
oracle also quantizes its 0.0 pad entries to shifted code 0, so both paths
accumulate the same ``M[0, 0]`` terms and stay bit-exact.

VMEM: the whole-image kernel holds ``8 * C * Hp * Wp`` bytes of image block
+ code scratch; the tiled kernel holds ``8 * C * (n_copies * bh * sh) * Wp``
— at a 224x224x64 ImageNet-scale layer that is ~26 MiB vs ~450 KiB per band.
``conv_plan`` audits both against the budget and picks the route
(``core.acu._conv_vmem_estimate`` / ``pick_conv_spatial_tiling``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret, round_apart
from jax.experimental.pallas import tpu as pltpu


def _acc_taps(a_img, w, lut, *, n_codes: int, inner: int, kh: int,
              kw: int, sh: int, sw: int, dh: int, dw: int, bh: int,
              wo: int, row0):
    """The shared tap-accumulate core: ``a_img`` is the resident (C, rows,
    cols) shifted-code block (whole image or halo'd band), ``w`` the
    (kh*kw, C, bn) tap-major weight codes, ``lut`` the flat product table.
    Returns the (bh*wo, bn) int32 accumulator for the output-row strip
    whose first tap reads input row ``row0``."""
    c = a_img.shape[0]
    bn = w.shape[2]
    bm = bh * wo
    acc = jnp.zeros((bm, bn), jnp.int32)
    for t in range(kh * kw):                        # static tap loop
        u, v = divmod(t, kw)
        win = jax.lax.dynamic_slice(
            a_img, (0, row0 + u * dh, v * dw),
            (c, (bh - 1) * sh + 1, (wo - 1) * sw + 1))
        win = jax.lax.slice(win, (0, 0, 0), win.shape, (1, sh, sw))  # (C, bh, wo)
        a_t = win.transpose(1, 2, 0).reshape(bm, c)  # (bm, C) patch rows
        w_t = w[t]                                   # (C, bn)

        def body(ci, acc):
            a_sl = jax.lax.dynamic_slice(a_t, (0, ci * inner), (bm, inner))
            w_sl = jax.lax.dynamic_slice(w_t, (ci * inner, 0), (inner, bn))
            idx = a_sl[:, :, None] * n_codes + w_sl[None, :, :]
            prods = jnp.take(lut, idx.reshape(-1), unique_indices=False,
                             indices_are_sorted=False).reshape(bm, inner, bn)
            return acc + prods.sum(axis=1)

        acc = jax.lax.fori_loop(0, c // inner, body, acc)
    return acc


def _quantize_codes(img, xs, xz, *, lo: int, hi: int, offset: int):
    """float block -> shifted codes in LUT index space. Spatial pad pixels
    are 0.0, which quantizes to the zero-point, i.e. index ``offset`` —
    exactly what the im2col oracle's 0.0 patch entries produce."""
    q = jnp.clip(jnp.round(img.astype(jnp.float32) / xs + xz), lo, hi)
    return q.astype(jnp.int32) - xz.astype(jnp.int32) + offset


def _kernel(x_ref, w_ref, lut_ref, xs_ref, xz_ref, ws_ref, o_ref, aimg_ref, *,
            offset: int, n_codes: int, lo: int, hi: int, inner: int,
            kh: int, kw: int, sh: int, sw: int, dh: int, dw: int,
            bh: int, wo: int, c_pad_corr: int, emit_acc: bool):
    i = pl.program_id(1)
    j = pl.program_id(2)
    xs = xs_ref[0]                                  # per-tensor activation scale
    xz = xz_ref[0]                                  # activation zero-point (code)

    @pl.when(jnp.logical_and(i == 0, j == 0))
    def _quantize_image():
        # once per image (scratch persists across the (i, j) sub-grid)
        aimg_ref[...] = _quantize_codes(x_ref[...][0], xs, xz, lo=lo, hi=hi,
                                        offset=offset)

    a_img = aimg_ref[...]                           # (C, Hp, Wp) index space
    w = w_ref[...].astype(jnp.int32) + offset       # (kh*kw, C, bn)
    lut = lut_ref[...]                              # (n_codes * n_codes,)
    bn = w.shape[2]
    row0 = i * bh * sh                              # first input row this strip

    acc = _acc_taps(a_img, w, lut, n_codes=n_codes, inner=inner, kh=kh,
                    kw=kw, sh=sh, sw=sw, dh=dh, dw=dw, bh=bh, wo=wo,
                    row0=row0)

    if c_pad_corr:  # padded channels contributed LUT[off, off] = M[0, 0]
        acc = acc - c_pad_corr * lut[offset * n_codes + offset]
    if emit_acc:
        # channel-contraction sharding: partial int32 accumulators leave the
        # kernel, psum across C shards, dequant once after the collective
        o_ref[...] = acc.reshape(1, bh, wo, bn)
    else:
        # one combined-scale multiply, same expression as fused_lut_dense
        out = acc.astype(jnp.float32) * (xs * ws_ref[...])
        o_ref[...] = out.reshape(1, bh, wo, bn)


@functools.partial(jax.jit, static_argnames=(
    "offset", "n_codes", "lo", "hi", "inner", "kh", "kw", "sh", "sw",
    "dh", "dw", "bh", "bn", "wo", "ho_pad", "c_pad_corr", "interpret",
    "emit_acc"))
def fused_lut_conv_kernel(xp: jnp.ndarray, wq: jnp.ndarray,
                          lut_flat: jnp.ndarray, x_scale: jnp.ndarray,
                          x_zp: jnp.ndarray, w_scale_row: jnp.ndarray, *,
                          offset: int, n_codes: int, lo: int, hi: int,
                          inner: int, kh: int, kw: int, sh: int, sw: int,
                          dh: int, dw: int, bh: int, bn: int, wo: int,
                          ho_pad: int, c_pad_corr: int = 0,
                          interpret: bool | None = None,
                          emit_acc: bool = False) -> jnp.ndarray:
    """Whole-image variant. xp: (N, C, Hp, Wp) float, spatially pre-padded,
    C a multiple of ``inner``; wq: (kh*kw, C, Cout) shifted int weight codes,
    tap-major; lut_flat: (n_codes**2,) int32; x_scale/x_zp: shape-(1,) f32;
    w_scale_row: (1, Cout) f32. Returns (N, ho_pad, Wo, Cout) float32 — or
    the raw int32 accumulator with ``emit_acc=True``."""
    n, c, hp, wp = xp.shape
    cout = wq.shape[2]
    assert c % inner == 0 and cout % bn == 0 and ho_pad % bh == 0, (
        f"conv tiling mismatch: C={c}/inner={inner}, Cout={cout}/bn={bn}, "
        f"Ho_pad={ho_pad}/bh={bh}")
    grid = (n, ho_pad // bh, cout // bn)
    out = pl.pallas_call(
        functools.partial(_kernel, offset=offset, n_codes=n_codes, lo=lo,
                          hi=hi, inner=inner, kh=kh, kw=kw, sh=sh, sw=sw,
                          dh=dh, dw=dw, bh=bh, wo=wo, c_pad_corr=c_pad_corr,
                          emit_acc=emit_acc),
        name="fused_lut_conv_kernel",
        grid=grid,
        in_specs=[
            # the whole padded image streams in once per n (the block index
            # is constant over the (i, j) sub-grid) — raw input bytes, never
            # the kh*kw-times-larger patch tensor
            pl.BlockSpec((1, c, hp, wp), lambda n, i, j: (n, 0, 0, 0)),
            pl.BlockSpec((kh * kw, c, bn), lambda n, i, j: (0, 0, j)),
            pl.BlockSpec((n_codes * n_codes,), lambda n, i, j: (0,)),
            pl.BlockSpec((1,), lambda n, i, j: (0,)),
            pl.BlockSpec((1,), lambda n, i, j: (0,)),
            pl.BlockSpec((1, bn), lambda n, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bh, wo, bn), lambda n, i, j: (n, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n, ho_pad, wo, cout), jnp.int32 if emit_acc else jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, hp, wp), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(xp, wq, lut_flat, x_scale, x_zp, w_scale_row)
    return round_apart(out, resolve_interpret(interpret))


def _bwd_w_kernel(*refs, offset: int, n_codes: int, lo: int, hi: int,
                  mc: int, kh: int, kw: int, sh: int, sw: int, dh: int,
                  dw: int, bh: int, wo: int, n_copies: int, pad_m: int):
    """Banded conv weight-grad: ``gw[t*C + ci, o] = sum_p M[x_tap, g]``.

    The contraction runs over output *pixels* — the rows of the implicit
    im2col GEMM — so the grid streams the same halo'd input-row bands as the
    tiled forward (``n_copies`` row-shifted blocks) plus the matching
    ``(bh, Wo, bn)`` strip of the incoming gradient, and the ``(kh*kw*C, bn)``
    accumulator persists in VMEM across every ``(n, band)`` step (the Cout
    grid dim is outermost so the scratch is coherent per ``j``). Both
    operands are float residuals quantized in-kernel per-tensor *symmetric*
    (zero-point 0), like the dense backward kernel.

    ``rmask`` is an explicit 0/1 input: output rows past ``Ho`` (band
    alignment padding — and, under the mesh wrap, dead band-slab rows)
    contribute ``M[x, 0]`` per product, which is *not* a constant, so they
    are masked multiplicatively before the pixel sum instead of corrected
    after it. Patch rows pad to a ``mc`` multiple with mask 0 the same way.
    Spatial 0.0 padding needs no mask: the im2col oracle's patch tensor
    carries the same quantized-zero codes. The kernel always emits the raw
    int32 accumulator — the planning layer owns the single combined-scale
    dequant (and the mesh route psums these partials over band shards first).
    """
    x_refs = refs[:n_copies]
    (g_ref, rm_ref, lut_ref, xs_ref, gs_ref, o_ref, acc_ref) = refs[n_copies:]
    n_i = pl.program_id(1)
    i = pl.program_id(2)
    first = jnp.logical_and(n_i == 0, i == 0)
    last = jnp.logical_and(n_i == pl.num_programs(1) - 1,
                           i == pl.num_programs(2) - 1)

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xs = xs_ref[0]
    gs = gs_ref[0]
    # re-quantized once per (j; n, band) step — j outermost means each band
    # is revisited per Cout tile, the price of a coherent gw accumulator;
    # the quantizer is deterministic so every visit produces the same codes
    band = jnp.concatenate([r[...][0] for r in x_refs], axis=1)
    a_band = jnp.clip(jnp.round(band.astype(jnp.float32) / xs), lo, hi
                      ).astype(jnp.int32) + offset      # (C, rows, Wp)
    gq = jnp.clip(jnp.round(g_ref[...][0].astype(jnp.float32) / gs), lo, hi
                  ).astype(jnp.int32) + offset          # (bh, wo, bn)
    lut = lut_ref[...]
    c = a_band.shape[0]
    bn = gq.shape[2]
    bm = bh * wo
    g2 = gq.reshape(bm, bn)
    mask = jnp.broadcast_to(rm_ref[...].reshape(bh, 1),
                            (bh, wo)).reshape(bm, 1)    # 0/1 row validity
    if pad_m:  # patch rows up to a mc multiple; padded rows mask to 0
        g2 = jnp.pad(g2, ((0, pad_m), (0, 0)))
        mask = jnp.pad(mask, ((0, pad_m), (0, 0)))
    nm = (bm + pad_m) // mc

    taps = []
    for t in range(kh * kw):                            # static tap loop
        u, v = divmod(t, kw)
        win = jax.lax.dynamic_slice(
            a_band, (0, u * dh, v * dw),
            (c, (bh - 1) * sh + 1, (wo - 1) * sw + 1))
        win = jax.lax.slice(win, (0, 0, 0), win.shape, (1, sh, sw))
        a_t = win.transpose(1, 2, 0).reshape(bm, c)     # (bm, C) patch rows
        if pad_m:
            a_t = jnp.pad(a_t, ((0, pad_m), (0, 0)))

        def body(mi, acc_t, a_t=a_t):
            a_sl = jax.lax.dynamic_slice(a_t, (mi * mc, 0), (mc, c))
            g_sl = jax.lax.dynamic_slice(g2, (mi * mc, 0), (mc, bn))
            m_sl = jax.lax.dynamic_slice(mask, (mi * mc, 0), (mc, 1))
            idx = a_sl[:, :, None] * n_codes + g_sl[:, None, :]  # (mc, C, bn)
            prods = jnp.take(lut, idx.reshape(-1), unique_indices=False,
                             indices_are_sorted=False).reshape(mc, c, bn)
            return acc_t + (prods * m_sl[:, :, None]).sum(axis=0)

        taps.append(jax.lax.fori_loop(0, nm, body,
                                      jnp.zeros((c, bn), jnp.int32)))

    acc_ref[...] += jnp.concatenate(taps, axis=0)       # (kh*kw*C, bn)

    @pl.when(last)
    def _emit():
        o_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "offset", "n_codes", "lo", "hi", "mc", "kh", "kw", "sh", "sw", "dh",
    "dw", "bh", "bn", "wo", "ho_pad", "n_copies", "interpret"))
def fused_lut_conv_bwd_w_kernel(xp: jnp.ndarray, g: jnp.ndarray,
                                rmask: jnp.ndarray, lut_flat: jnp.ndarray,
                                x_scale: jnp.ndarray, g_scale: jnp.ndarray, *,
                                offset: int, n_codes: int, lo: int, hi: int,
                                mc: int, kh: int, kw: int, sh: int, sw: int,
                                dh: int, dw: int, bh: int, bn: int, wo: int,
                                ho_pad: int, n_copies: int,
                                interpret: bool | None = None) -> jnp.ndarray:
    """Banded approximate conv weight-grad. ``xp``: (N, C, Hp, Wp) float
    residuals, spatially pre-padded like the tiled forward (rows to
    ``(n_bands + n_copies - 1) * bh * sh``); ``g``: (N, ho_pad, Wo, Cout)
    float incoming gradient; ``rmask``: (N, ho_pad) int32 0/1 output-row
    validity; scales: shape-(1,) f32 per-tensor symmetric. Returns the raw
    (kh*kw*C, Cout) int32 accumulator, tap-major — the full ``(N*Ho*Wo,
    kh*kw*C)`` patch tensor never exists anywhere."""
    n, c, hp, wp = xp.shape
    cout = g.shape[3]
    n_bands = ho_pad // bh
    s_rows = bh * sh
    bm = bh * wo
    assert cout % bn == 0 and ho_pad % bh == 0, (
        f"conv bwd tiling mismatch: Cout={cout}/bn={bn}, "
        f"Ho_pad={ho_pad}/bh={bh}")
    assert hp == (n_bands + n_copies - 1) * s_rows, (
        f"banded row padding mismatch: Hp={hp} != "
        f"({n_bands} + {n_copies} - 1) * {s_rows}")
    grid = (cout // bn, n, n_bands)   # j outermost: acc coherent per j

    def x_spec(k):
        return pl.BlockSpec((1, c, s_rows, wp),
                            lambda j, n, i, k=k: (n, 0, i + k, 0))

    return pl.pallas_call(
        functools.partial(_bwd_w_kernel, offset=offset, n_codes=n_codes,
                          lo=lo, hi=hi, mc=mc, kh=kh, kw=kw, sh=sh, sw=sw,
                          dh=dh, dw=dw, bh=bh, wo=wo, n_copies=n_copies,
                          pad_m=(-bm) % mc),
        name="fused_lut_conv_bwd_w_kernel",
        grid=grid,
        in_specs=[x_spec(k) for k in range(n_copies)] + [
            pl.BlockSpec((1, bh, wo, bn), lambda j, n, i: (n, i, 0, j)),
            pl.BlockSpec((1, bh), lambda j, n, i: (n, i)),
            pl.BlockSpec((n_codes * n_codes,), lambda j, n, i: (0,)),
            pl.BlockSpec((1,), lambda j, n, i: (0,)),
            pl.BlockSpec((1,), lambda j, n, i: (0,)),
        ],
        out_specs=pl.BlockSpec((kh * kw * c, bn), lambda j, n, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((kh * kw * c, cout), jnp.int32),
        scratch_shapes=[pltpu.VMEM((kh * kw * c, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*([xp] * n_copies), g, rmask, lut_flat, x_scale, g_scale)


def _tiled_kernel(*refs, offset: int, n_codes: int, lo: int, hi: int,
                  inner: int, kh: int, kw: int, sh: int, sw: int, dh: int,
                  dw: int, bh: int, wo: int, n_copies: int, c_pad_corr: int,
                  emit_acc: bool):
    x_refs = refs[:n_copies]
    w_ref, lut_ref, xs_ref, xz_ref, ws_ref, o_ref, aband_ref = refs[n_copies:]
    j = pl.program_id(2)
    xs = xs_ref[0]
    xz = xz_ref[0]

    @pl.when(j == 0)
    def _quantize_band():
        # once per (n, band): the n_copies row-shifted blocks concatenate to
        # the halo'd band [i*S, (i + n_copies)*S); quantized codes persist in
        # the band scratch across the Cout sub-grid. Halo rows shared with
        # the neighbouring band are re-quantized there — the quantizer is
        # deterministic, so the codes (and the accumulators built from them)
        # are identical either way.
        band = jnp.concatenate([r[...][0] for r in x_refs], axis=1)
        aband_ref[...] = _quantize_codes(band, xs, xz, lo=lo, hi=hi,
                                         offset=offset)

    a_band = aband_ref[...]                         # (C, n_copies*S, Wp)
    w = w_ref[...].astype(jnp.int32) + offset       # (kh*kw, C, bn)
    lut = lut_ref[...]
    bn = w.shape[2]

    # band-local coordinates: the band block already starts at input row
    # i*bh*sh, so every tap offset is static (row0 = 0)
    acc = _acc_taps(a_band, w, lut, n_codes=n_codes, inner=inner, kh=kh,
                    kw=kw, sh=sh, sw=sw, dh=dh, dw=dw, bh=bh, wo=wo,
                    row0=0)

    if c_pad_corr:
        acc = acc - c_pad_corr * lut[offset * n_codes + offset]
    if emit_acc:
        o_ref[...] = acc.reshape(1, bh, wo, bn)
    else:
        out = acc.astype(jnp.float32) * (xs * ws_ref[...])
        o_ref[...] = out.reshape(1, bh, wo, bn)


@functools.partial(jax.jit, static_argnames=(
    "offset", "n_codes", "lo", "hi", "inner", "kh", "kw", "sh", "sw",
    "dh", "dw", "bh", "bn", "wo", "ho_pad", "n_copies", "c_pad_corr",
    "interpret", "emit_acc"))
def fused_lut_conv_tiled_kernel(xp: jnp.ndarray, wq: jnp.ndarray,
                                lut_flat: jnp.ndarray, x_scale: jnp.ndarray,
                                x_zp: jnp.ndarray, w_scale_row: jnp.ndarray,
                                *, offset: int, n_codes: int, lo: int,
                                hi: int, inner: int, kh: int, kw: int,
                                sh: int, sw: int, dh: int, dw: int, bh: int,
                                bn: int, wo: int, ho_pad: int, n_copies: int,
                                c_pad_corr: int = 0, interpret: bool | None = None,
                                emit_acc: bool = False) -> jnp.ndarray:
    """Spatially-tiled variant. Same operand layout as
    :func:`fused_lut_conv_kernel`, but ``xp`` rows must be padded to
    ``(ho_pad // bh + n_copies - 1) * bh * sh`` so the ``n_copies``
    row-shifted input blocks stay in bounds for the last band. Only the
    halo'd band — never the whole image — is VMEM-resident per grid step."""
    n, c, hp, wp = xp.shape
    cout = wq.shape[2]
    n_bands = ho_pad // bh
    s_rows = bh * sh
    assert c % inner == 0 and cout % bn == 0 and ho_pad % bh == 0, (
        f"conv tiling mismatch: C={c}/inner={inner}, Cout={cout}/bn={bn}, "
        f"Ho_pad={ho_pad}/bh={bh}")
    assert hp == (n_bands + n_copies - 1) * s_rows, (
        f"banded row padding mismatch: Hp={hp} != "
        f"({n_bands} + {n_copies} - 1) * {s_rows}")
    grid = (n, n_bands, cout // bn)

    def x_spec(k):
        # block k of the halo stack: rows [(i + k)*S, (i + k + 1)*S)
        return pl.BlockSpec((1, c, s_rows, wp),
                            lambda n, i, j, k=k: (n, 0, i + k, 0))

    out = pl.pallas_call(
        functools.partial(_tiled_kernel, offset=offset, n_codes=n_codes,
                          lo=lo, hi=hi, inner=inner, kh=kh, kw=kw, sh=sh,
                          sw=sw, dh=dh, dw=dw, bh=bh, wo=wo,
                          n_copies=n_copies, c_pad_corr=c_pad_corr,
                          emit_acc=emit_acc),
        name="fused_lut_conv_tiled_kernel",
        grid=grid,
        in_specs=[x_spec(k) for k in range(n_copies)] + [
            pl.BlockSpec((kh * kw, c, bn), lambda n, i, j: (0, 0, j)),
            pl.BlockSpec((n_codes * n_codes,), lambda n, i, j: (0,)),
            pl.BlockSpec((1,), lambda n, i, j: (0,)),
            pl.BlockSpec((1,), lambda n, i, j: (0,)),
            pl.BlockSpec((1, bn), lambda n, i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((1, bh, wo, bn), lambda n, i, j: (n, i, 0, j)),
        out_shape=jax.ShapeDtypeStruct(
            (n, ho_pad, wo, cout), jnp.int32 if emit_acc else jnp.float32),
        scratch_shapes=[pltpu.VMEM((c, n_copies * s_rows, wp), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(*([xp] * n_copies), wq, lut_flat, x_scale, x_zp, w_scale_row)
    return round_apart(out, resolve_interpret(interpret))
