"""Patch-streaming fused conv: bit-exactness vs the eager im2col +
``fused_lut_dense`` oracle — the exact path the kernel retired.

"Bit-exact" is literal float equality: the fused kernel must perform the
same per-pixel quantize, the same int32 accumulate (taps and channel chunks
add associatively; channel padding corrected in *integer* space), and the
same single combined-scale dequant as the eager route. ``conv2d(...,
route="im2col")`` pins that oracle with the same quantizers, so the two
public routes are comparable end to end — eager and jit, with bias, and
through the STE backward.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hypothesis import assume, given, settings, strategies as st
from repro.core import build_lut, get_multiplier, make_acu
from repro.core.acu import (AcuMode, ConvSpec, conv_plan,
                            resolve_conv_padding)
from repro.core.approx_ops import ApproxConfig, conv2d, conv_plan_report
from repro.core.multipliers import make_exact
from repro.core.quantization import acu_operand, quantize, symmetric_qparams
from repro.kernels.fused_lut_conv.ops import (fused_lut_conv,
                                              fused_lut_conv_tiled)
from repro.kernels.fused_lut_conv.ref import fused_lut_conv_ref

MULT = get_multiplier("mul8s_1L2H")
LUT = jnp.asarray(build_lut(MULT))
ACU_FUSED = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True, fused=True)
CFG = ApproxConfig(acu=ACU_FUSED)


def _conv_operands(shape, wshape, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=shape), jnp.float32)
    w = jnp.asarray(rng.normal(size=wshape), jnp.float32)
    return x, w


# ---------------------------------------------------------------------------
# kernel vs its own pure-jnp reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("geom", [
    # (x_shape, w_shape, stride, padding, dilation)
    ((2, 3, 12, 12), (5, 3, 3, 3), (1, 1), "SAME", (1, 1)),
    ((1, 8, 9, 9), (4, 8, 3, 3), (2, 2), "SAME", (1, 1)),      # stride > 1
    ((2, 5, 10, 10), (6, 5, 3, 3), (1, 1), "SAME", (2, 2)),    # dilation > 1
    ((1, 6, 11, 5), (9, 6, 3, 3), (2, 1), "VALID", (1, 1)),    # mixed stride
    ((1, 4, 7, 7), (3, 4, 1, 1), (1, 1), "VALID", (1, 1)),     # pointwise
    ((2, 40, 6, 6), (7, 40, 3, 3), (1, 1), "SAME", (1, 1)),    # C pad to inner
    ((1, 3, 13, 13), (5, 3, 5, 5), (3, 3), "SAME", (1, 1)),    # 5x5, stride 3
])
def test_kernel_matches_ref(geom):
    """Edge geometry sweep: stride>1, dilation>1, non-divisible spatial
    tiles, channel padding — kernel output equals the im2col oracle
    bitwise."""
    shape, wshape, stride, padding, dilation = geom
    x, w = _conv_operands(shape, wshape, seed=sum(shape))
    pad = resolve_conv_padding(padding, shape, wshape, stride, dilation)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(
        jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-9), 8, axis=0)
    wq = acu_operand(quantize(w, wqp), wqp)
    out = fused_lut_conv(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                         wqp.scale, stride=stride, padding=pad,
                         dilation=dilation, bits=8, interpret=True)
    ref = fused_lut_conv_ref(x, wq, LUT.reshape(-1), 128, 256, xqp.scale,
                             xqp.zero_point, wqp.scale, stride=stride,
                             padding=pad, dilation=dilation, bits=8)
    assert jnp.array_equal(out, ref)


def test_kernel_biased_m00_channel_pad():
    """Channel padding contributes kh*kw * LUT[off, off] = kh*kw * M[0, 0]
    per padded channel; the kernel must subtract it in integer space.
    Exercised with a synthetic multiplier whose M[0, 0] = 7 (every
    registered family has M[0, 0] == 0) at C=5, which pads to the gather
    chunk."""
    biased = dataclasses.replace(
        make_exact(8), name="mul8s_biased",
        fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
    lut = jnp.asarray(build_lut(biased))
    assert int(lut[128, 128]) == 7
    x, w = _conv_operands((2, 5, 7, 7), (4, 5, 3, 3), seed=5)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(
        jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-9), 8, axis=0)
    wq = acu_operand(quantize(w, wqp), wqp)
    pad = ((1, 1), (1, 1))
    out = fused_lut_conv(x, wq, lut, 128, xqp.scale, xqp.zero_point,
                         wqp.scale, padding=pad, bits=8, interpret=True)
    ref = fused_lut_conv_ref(x, wq, lut.reshape(-1), 128, 256, xqp.scale,
                             xqp.zero_point, wqp.scale, padding=pad, bits=8)
    assert jnp.array_equal(out, ref)


def test_kernel_emit_acc_is_raw_accumulator():
    """emit_acc=True returns the int32 accumulator (channel padding already
    corrected) — what the channel-contraction route psums — and dequantizing
    it reproduces the normal output bitwise."""
    x, w = _conv_operands((1, 6, 8, 8), (5, 6, 3, 3), seed=13)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(
        jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-9), 8, axis=0)
    wq = acu_operand(quantize(w, wqp), wqp)
    pad = ((1, 1), (1, 1))
    acc = fused_lut_conv(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                         wqp.scale, padding=pad, bits=8, interpret=True,
                         emit_acc=True)
    assert acc.dtype == jnp.int32
    out = fused_lut_conv(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                         wqp.scale, padding=pad, bits=8, interpret=True)
    dq = acc.astype(jnp.float32) * \
        (xqp.scale * wqp.scale.reshape(1, 1, 1, -1))
    assert jnp.array_equal(out, dq)


# ---------------------------------------------------------------------------
# public conv2d: fused route vs the pinned eager-im2col oracle route
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("geom", [
    ((2, 3, 12, 12), (5, 3, 3, 3), dict()),
    ((1, 8, 9, 9), (4, 8, 3, 3), dict(stride=(2, 2))),
    ((2, 5, 10, 10), (6, 5, 3, 3), dict(dilation=(2, 2))),
    ((1, 6, 11, 5), (9, 6, 3, 3), dict(stride=(2, 1), padding="VALID")),
    ((1, 4, 7, 7), (3, 4, 1, 1), dict(padding="VALID")),
])
def test_conv2d_fused_equals_im2col_route(geom):
    """End to end with bias, eager AND jit: conv2d through the fused plan
    equals conv2d pinned to the eager im2col route, bitwise, within each
    execution regime."""
    shape, wshape, kw_ = geom
    x, w = _conv_operands(shape, wshape, seed=sum(shape) + 1)
    b = jnp.asarray(np.random.default_rng(9).normal(size=(wshape[0],)),
                    jnp.float32)
    y_f = conv2d(x, w, b, cfg=CFG, **kw_)
    y_o = conv2d(x, w, b, cfg=CFG, route="im2col", **kw_)
    assert jnp.array_equal(y_f, y_o)
    j_f = jax.jit(lambda x, w, b: conv2d(x, w, b, cfg=CFG, **kw_))(x, w, b)
    j_o = jax.jit(lambda x, w, b: conv2d(x, w, b, cfg=CFG, route="im2col",
                                         **kw_))(x, w, b)
    assert jnp.array_equal(j_f, j_o)


def test_conv2d_grouped_keeps_vmapped_gemm_route():
    """groups>1 resolves to the single-vmapped-GEMM route (PR 2 semantics)
    and still matches lax.conv to quantization tolerance."""
    x, w = _conv_operands((2, 8, 8, 8), (8, 4, 3, 3), seed=3)
    spec = ConvSpec(x_shape=(2, 8, 8, 8), w_shape=(8, 4, 3, 3),
                    padding=((1, 1), (1, 1)), groups=2)
    plan = conv_plan(ACU_FUSED, spec, fused=True)
    assert plan.route == "im2col_grouped"
    assert any("groups" in r for r in plan.report)
    cfg12 = ApproxConfig(acu=make_acu("mul12s_exact", AcuMode.EXACT),
                         a_bits=12, w_bits=12)
    ours = conv2d(x, w, groups=2, cfg=cfg12)
    ref = jax.lax.conv_general_dilated(
        x, w, (1, 1), "SAME", feature_group_count=2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    rel = float(jnp.abs(ours - ref).max() / (jnp.abs(ref).max() + 1e-9))
    assert rel < 5e-3


def test_conv2d_ste_backward_matches_im2col_route():
    """QAT: gradients through the fused forward are bitwise identical to the
    eager route's STE gradients (same fake-quant residuals, same GEMMs)."""
    x, w = _conv_operands((2, 3, 8, 8), (5, 3, 3, 3), seed=4)

    def loss(x, w, route):
        return (conv2d(x, w, None, cfg=CFG, route=route) ** 2).sum()

    gx_f, gw_f = jax.grad(loss, argnums=(0, 1))(x, w, None)
    gx_o, gw_o = jax.grad(loss, argnums=(0, 1))(x, w, "im2col")
    assert jnp.array_equal(gx_f, gx_o)
    assert jnp.array_equal(gw_f, gw_o)


# ---------------------------------------------------------------------------
# plan resolution
# ---------------------------------------------------------------------------

def test_conv_plan_routing():
    spec = ConvSpec(x_shape=(2, 3, 12, 12), w_shape=(5, 3, 3, 3),
                    padding=((1, 1), (1, 1)))
    assert conv_plan(ACU_FUSED, spec).route == "fused_conv"
    assert conv_plan(ACU_FUSED, spec, fused=False).route == "im2col"
    # non-Pallas LUT: audited fallback
    jnp_acu = make_acu("mul8s_1L2H", AcuMode.LUT)
    plan = conv_plan(jnp_acu, spec, fused=True)
    assert plan.route == "im2col"
    assert any("use_pallas" in r for r in plan.report)
    # FUNCTIONAL mode can't fuse either
    func = make_acu("mul8s_1L2H", AcuMode.FUNCTIONAL, use_pallas=True)
    assert conv_plan(func, spec, fused=True).route == "im2col"
    # depthwise keeps its block-diagonal route
    dspec = ConvSpec(x_shape=(2, 6, 8, 8), w_shape=(6, 1, 3, 3),
                     padding=((1, 1), (1, 1)), groups=6)
    assert conv_plan(ACU_FUSED, dspec).route == "im2col_depthwise"
    # pinning fused_conv on an unservable request raises instead of falling
    with pytest.raises(ValueError):
        conv_plan(jnp_acu, spec, route="fused_conv")


def test_conv2d_route_pin_fused_on_unfused_cfg():
    """route="fused_conv" forces the fused kernel even when the config
    doesn't default to fusion — and matches the fused-by-default result
    bitwise (same plan, same quantizers)."""
    x, w = _conv_operands((1, 3, 8, 8), (4, 3, 3, 3), seed=6)
    plain = ApproxConfig(acu=make_acu("mul8s_1L2H", AcuMode.LUT,
                                      use_pallas=True))  # fused=False default
    y_pin = conv2d(x, w, None, cfg=plain, route="fused_conv")
    y_def = conv2d(x, w, None, cfg=CFG)
    assert jnp.array_equal(y_pin, y_def)


def test_conv2d_fake_quant_only_never_hits_the_integer_kernel():
    """fake_quant_only must run the fake-quant QAT forward on every route:
    the default pins the eager path, and pinning the fused route explicitly
    is a caller error, not a silent integer-GEMM forward."""
    x, w = _conv_operands((1, 3, 6, 6), (4, 3, 3, 3), seed=2)
    fq = ApproxConfig(acu=ACU_FUSED, fake_quant_only=True)
    y = conv2d(x, w, None, cfg=fq)
    y_ref = conv2d(x, w, None, cfg=ApproxConfig(
        acu=make_acu("mul8s_1L2H", AcuMode.LUT), fake_quant_only=True))
    assert jnp.array_equal(y, y_ref)
    with pytest.raises(ValueError):
        conv2d(x, w, None, cfg=fq, route="fused_conv")


def test_conv_plan_vmem_resolves_tiled():
    """Images whose whole-image working set exceeds the VMEM budget resolve
    to the spatially-tiled kernel (NOT the eager fallback) with an audited
    report naming the chosen banding."""
    spec = ConvSpec(x_shape=(1, 64, 224, 224), w_shape=(64, 64, 3, 3),
                    padding=((1, 1), (1, 1)))
    plan = conv_plan(ACU_FUSED, spec, fused=True)
    assert plan.route == "tiled"
    assert plan.tiling is not None
    assert plan.fn is not None
    assert any("spatially tiled" in r for r in plan.report)
    assert not any("im2col" in r for r in plan.report)


def test_conv_plan_report_shape():
    rep = conv_plan_report((2, 3, 12, 12), (5, 3, 3, 3), CFG)
    assert rep["route"] == "fused_conv" and rep["fused"]
    assert rep["partition"] is None          # no active mesh
    assert rep["gemm"] == "M=288 K=27 N=5"


def test_resolve_conv_padding_matches_xla_same():
    """Our SAME split must agree with XLA's (lo = total // 2) so the fused
    kernel, the eager patches route, and lax.conv see identical geometry."""
    for (hw, k, s, d) in [((12, 12), 3, (1, 1), (1, 1)),
                          ((9, 9), 3, (2, 2), (1, 1)),
                          ((10, 7), 5, (2, 3), (2, 1))]:
        x_shape = (1, 2, *hw)
        w_shape = (3, 2, k, k)
        pad = resolve_conv_padding("SAME", x_shape, w_shape, s, d)
        x = jnp.zeros(x_shape)
        w = jnp.zeros(w_shape)
        args = dict(window_strides=s, rhs_dilation=d,
                    dimension_numbers=("NCHW", "OIHW", "NCHW"))
        ref = jax.lax.conv_general_dilated(x, w, padding="SAME", **args)
        ours = jax.lax.conv_general_dilated(x, w, padding=pad, **args)
        assert ours.shape == ref.shape, (hw, k, s, d, pad)


# ---------------------------------------------------------------------------
# spatially-tiled kernel (PR 4): tiled == whole-image == eager oracle
# ---------------------------------------------------------------------------

def _quantized_operands(x, w):
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(
        jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-9), 8, axis=0)
    return xqp, wqp, acu_operand(quantize(w, wqp), wqp)


def test_tiled_kernel_matches_whole_and_ref_across_band_heights():
    """Any band height is bit-identical: int32 tap accumulation is
    order-independent, so tiling only moves work between grid steps."""
    x, w = _conv_operands((2, 5, 13, 11), (6, 5, 3, 3), seed=21)
    xqp, wqp, wq = _quantized_operands(x, w)
    pad = ((1, 1), (1, 1))
    ref = fused_lut_conv_ref(x, wq, LUT.reshape(-1), 128, 256, xqp.scale,
                             xqp.zero_point, wqp.scale, padding=pad, bits=8)
    whole = fused_lut_conv(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                           wqp.scale, padding=pad, bits=8, interpret=True)
    assert jnp.array_equal(whole, ref)
    for bh in (1, 2, 3, 5, 13):
        tiled = fused_lut_conv_tiled(x, wq, LUT, 128, xqp.scale,
                                     xqp.zero_point, wqp.scale, padding=pad,
                                     bits=8, bh=bh, interpret=True)
        assert jnp.array_equal(tiled, ref), bh


def test_tiled_kernel_biased_m00_channel_pad():
    """The tiled kernel's integer-space channel-pad correction, exercised
    with a synthetic M[0, 0] = 7 multiplier at C=5 (pads to the gather
    chunk)."""
    biased = dataclasses.replace(
        make_exact(8), name="mul8s_biased",
        fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
    lut = jnp.asarray(build_lut(biased))
    x, w = _conv_operands((2, 5, 9, 7), (4, 5, 3, 3), seed=23)
    xqp, wqp, wq = _quantized_operands(x, w)
    pad = ((1, 1), (1, 1))
    ref = fused_lut_conv_ref(x, wq, lut.reshape(-1), 128, 256, xqp.scale,
                             xqp.zero_point, wqp.scale, padding=pad, bits=8)
    for bh in (1, 2, 4):
        tiled = fused_lut_conv_tiled(x, wq, lut, 128, xqp.scale,
                                     xqp.zero_point, wqp.scale, padding=pad,
                                     bits=8, bh=bh, interpret=True)
        assert jnp.array_equal(tiled, ref), bh


def test_tiled_kernel_emit_acc_is_raw_accumulator():
    """emit_acc=True on the tiled kernel returns the int32 accumulator
    (channel padding already corrected) — what the channel-contraction
    route psums — and dequantizing it reproduces the whole-image output
    bitwise."""
    x, w = _conv_operands((1, 6, 10, 8), (5, 6, 3, 3), seed=29)
    xqp, wqp, wq = _quantized_operands(x, w)
    pad = ((1, 1), (1, 1))
    acc = fused_lut_conv_tiled(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                               wqp.scale, padding=pad, bits=8, bh=2,
                               interpret=True, emit_acc=True)
    assert acc.dtype == jnp.int32
    ref = fused_lut_conv(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                         wqp.scale, padding=pad, bits=8, interpret=True)
    dq = acc.astype(jnp.float32) * \
        (xqp.scale * wqp.scale.reshape(1, 1, 1, -1))
    assert jnp.array_equal(dq, ref)


def test_conv2d_route_pin_tiled():
    """route="tiled" forces the spatially-tiled kernel on a fits-in-VMEM
    image and matches the whole-image fused route and the eager oracle
    bitwise, eager and jit; fake_quant_only contradicts the pin."""
    x, w = _conv_operands((2, 4, 11, 9), (5, 4, 3, 3), seed=31)
    b = jnp.asarray(np.random.default_rng(31).normal(size=(5,)), jnp.float32)
    y_t = conv2d(x, w, b, cfg=CFG, route="tiled")
    y_f = conv2d(x, w, b, cfg=CFG)
    y_o = conv2d(x, w, b, cfg=CFG, route="im2col")
    assert jnp.array_equal(y_t, y_o)
    assert jnp.array_equal(y_f, y_o)
    j_t = jax.jit(lambda x, w: conv2d(x, w, None, cfg=CFG,
                                      route="tiled"))(x, w)
    j_o = jax.jit(lambda x, w: conv2d(x, w, None, cfg=CFG,
                                      route="im2col"))(x, w)
    assert jnp.array_equal(j_t, j_o)
    fq = ApproxConfig(acu=ACU_FUSED, fake_quant_only=True)
    with pytest.raises(ValueError):
        conv2d(x, w, None, cfg=fq, route="tiled")


def test_conv2d_tiled_ste_backward_matches_im2col_route():
    """QAT through the tiled forward: gradients bitwise identical to the
    eager route's STE gradients."""
    x, w = _conv_operands((2, 3, 10, 10), (5, 3, 3, 3), seed=37)

    def loss(x, w, route):
        return (conv2d(x, w, None, cfg=CFG, route=route) ** 2).sum()

    gx_t, gw_t = jax.grad(loss, argnums=(0, 1))(x, w, "tiled")
    gx_o, gw_o = jax.grad(loss, argnums=(0, 1))(x, w, "im2col")
    assert jnp.array_equal(gx_t, gx_o)
    assert jnp.array_equal(gw_t, gw_o)


def test_vmem_estimate_matches_kernel_allocation():
    """Regression for the pre-PR 4 VMEM model bug: the estimate must count
    the exact padded extents the kernel allocates — including the
    (kh-1)*dilation halo rows a stride-only model misses — so near-budget
    dilated convs can never pick an overflowing tile. Pinned against the
    geometry helper the kernel wrapper itself pads with."""
    from repro.kernels.fused_lut_conv.ops import (conv_padded_geometry,
                                                  conv_vmem_bytes,
                                                  pick_conv_tiling)
    # dilation=3: the dilated tap span (kh-1)*dh = 12 dwarfs bh*sh
    geoms = [
        (8, 20, 20, 8, 5, 5, 1, 1, 3, 3, ((6, 6), (6, 6))),
        (16, 30, 14, 32, 3, 3, 2, 2, 2, 2, ((2, 2), (2, 2))),
        (4, 9, 33, 4, 3, 3, 1, 1, 1, 1, ((1, 1), (1, 1))),
    ]
    for (c, h, w, cout, kh, kw, sh, sw, dh, dw, pad) in geoms:
        ho, wo, _, _, _ = conv_padded_geometry(h, w, kh, kw, sh, sw, dh, dw,
                                               pad, 1)
        inner, bh, bn = pick_conv_tiling(c, ho, wo, cout)
        _, _, _, hp, wp = conv_padded_geometry(h, w, kh, kw, sh, sw, dh, dw,
                                               pad, bh)
        c_pad = c + (-c) % inner
        est = conv_vmem_bytes(c, h, w, cout, kh, kw, sh, sw, dh, dw, pad, 256)
        # the image-block + scratch term must cover the kernel's actual
        # (C_pad, Hp, Wp) f32 block and int32 scratch allocation
        assert est >= 8 * c_pad * hp * wp, (c, h, w, est)
        # and the whole estimate is what conv_plan budgets against
        from repro.core.acu import ConvSpec, _conv_vmem_estimate
        spec = ConvSpec(x_shape=(1, c, h, w), w_shape=(cout, c, kh, kw),
                        stride=(sh, sw), padding=pad, dilation=(dh, dw))
        assert _conv_vmem_estimate(spec, 256) == est


def test_spatial_tiling_pick_respects_budget():
    """pick_conv_spatial_tiling returns a banding whose modeled working set
    fits the budget, and None when even a one-row band cannot."""
    from repro.kernels.fused_lut_conv.ops import (conv_tiled_vmem_bytes,
                                                  pick_conv_spatial_tiling)
    args = (64, 224, 224, 64, 3, 3, 1, 1, 1, 1, ((1, 1), (1, 1)), 256)
    tiling = pick_conv_spatial_tiling(*args)
    assert tiling is not None
    inner, bh, bn, n_copies = tiling
    assert conv_tiled_vmem_bytes(*args[:-1], 256, inner=inner, bh=bh,
                                 bn=bn) <= 12 << 20
    # a taller band would not have fit (the pick is the tallest feasible)
    if bh < 64:
        assert conv_tiled_vmem_bytes(*args[:-1], 256, inner=inner, bh=bh + 1,
                                     bn=bn) > 12 << 20
    # LUT alone (256 KiB) over budget -> no feasible band
    assert pick_conv_spatial_tiling(*args, budget=128 << 10) is None


# ---------------------------------------------------------------------------
# property-based tiling harness: hypothesis strategy over ConvSpec geometry
# ---------------------------------------------------------------------------

_BIASED_MULT = dataclasses.replace(
    make_exact(8), name="mul8s_biased",
    fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
_BIASED_LUT = jnp.asarray(build_lut(_BIASED_MULT))
ACU_BIASED = dataclasses.replace(
    make_acu("mul8s_exact", AcuMode.LUT, use_pallas=True, fused=True),
    multiplier=_BIASED_MULT, lut=build_lut(_BIASED_MULT))


@settings(max_examples=8, deadline=None)
@given(
    h=st.integers(6, 18),
    w=st.integers(5, 17),
    c=st.integers(1, 9),
    cout=st.integers(1, 9),
    k=st.sampled_from([1, 3, 5]),          # odd kernels
    sh=st.integers(1, 3),
    sw=st.integers(1, 3),
    dh=st.integers(1, 2),
    dw=st.integers(1, 2),
    same=st.sampled_from([True, False]),
    bh=st.integers(1, 4),                  # pinned band height under test
    groups=st.sampled_from([1, 1, 1, 2]),
    biased=st.sampled_from([False, True]),
)
def test_property_tiled_whole_oracle_bitwise(h, w, c, cout, k, sh, sw, dh,
                                             dw, same, bh, groups, biased):
    """Property harness over ConvSpec geometry: for every drawn (H, W, C,
    Cout, kernel, stride, dilation, padding, band height, multiplier bias)
    the spatially-tiled kernel, the whole-image kernel, and the eager
    im2col + fused_lut_dense oracle agree BITWISE, eager and jit; and plan
    resolution against a budget the whole image exceeds picks the tiled
    route exactly when a feasible banding exists. Grouped draws assert the
    preserved vmapped-GEMM route instead (the fused kernels serve groups=1).
    """
    if groups != 1:
        assume(c % groups == 0 and cout % groups == 0)
    x_shape = (2, c, h, w)
    w_shape = (cout, c // groups, k, k)
    stride, dil = (sh, sw), (dh, dw)
    padding = "SAME" if same else "VALID"
    pad = resolve_conv_padding(padding, x_shape, w_shape, stride, dil)
    from repro.kernels.fused_lut_conv.ops import conv_out_size
    ho = conv_out_size(h, k, sh, dh, pad[0])
    wo = conv_out_size(w, k, sw, dw, pad[1])
    assume(ho >= 1 and wo >= 1)
    seed = (h * 31 + w * 17 + c * 13 + cout * 11 + k * 7 + sh * 5 + sw * 3
            + dh * 2 + dw + bh + groups + int(biased))
    x, wt = _conv_operands(x_shape, w_shape, seed=seed)
    spec = ConvSpec(x_shape=x_shape, w_shape=w_shape, stride=stride,
                    padding=pad, dilation=dil, groups=groups)
    acu = ACU_BIASED if biased else ACU_FUSED
    cfg = ApproxConfig(acu=acu)

    if groups != 1:
        plan = conv_plan(acu, spec, fused=True)
        assert plan.route in ("im2col_grouped", "im2col_depthwise")
        y = conv2d(x, wt, None, cfg=cfg, stride=stride, padding=padding,
                   dilation=dil, groups=groups)
        y2 = conv2d(x, wt, None, cfg=cfg, stride=stride, padding=padding,
                    dilation=dil, groups=groups, route="im2col")
        assert jnp.array_equal(y, y2)
        return

    lut = _BIASED_LUT if biased else LUT
    xqp, wqp, wq = _quantized_operands(x, wt)
    geom = dict(stride=stride, padding=pad, dilation=dil, bits=8)
    ref = fused_lut_conv_ref(x, wq, lut.reshape(-1), 128, 256, xqp.scale,
                             xqp.zero_point, wqp.scale, **geom)
    whole = fused_lut_conv(x, wq, lut, 128, xqp.scale, xqp.zero_point,
                           wqp.scale, interpret=True, **geom)
    tiled = fused_lut_conv_tiled(x, wq, lut, 128, xqp.scale, xqp.zero_point,
                                 wqp.scale, bh=bh, interpret=True, **geom)
    assert jnp.array_equal(whole, ref)
    assert jnp.array_equal(tiled, ref)
    j_t = jax.jit(lambda x, wq, xs, xz, ws: fused_lut_conv_tiled(
        x, wq, lut, 128, xs, xz, ws, bh=bh, interpret=True, **geom))(
            x, wq, xqp.scale, xqp.zero_point, wqp.scale)
    j_w = jax.jit(lambda x, wq, xs, xz, ws: fused_lut_conv(
        x, wq, lut, 128, xs, xz, ws, interpret=True, **geom))(
            x, wq, xqp.scale, xqp.zero_point, wqp.scale)
    j_r = jax.jit(lambda x, wq, xs, xz, ws: fused_lut_conv_ref(
        x, wq, lut.reshape(-1), 128, 256, xs, xz, ws, **geom))(
            x, wq, xqp.scale, xqp.zero_point, wqp.scale)
    assert jnp.array_equal(j_t, j_r)
    assert jnp.array_equal(j_w, j_r)

    # plan resolution: shrink the budget below the whole-image working set;
    # the plan must pick the tiled route iff a feasible banding exists
    from repro.kernels.fused_lut_conv.ops import (conv_vmem_bytes,
                                                  pick_conv_spatial_tiling)
    gargs = (c, h, w, cout, k, k, sh, sw, dh, dw, pad, 256)
    budget = conv_vmem_bytes(*gargs) - 1
    plan = conv_plan(acu, spec, fused=True, vmem_budget=budget)
    tiling = pick_conv_spatial_tiling(*gargs, budget=budget)
    if tiling is None:
        assert plan.route == "im2col"
        assert any("degenerate" in r for r in plan.report)
    else:
        assert plan.route == "tiled"
        assert plan.tiling == tiling
        out = plan(x, wq, xqp.scale, xqp.zero_point, wqp.scale)
        assert jnp.array_equal(out, ref)


@pytest.mark.slow
def test_imagenet_scale_conv_resolves_tiled_and_matches_oracle():
    """The PR 4 acceptance geometry: a 1x64x224x224 conv2d resolves to
    route="tiled" under the default budget (no im2col fallback anywhere in
    the plan report) and is bitwise identical to the eager im2col +
    fused_lut_dense oracle."""
    rep = conv_plan_report((1, 64, 224, 224), (64, 64, 3, 3), CFG)
    assert rep["route"] == "tiled"
    assert rep["tiling"] is not None
    assert not any("im2col" in r for r in rep["report"])
    x, w = _conv_operands((1, 64, 224, 224), (64, 64, 3, 3), seed=224)
    y_t = conv2d(x, w, None, cfg=CFG)
    y_o = conv2d(x, w, None, cfg=CFG, route="im2col")
    assert jnp.array_equal(y_t, y_o)


def test_conv2d_separable_still_works():
    """separable_conv2d composes the depthwise and pointwise plans; the
    pointwise half rides the fused kernel."""
    x, _ = _conv_operands((1, 4, 8, 8), (1, 1, 1, 1), seed=8)
    rng = np.random.default_rng(8)
    wdw = jnp.asarray(rng.normal(size=(4, 1, 3, 3)), jnp.float32)
    wpw = jnp.asarray(rng.normal(size=(6, 4, 1, 1)), jnp.float32)
    from repro.core.approx_ops import separable_conv2d
    out = separable_conv2d(x, wdw, wpw, cfg=CFG)
    assert out.shape == (1, 6, 8, 8)
    assert bool(jnp.isfinite(out).all())


# ---------------------------------------------------------------------------
# approximate backward: banded weight-grad kernel + conv STE approx_bwd
# ---------------------------------------------------------------------------

from repro.core.approx_ops import _conv_qparams, _im2col
from repro.core.quantization import fake_quantize, inline_symmetric_scale, \
    pin_rounding
from repro.kernels.fused_lut_conv.ops import conv_out_size, \
    fused_lut_conv_bwd_w


def _oracle_bwd_w(acu, xf, g, sx, sg, ksize, stride, padding, dilation):
    """quantize -> im2col of CODES -> unfused LUT GEMM: the materialized
    oracle the banded kernel must reproduce bitwise (int accumulators)."""
    kh, kw = ksize
    qx = jnp.clip(jnp.round(xf.astype(jnp.float32) / sx), -128, 127)
    qg = jnp.clip(jnp.round(g.astype(jnp.float32) / sg), -128,
                  127).astype(jnp.int32)
    cols, _ = _im2col(qx, kh, kw, stride, padding, dilation)  # pads -> code 0
    cols = cols.astype(jnp.int32).reshape(-1, cols.shape[-1])
    g2 = qg.reshape(-1, g.shape[3])
    acc = acu._lut_matmul_jnp(cols.T, g2, k_chunk=min(256, cols.shape[0]))
    c = xf.shape[1]
    return acc.reshape(c, kh * kw, g.shape[3]).transpose(1, 0, 2)


@pytest.mark.parametrize("geom", [
    # (n, c, h, w, cout, (kh, kw), stride, dilation, padding)
    (2, 3, 9, 11, 5, (3, 3), (1, 1), (1, 1), ((1, 1), (1, 1))),
    (1, 4, 12, 10, 7, (3, 2), (2, 1), (1, 2), ((0, 0), (1, 0))),
    (2, 2, 8, 8, 3, (2, 2), (2, 2), (1, 1), ((0, 0), (0, 0))),
    (1, 5, 14, 9, 6, (3, 3), (1, 2), (2, 1), ((2, 2), (1, 1))),
])
@pytest.mark.parametrize("bh", [0, 1, 3])
def test_bwd_w_kernel_matches_im2col_oracle(geom, bh):
    """Banded weight-grad kernel (patch rows streamed per output-row band,
    invalid rows masked in-kernel) == materialized im2col-code oracle,
    bitwise on the int32 accumulator, across stride/dilation/asymmetric-pad
    geometry and band heights (bh=0 lets the VMEM model pick)."""
    n, c, h, w, cout, ksize, stride, dil, pad = geom
    rng = np.random.default_rng(sum(ksize) + n + c + h)
    xf = jnp.asarray(rng.standard_normal((n, c, h, w)), jnp.float32)
    ho = conv_out_size(h, ksize[0], stride[0], dil[0], pad[0])
    wo = conv_out_size(w, ksize[1], stride[1], dil[1], pad[1])
    g = jnp.asarray(rng.standard_normal((n, ho, wo, cout)), jnp.float32)
    sx = inline_symmetric_scale(jnp.max(jnp.abs(xf)), 8)
    sg = inline_symmetric_scale(jnp.max(jnp.abs(g)), 8)
    ref = _oracle_bwd_w(ACU_FUSED, xf, g, sx, sg, ksize, stride, pad, dil)
    got = fused_lut_conv_bwd_w(xf, g, LUT, 128, sx, sg, ksize=ksize,
                               stride=stride, padding=pad, dilation=dil,
                               bits=8, bh=bh, interpret=True)
    assert got.dtype == jnp.int32
    assert jnp.array_equal(got, ref)


def test_bwd_w_kernel_biased_m00_masks_invalid_rows():
    """Biased multiplier (M[0,0] = 7): band-alignment pad rows would each
    leak a non-constant LUT[qx, off] sum — the in-kernel row mask must kill
    them exactly (no post-hoc correction can)."""
    rng = np.random.default_rng(4)
    xf = jnp.asarray(rng.standard_normal((1, 3, 9, 8)), jnp.float32)
    g = jnp.asarray(rng.standard_normal((1, 7, 6, 4)), jnp.float32)
    sx = inline_symmetric_scale(jnp.max(jnp.abs(xf)), 8)
    sg = inline_symmetric_scale(jnp.max(jnp.abs(g)), 8)
    ref = _oracle_bwd_w(ACU_BIASED, xf, g, sx, sg, (3, 3), (1, 1),
                        ((0, 0), (0, 0)), (1, 1))
    for bh in (2, 3):   # 7 rows: both leave partial last bands
        got = fused_lut_conv_bwd_w(xf, g, _BIASED_LUT, 128, sx, sg,
                                   ksize=(3, 3), stride=(1, 1),
                                   padding=((0, 0), (0, 0)), dilation=(1, 1),
                                   bits=8, bh=bh, interpret=True)
        assert jnp.array_equal(got, ref)


def _oracle_approx_grads(acu, x, w, g_nchw, cfg, stride, padding, dilation):
    """Unfused approximate-backward oracle for the conv STE: quantize
    globally -> code im2col -> int LUT GEMMs -> int scatter (gx) -> ONE
    combined-scale dequant per grad."""
    n, cin, h, w_in = x.shape
    cout, _, kh, kw = w.shape
    sh, sw = stride
    dh, dw = dilation
    (ph0, ph1), (pw0, pw1) = padding
    xqp, wqp = _conv_qparams(x, w, cfg, None, None)
    xf = fake_quantize(x, xqp).astype(jnp.float32)
    wf = fake_quantize(w, wqp).astype(jnp.float32)
    g = g_nchw.transpose(0, 2, 3, 1).astype(jnp.float32)
    ho, wo = g.shape[1:3]
    sg = inline_symmetric_scale(jnp.max(jnp.abs(g)), 8)
    sx = inline_symmetric_scale(jnp.max(jnp.abs(xf)), 8)
    sw_s = inline_symmetric_scale(jnp.max(jnp.abs(wf)), 8)
    accw = _oracle_bwd_w(acu, xf, g, sx, sg, (kh, kw), stride, padding,
                         dilation)
    gw = (accw.astype(jnp.float32) * pin_rounding(sx * sg)
          ).transpose(2, 1, 0).reshape(cout, cin, kh, kw)
    qg = jnp.clip(jnp.round(g / sg), -128, 127).astype(jnp.int32)
    qw = jnp.clip(jnp.round(wf / sw_s), -128, 127).astype(jnp.int32)
    accx = acu._lut_matmul_jnp(qg.reshape(-1, cout), qw.reshape(cout, -1),
                               k_chunk=min(256, cout))
    accx = accx.reshape(n, ho, wo, cin, kh, kw)
    canvas = jnp.zeros((n, cin, h + ph0 + ph1, w_in + pw0 + pw1), jnp.int32)
    for u in range(kh):
        for v in range(kw):
            canvas = canvas.at[
                :, :, u * dh:u * dh + (ho - 1) * sh + 1:sh,
                v * dw:v * dw + (wo - 1) * sw + 1:sw,
            ].add(accx[:, :, :, :, u, v].transpose(0, 3, 1, 2))
    canvas = canvas[:, :, ph0:ph0 + h, pw0:pw0 + w_in]
    gx = canvas.astype(jnp.float32) * pin_rounding(sg * sw_s)
    return gx, gw


@pytest.mark.parametrize("geom", [
    ((2, 3, 9, 11), (5, 3, 3, 3), (1, 1), "SAME", (1, 1)),
    ((1, 4, 12, 10), (7, 4, 3, 2), (2, 1), "VALID", (1, 2)),
])
def test_conv2d_approx_bwd_matches_unfused_oracle(geom):
    """End-to-end jax.vjp through conv2d with cfg.approx_bwd: the banded
    fused backward (weight-grad kernel + per-band gx GEMMs scattering int32)
    equals the materialized unfused composition bitwise, eager and jit. The
    im2col patch tensor never exists in HBM on the fused route."""
    x_shape, w_shape, stride, padding, dil = geom
    rng = np.random.default_rng(x_shape[2])
    cfg = ApproxConfig(acu=ACU_FUSED, approx_bwd=True)
    x = jnp.asarray(rng.standard_normal(x_shape), jnp.float32)
    w = jnp.asarray(rng.standard_normal(w_shape), jnp.float32)
    pad = resolve_conv_padding(padding, x_shape, w_shape, stride, dil)
    spec = ConvSpec(x_shape=x_shape, w_shape=w_shape, stride=stride,
                    padding=pad, dilation=dil)
    plan = conv_plan(ACU_FUSED, spec, a_bits=8, fused=True, mesh=False)
    assert plan.bwd_route == "banded"

    def f(x, w):
        return conv2d(x, w, stride=stride, padding=padding, dilation=dil,
                      cfg=cfg)

    y, vjp = jax.vjp(f, x, w)
    g = jnp.asarray(rng.standard_normal(y.shape), jnp.float32)
    gx, gw = vjp(g)
    ogx, ogw = _oracle_approx_grads(ACU_FUSED, x, w, g, cfg, stride, pad, dil)
    assert jnp.array_equal(gx, ogx)
    assert jnp.array_equal(gw, ogw)
    gx_j, gw_j = jax.jit(lambda x, w, g: jax.vjp(f, x, w)[1](g))(x, w, g)
    assert jnp.array_equal(gx, gx_j) and jnp.array_equal(gw, gw_j)


def test_conv_plan_resolves_bwd_route():
    """Fused plans resolve a banded bwd_route + tiling under the VMEM budget;
    unfused plans carry none."""
    spec = ConvSpec(x_shape=(1, 8, 16, 16), w_shape=(8, 8, 3, 3),
                    stride=(1, 1), padding=((1, 1), (1, 1)), dilation=(1, 1))
    plan = conv_plan(ACU_FUSED, spec, a_bits=8, fused=True, mesh=False)
    assert plan.bwd_route == "banded" and plan.bwd_tiling is not None
    assert "bwd_route" in plan.describe()
    acu_unfused = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True)
    plan_u = conv_plan(acu_unfused, spec, a_bits=8, fused=False, mesh=False)
    assert plan_u.bwd_route is None
