"""Fused quantize->LUT-GEMM->dequant kernel: bit-exactness vs the pure-jnp
oracle (``Acu._lut_matmul_jnp`` + ``_affine_matmul_dequant``), interpret mode.

"Bit-exact" here is literal float equality: the kernel must perform the same
quantize, the same int32 accumulate (with integer-space K-pad correction), and
the same single combined-scale dequant ``acc * (xs * ws)`` as the unfused
reference pipeline.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import build_lut, get_multiplier, make_acu, matmul_plan
from repro.core.acu import Acu, AcuMode
from repro.core.approx_ops import (ApproxConfig, _affine_matmul_dequant,
                                   approx_dense, approx_matmul)
from repro.core.quantization import (QParams, acu_operand, affine_qparams,
                                     quantize, symmetric_qparams)
from repro.kernels.fused_lut_dense.ops import fused_lut_dense
from repro.kernels.fused_lut_dense.ref import fused_lut_dense_ref

MULT = get_multiplier("mul8s_1L2H")
LUT = jnp.asarray(build_lut(MULT))
ACU = make_acu("mul8s_1L2H", AcuMode.LUT)
ACU_PALLAS = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True)


def unfused_oracle(x, w, xqp, wqp, acu=ACU):
    """The three-stage reference pipeline the fused kernel replaces."""
    a = acu_operand(quantize(x, xqp), xqp)
    wq = acu_operand(quantize(w, wqp), wqp)
    acc = acu._lut_matmul_jnp(a, wq)
    return _affine_matmul_dequant(acc, xqp, wqp)


@pytest.mark.parametrize("shape", [(8, 16, 8), (128, 128, 128), (130, 70, 50),
                                   (1, 257, 3), (256, 8, 384), (33, 64, 129),
                                   (640, 200, 384)])
def test_fused_matches_oracle_shapes(shape):
    """Shape sweep incl. non-divisible M/K/N; per-channel weight scales.
    (640, 200, 384) runs one (640, 256, 384) tile: five one-hot row blocks
    against three gathered column chunks, two K pieces."""
    M, K, N = shape
    rng = np.random.default_rng(M * K + N)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9),
                            8, axis=1)
    wq = acu_operand(quantize(w, wqp), wqp)
    out = fused_lut_dense(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                          wqp.scale, bits=8, interpret=True)
    ref = unfused_oracle(x, w, xqp, wqp)
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("zp_case", ["zero", "mid", "lo_edge", "hi_edge"])
def test_fused_zero_point_edges(zp_case):
    """Affine activation quantization: zero-point at 0, mid-range, and the
    clip-range edges. a_bits=7 keeps shifted codes inside the 8-bit ACU's
    operand range even at the edges."""
    bits = 7
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    zp = {"zero": 0.0, "mid": 11.0, "lo_edge": float(lo),
          "hi_edge": float(hi)}[zp_case]
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.normal(size=(20, 40)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(40, 9)), jnp.float32)
    xqp = QParams(scale=jnp.float32(0.05), zero_point=jnp.float32(zp),
                  bits=bits)
    wqp = symmetric_qparams(jnp.max(jnp.abs(w)), 8)
    wq = acu_operand(quantize(w, wqp), wqp)
    out = fused_lut_dense(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                          wqp.scale, bits=bits, interpret=True)
    ref = unfused_oracle(x, w, xqp, wqp)
    assert jnp.array_equal(out, ref)


def test_fused_kernel_matches_own_ref():
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.normal(size=(17, 130)), jnp.float32)
    wq = jnp.asarray(rng.integers(-128, 128, (130, 21)), jnp.int32)
    ws = jnp.asarray(np.abs(rng.normal(size=(21,))) * 0.02 + 1e-4, jnp.float32)
    out = fused_lut_dense(x, wq, LUT, 128, 0.03, -5.0, ws, bits=8,
                          interpret=True)
    ref = fused_lut_dense_ref(x, wq, LUT.reshape(-1), 128, 256, 0.03, -5.0,
                              ws, bits=8)
    assert jnp.array_equal(out, ref)


def test_fused_k_pad_correction_nonzero_m00():
    """K padding contributes LUT[off, off] = M[0, 0] per padded k; the kernel
    must subtract it in integer space. Exercised with a synthetic multiplier
    whose M[0, 0] != 0 (every registered family has M[0, 0] == 0)."""
    import dataclasses

    from repro.core.multipliers import make_exact

    biased = dataclasses.replace(
        make_exact(8), name="mul8s_biased",
        fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
    lut = jnp.asarray(build_lut(biased))
    assert int(lut[128, 128]) == 7
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.normal(size=(6, 30)), jnp.float32)  # K=30 -> pad 98
    wq = jnp.asarray(rng.integers(-128, 128, (30, 5)), jnp.int32)
    out = fused_lut_dense(x, wq, lut, 128, 0.04, 2.0, 0.01, bits=8,
                          interpret=True)
    ref = fused_lut_dense_ref(x, wq, lut.reshape(-1), 128, 256, 0.04, 2.0,
                              0.01, bits=8)
    assert jnp.array_equal(out, ref)


def test_fused_emit_acc_is_raw_accumulator():
    """emit_acc=True returns the int32 accumulator (tile K-pad already
    corrected) — what the mesh contraction route psums — and dequantizing it
    reproduces the normal fused output bitwise."""
    rng = np.random.default_rng(13)
    x = jnp.asarray(rng.normal(size=(9, 40)), jnp.float32)   # K=40 -> pad 88
    w = jnp.asarray(rng.normal(size=(40, 7)), jnp.float32)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9),
                            8, axis=1)
    wq = acu_operand(quantize(w, wqp), wqp)
    acc = fused_lut_dense(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                          wqp.scale, bits=8, interpret=True, emit_acc=True)
    assert acc.dtype == jnp.int32
    a = acu_operand(quantize(x, xqp), xqp)
    assert jnp.array_equal(acc, ACU._lut_matmul_jnp(a, wq))
    out = fused_lut_dense(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                          wqp.scale, bits=8, interpret=True)
    dq = acc.astype(jnp.float32) * (xqp.scale * wqp.scale.reshape(1, -1))
    assert jnp.array_equal(out, dq)


def test_matmul_plan_fused_routing():
    """matmul_plan serves a fused plan only when it can (LUT + pallas + table)
    and falls back to unfused otherwise."""
    assert matmul_plan(ACU_PALLAS, fused=True).fused
    assert not matmul_plan(ACU_PALLAS, fused=False).fused
    assert not matmul_plan(ACU, fused=True).fused            # no pallas
    func = make_acu("mul8s_1L2H", AcuMode.FUNCTIONAL, use_pallas=True)
    assert not matmul_plan(func, fused=True).fused           # not LUT mode
    # acu-level default threads through
    fused_acu = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True,
                         fused=True)
    assert matmul_plan(fused_acu).fused


@pytest.mark.parametrize("shape", [(12, 40, 9), (64, 128, 32)])
def test_ste_fused_equals_unfused(shape):
    """Public approx_matmul: fused cfg == unfused cfg, bitwise, and the STE
    backward (exact fp32 arithmetic) is identical for both."""
    M, K, N = shape
    rng = np.random.default_rng(K)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    xqp = affine_qparams(jnp.min(x), jnp.max(x), 8)
    wqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9),
                            8, axis=1)
    c0 = ApproxConfig(acu=ACU_PALLAS)
    c1 = ApproxConfig(acu=ACU_PALLAS, fused=True)
    y0 = approx_matmul(x, w, c0, xqp, wqp)
    y1 = approx_matmul(x, w, c1, xqp, wqp)
    assert jnp.array_equal(y0, y1)
    g0 = jax.grad(lambda x: approx_matmul(x, w, c0, xqp, wqp).sum())(x)
    g1 = jax.grad(lambda x: approx_matmul(x, w, c1, xqp, wqp).sum())(x)
    assert jnp.array_equal(g0, g1)


def test_approx_dense_fused_batched():
    """approx_dense with leading batch dims routes through the fused kernel
    (acu-level fused flag) and matches the unfused result bitwise."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.normal(size=(3, 5, 33)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(33, 14)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(14,)), jnp.float32)
    fused_acu = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True,
                         fused=True)
    y0 = approx_dense(x, w, b, ApproxConfig(acu=ACU_PALLAS))
    y1 = approx_dense(x, w, b, ApproxConfig(acu=fused_acu))
    assert y1.shape == (3, 5, 14)
    assert jnp.array_equal(y0, y1)


def test_acu_matmul_unchanged_by_fused_flag():
    """Acu.matmul stays the unfused integer-operand GEMM regardless of the
    fused default (it has no qparams to fuse with)."""
    rng = np.random.default_rng(11)
    a = jnp.asarray(rng.integers(-128, 128, (7, 19)), jnp.int32)
    w = jnp.asarray(rng.integers(-128, 128, (19, 4)), jnp.int32)
    import dataclasses
    fused_acu = dataclasses.replace(ACU, fused=True)
    assert jnp.array_equal(fused_acu.matmul(a, w), ACU.matmul(a, w))


# ---------------------------------------------------------------------------
# approximate backward: fused_lut_bwd (in-kernel fake-quant STE grads)
# ---------------------------------------------------------------------------

import dataclasses

from hypothesis import given, settings, strategies as st
from repro.core.multipliers import make_exact
from repro.kernels.fused_lut_dense.ops import fused_lut_bwd
from repro.kernels.fused_lut_dense.ref import fused_lut_bwd_ref

_BIASED_MULT = dataclasses.replace(
    make_exact(8), name="mul8s_biased",
    fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
_BIASED_LUT = jnp.asarray(build_lut(_BIASED_MULT))


def _bwd_operands(m, k, n, seed):
    rng = np.random.default_rng(seed)
    a = jnp.asarray(rng.normal(size=(m, k)), jnp.float32)
    b = jnp.asarray(rng.normal(size=(k, n)), jnp.float32)
    sa = jnp.max(jnp.abs(a)) / 127.0
    sb = jnp.max(jnp.abs(b)) / 127.0
    return a, b, sa, sb


@pytest.mark.parametrize("shape", [(1, 1, 1), (8, 128, 8), (33, 257, 5),
                                   (64, 96, 32), (130, 70, 129),
                                   (640, 200, 384)])
def test_fused_bwd_matches_ref_shapes(shape):
    """Backward-flavor kernel (both operands quantized in-kernel, per-tensor
    symmetric) vs its O(MKN) reference, odd and divisible M/K/N, eager and
    jit, bitwise."""
    a, b, sa, sb = _bwd_operands(*shape, seed=sum(shape))
    ref = fused_lut_bwd_ref(a, b, LUT.reshape(-1), 128, 256, sa, sb, bits=8)
    out = fused_lut_bwd(a, b, LUT, 128, sa, sb, bits=8, interpret=True)
    assert jnp.array_equal(out, ref)
    outj = jax.jit(lambda a, b: fused_lut_bwd(a, b, LUT, 128, sa, sb, bits=8,
                                              interpret=True))(a, b)
    assert jnp.array_equal(outj, ref)


def test_fused_bwd_k_pad_correction_biased_m00():
    """K=30 pads 98 ks; each contributes LUT[off, off] = 7 with the biased
    multiplier — the kernel must subtract them in integer space."""
    a, b, sa, sb = _bwd_operands(6, 30, 5, seed=3)
    ref = fused_lut_bwd_ref(a, b, _BIASED_LUT.reshape(-1), 128, 256, sa, sb,
                            bits=8)
    out = fused_lut_bwd(a, b, _BIASED_LUT, 128, sa, sb, bits=8,
                        interpret=True)
    assert jnp.array_equal(out, ref)


def test_wide_tiles_biased_m00_forward_and_backward():
    """A (256, 384) tile (two one-hot row blocks, three column chunks) with
    K = 200 padded to 256: both kernels subtract the 56 padded ks' M[0, 0]
    = 7 of the biased multiplier in integer space, bitwise."""
    from repro.kernels.fused_lut_dense.ops import tiles
    from repro.kernels.lut_gather import lut_planes
    assert tiles(256, 200, 384, lut_planes(_BIASED_LUT)) == (256, 256, 384)
    a, b, sa, sb = _bwd_operands(256, 200, 384, seed=21)
    ref = fused_lut_bwd_ref(a, b, _BIASED_LUT.reshape(-1), 128, 256, sa, sb,
                            bits=8)
    out = fused_lut_bwd(a, b, _BIASED_LUT, 128, sa, sb, bits=8,
                        interpret=True)
    assert jnp.array_equal(out, ref)
    wq = jnp.asarray(np.random.default_rng(22).integers(-128, 128, (200, 384)),
                     jnp.int32)
    out = fused_lut_dense(a, wq, _BIASED_LUT, 128, 0.04, 2.0, 0.01, bits=8,
                          interpret=True)
    ref = fused_lut_dense_ref(a, wq, _BIASED_LUT.reshape(-1), 128, 256, 0.04,
                              2.0, 0.01, bits=8)
    assert jnp.array_equal(out, ref)


def test_fused_bwd_emit_acc_is_raw_accumulator():
    """emit_acc=True is the int32 accumulator the mesh contraction route
    psums — equal to the unfused code-GEMM, and dequantizing reproduces the
    normal output bitwise."""
    a, b, sa, sb = _bwd_operands(9, 40, 7, seed=13)
    acc = fused_lut_bwd(a, b, LUT, 128, sa, sb, bits=8, interpret=True,
                        emit_acc=True)
    assert acc.dtype == jnp.int32
    qa = jnp.clip(jnp.round(a / sa), -128, 127).astype(jnp.int32)
    qb = jnp.clip(jnp.round(b / sb), -128, 127).astype(jnp.int32)
    assert jnp.array_equal(acc, ACU._lut_matmul_jnp(qa, qb))
    out = fused_lut_bwd(a, b, LUT, 128, sa, sb, bits=8, interpret=True)
    assert jnp.array_equal(out, acc.astype(jnp.float32) * (sa * sb))


@settings(max_examples=10, deadline=None)
@given(m=st.integers(1, 100), k=st.integers(1, 280), n=st.integers(1, 100),
       biased=st.sampled_from([False, True]))
def test_property_fused_bwd_oracle_bitwise(m, k, n, biased):
    """Property harness: any drawn (M, K, N) — including K-pad branches —
    and either multiplier, the fused backward equals the reference
    bitwise."""
    lut = _BIASED_LUT if biased else LUT
    a, b, sa, sb = _bwd_operands(m, k, n, seed=m * 31 + k * 7 + n)
    ref = fused_lut_bwd_ref(a, b, lut.reshape(-1), 128, 256, sa, sb, bits=8)
    out = fused_lut_bwd(a, b, lut, 128, sa, sb, bits=8, interpret=True)
    assert jnp.array_equal(out, ref)


@pytest.mark.parametrize("shape", [(16, 32, 8), (33, 70, 21)])
def test_ste_approx_bwd_fused_equals_unfused(shape):
    """cfg.approx_bwd routes the STE grads through the ACU; the fused
    in-kernel route and the unfused quantize->code-GEMM->dequant route are
    the same computation and must agree bitwise — values AND both grads."""
    M, K, N = shape
    rng = np.random.default_rng(N)
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)), jnp.float32)
    xqp = symmetric_qparams(jnp.max(jnp.abs(x)), 8)
    wqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9),
                            8, axis=1)
    acu_f = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True, fused=True)
    c0 = ApproxConfig(acu=ACU_PALLAS, approx_bwd=True)
    c1 = ApproxConfig(acu=acu_f, approx_bwd=True)

    def loss(cfg):
        return lambda x, w: (approx_matmul(x, w, cfg, xqp, wqp)
                             * jnp.arange(N)).sum()

    g0x, g0w = jax.grad(loss(c0), argnums=(0, 1))(x, w)
    g1x, g1w = jax.grad(loss(c1), argnums=(0, 1))(x, w)
    assert jnp.array_equal(g0x, g1x)
    assert jnp.array_equal(g0w, g1w)
    # jit agrees with eager (the scale expression is pinned against SPMD
    # rewrites)
    g2x, g2w = jax.jit(jax.grad(loss(c1), argnums=(0, 1)))(x, w)
    assert jnp.array_equal(g1x, g2x)
    assert jnp.array_equal(g1w, g2w)


# ---------------------------------------------------------------------------
# tile choice (ops.tiles) and its VMEM model
# ---------------------------------------------------------------------------

from repro.kernels.fused_lut_dense.kernel import DEFAULT_VMEM
from repro.kernels.fused_lut_dense.ops import gemm_vmem_bytes, tiles

D, F, V = 576, 1536, 49152                      # smollm-135m widths
# every GEMM of a smollm-135m layer and head at M = 2048 tokens: forward
# (M, K, N), then the STE backward's gx (M, N, K) and gw (K, M, N)
_SMOLLM = [(2048, k, n) for k, n in ((D, D), (D, 192), (D, F), (F, D),
                                     (D, V))]
_SMOLLM += [(m, n, k) for m, k, n in _SMOLLM[:5]] + \
           [(k, m, n) for m, k, n in _SMOLLM[:5]]


def _widest(dim):
    """The widest multiple of 128 up to 640 that divides the padded dim."""
    d = -(-dim // 128) * 128
    return max(t for t in range(128, 641, 128) if d % t == 0)


@pytest.mark.parametrize("planes", [2, 4])
@pytest.mark.parametrize("shape", _SMOLLM + [(4, D, D), (8, D, V), (130, 70, 50),
                                             (1, 1, 1), (1000, 300, 700),
                                             (640, 512, 640)])
def test_tiles_divide_and_fit(shape, planes):
    """Each tile divides its padded dim, the contraction tile is a multiple
    of 128, a short M keeps one 8-row-aligned tile, and the VMEM model of
    the tile stays under the scoped VMEM a kernel gets by default."""
    M, K, N = shape
    bm, bk, bn = tiles(M, K, N, planes)
    ceil = lambda x, m: -(-x // m) * m
    assert ceil(M, bm) % bm == 0 and bm % 8 == 0
    assert ceil(K, 128) % bk == 0 and bk % 128 == 0
    assert ceil(N, 128) % bn == 0 and bn % 128 == 0
    if M <= 128:
        assert bm == ceil(M, 8)
    else:
        assert bm % 128 == 0 and ceil(M, 128) % bm == 0
    assert gemm_vmem_bytes(bm, bk, bn, planes) <= DEFAULT_VMEM


def test_tiles_amortize_at_smollm_shapes():
    """The cells' GEMMs run wide tiles: 512 rows of M = 2048, the whole
    padded 640 of d_model = 576 on either side, 512 columns of 1536 and of
    the 49,152-token head."""
    assert tiles(2048, D, F, 2) == (512, 128, 512)
    assert tiles(2048, D, D, 2) == (512, 128, 640)
    assert tiles(2048, D, 192, 2) == (512, 128, 256)
    assert tiles(2048, F, D, 2) == (512, 256, 640)
    assert tiles(2048, D, V, 2) == (512, 128, 512)
    assert tiles(D, 2048, F, 2) == (640, 256, 512)       # gw of gate/up
    assert tiles(F, 2048, D, 2) == (512, 256, 640)       # gw of down
    assert tiles(4, D, D, 2) == (8, 128, 640)            # decode


def test_two_plane_tiles_fit_the_default_scoped_vmem():
    """With the 2-plane mul8s_1L2H table every smollm-135m GEMM runs the
    widest tile of each dim within the default scoped VMEM; a 4-plane table
    narrows a tile only where the model needs it: of (640, 256, 640) at the
    gw of the 576-wide projections, the 640 rows are kept."""
    for M, K, N in _SMOLLM:
        bm, _, bn = tiles(M, K, N, 2)
        assert (bm, bn) == (_widest(M), _widest(N))
    assert DEFAULT_VMEM < gemm_vmem_bytes(640, 256, 640, 4)
    assert tiles(D, 2048, D, 4) == (640, 256, 128)
    assert tiles(D, 2048, F, 4) == tiles(D, 2048, F, 2) == (640, 256, 512)


def test_plan_reports_tiles():
    """Fused forward and backward plans report the (bm, bk, bn) of each
    kernel shape traced, and ``resolved_plans`` shows them; unfused plans
    report none."""
    from repro.core.acu import matmul_bwd_plan
    from repro.core.approx_ops import resolved_plans
    S = jax.ShapeDtypeStruct
    f32, i32 = jnp.float32, jnp.int32
    acu_f = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True, fused=True)
    plan = matmul_plan(acu_f, mesh=False)
    assert plan.describe()["tiles"] == {}
    jax.eval_shape(plan, S((2048, D), f32), S((D, F), i32), S((), f32),
                   S((), f32), S((F,), f32))
    assert plan.describe()["tiles"] == {f"2048x{D}x{F}": (512, 128, 512)}
    assert "tiles" not in matmul_plan(ACU_PALLAS, fused=False,
                                      mesh=False).describe()
    bwd = matmul_bwd_plan(acu_f, mesh=False)
    jax.eval_shape(bwd.gx, S((2048, F), f32), S((F, D), f32), 1.0, 1.0)
    jax.eval_shape(bwd.gw, S((D, 2048), f32), S((2048, F), f32), 1.0, 1.0)
    assert bwd.describe() == {"route": "fused_lut_bwd", "tiles": {
        f"2048x{F}x{D}": (512, 256, 640), f"{D}x2048x{F}": (640, 256, 512)}}
    # the STE GEMM's report, through the process's resolved plans
    cfg = ApproxConfig(acu=acu_f, approx_bwd=True)
    xqp, wqp = QParams(1.0, 0.0, 8), QParams(1.0, 0.0, 8, axis=1)
    jax.eval_shape(jax.grad(lambda x, w: approx_matmul(x, w, cfg, xqp,
                                                       wqp).sum()),
                   S((136, 200), f32), S((200, 384), f32))
    rep = [p for p in resolved_plans() if p.get("bwd_tiles") and
           "200x136x384" in p["bwd_tiles"]]
    assert rep and rep[-1]["tiles"] == {"136x200x384": (256, 256, 384)}
    assert rep[-1]["bwd_tiles"] == {"136x384x200": (256, 384, 256),
                                    "200x136x384": (256, 256, 384)}
