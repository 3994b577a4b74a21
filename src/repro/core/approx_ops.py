"""Approximate layer operations (paper §3.3): quantize -> ACU GEMM -> dequant.

This is the "graph re-transform" equivalent: model code calls
:func:`approx_dense` / :func:`approx_conv2d` at its matmul sites, and an
:class:`ApproxConfig` (threaded through the model, or None for exact fp)
decides whether and how approximation happens. Conv2D is lowered to GEMM by
im2col exactly as in the paper (§3.3.1, Fig. 3); separable conv is depthwise +
pointwise (§3.3.2); RNN cells reuse the approximate Linear (§3.3.4).
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .acu import Acu, AcuMode, GroupedSpec, grouped_plan, matmul_plan
from .quantization import (QParams, acu_operand, dequantize, fake_quantize,
                           pin_rounding, quantize)

Array = jnp.ndarray


@dataclasses.dataclass(frozen=True)
class ApproxConfig:
    """Per-model approximation configuration (the paper's "user sets the
    desired DNN model with the quantization parameters + approximate module")."""

    acu: Acu
    a_bits: int = 8
    w_bits: int = 8
    fake_quant_only: bool = False   # QAT fake-quant path (no integer GEMM)
    fused: Optional[bool] = None    # route the STE forward through the fused
                                    # quantize->LUT-GEMM->dequant Pallas kernel
                                    # (None = inherit acu.fused; only effective
                                    # for LUT mode with use_pallas=True)
    approx_bwd: bool = False        # run the STE backward GEMMs through the
                                    # ACU too (ApproxTrain regime): residuals
                                    # and the incoming gradient quantize
                                    # per-tensor symmetric and the grad GEMMs
                                    # go through the LUT (fused in-kernel when
                                    # the forward is fused). False keeps the
                                    # exact-f32 STE backward.

    def __post_init__(self):
        if max(self.a_bits, self.w_bits) > self.acu.bits:
            raise ValueError(
                f"quantization bits ({self.a_bits}/{self.w_bits}) exceed the "
                f"ACU's operand width ({self.acu.bits}-bit "
                f"{self.acu.multiplier.name}); codes would overflow")

    def replace(self, **kw) -> "ApproxConfig":
        return dataclasses.replace(self, **kw)


def _affine_matmul_dequant(acc: Array, xqp: QParams, wqp: QParams) -> Array:
    """Dequantize an integer GEMM accumulator (paper eq. 2).

    Operands were shifted codes (code - zp), so the accumulator is directly
    ``sum (q1-z1)(q2-z2)`` and the dequant is a pure scale product.
    Weight scale may be per-output-channel (axis 0 of w^T layout handled by
    caller passing wqp with axis=1 on the (K, N) matrix).

    The two scales combine into ONE multiply, ``acc * (s1 * s2)``, and the
    combined scale sits behind an optimization barrier: a ``acc * s1 * s2``
    chain gets reassociated by the XLA simplifier inside shard_map-partitioned
    programs, and letting inline scale *computations* (amax -> divide) fuse
    into the big multiply perturbs its rounding between compilation contexts.
    Bit-exactness across every fused/unfused/sharded route — jitted or eager —
    is the contract here, so the scale product is pinned to one f32 rounding.
    """
    s1 = xqp.scale  # per-tensor
    s2 = wqp.scale  # scalar or (N,)
    if wqp.axis is not None:
        s2 = jnp.reshape(s2, (1, -1))
    s = pin_rounding(jnp.asarray(s1, jnp.float32) * jnp.asarray(s2, jnp.float32))
    return acc.astype(jnp.float32) * s


_STE_CACHE: dict = {}
_RESOLVED: dict = {}    # cache key -> report of the plan it resolved


def resolved_plans() -> list[dict]:
    """``describe()`` of every GEMM, grouped expert GEMM (``op``
    ``grouped_gemm``) and attention plan this process has resolved so far.
    GEMM entries add the STE backward's ``bwd_route``;
    fused routes list the ``(bm, bk, bn)`` of each kernel shape traced so
    far under ``tiles`` (forward) and ``bwd_tiles`` (both backward GEMMs)."""
    return [report() for report in _RESOLVED.values()]


def _mesh_cache_key(ctx):
    """Hashable fingerprint of a MeshContext for the STE cache (meshes are
    hashable in jax; the acu_* rules are what the plan resolution reads)."""
    if ctx is None:
        return None
    rules = tuple(sorted((k, v) for k, v in ctx.rules.items()
                         if k.startswith("acu_")))
    return (ctx.mesh, rules)


def _get_ste_fn(acu: Acu, a_bits: int, w_bits: int, fused: bool = False,
                ctx=None, approx_bwd: bool = False):
    """Per-ACU custom_vjp GEMM: approximate forward, STE backward — exact
    f32 by default, or through the ACU itself with ``approx_bwd`` (the
    ApproxTrain regime: both grad GEMMs quantize their operands per-tensor
    symmetric and gather from the same LUT as the forward).

    The forward dispatches through :func:`matmul_plan`; a fused plan runs
    quantize -> LUT GEMM -> dequant as one Pallas kernel (weights are still
    quantized outside — their codes are produced once per layer, not per
    tile), an unfused plan keeps the three-stage pipeline. With an active
    mesh the plan runs sharded, and the backward GEMMs carry matching specs
    (exact: ``gx`` row-sharded like the activations, ``gw`` column-sharded
    like the weights, contractions device-local; approximate: the permuted
    forward partition with int32 psums over the contraction axes — see
    :func:`~repro.core.acu.matmul_bwd_plan`), so sharded QAT gradients are
    bitwise identical to single-device ones either way.
    """
    key = (id(acu), a_bits, w_bits, fused, approx_bwd, _mesh_cache_key(ctx))
    if key in _STE_CACHE:
        return _STE_CACHE[key]

    plan = matmul_plan(acu, a_bits=a_bits, fused=fused, mesh=ctx or False)
    bwd_report = lambda: {"bwd_route": "exact_f32"}
    if approx_bwd:
        from .acu import matmul_bwd_plan
        bwd_plan = matmul_bwd_plan(acu, a_bits=a_bits, fused=fused,
                                   mesh=ctx or False)
        gx_bwd, gw_bwd = bwd_plan.gx, bwd_plan.gw
        bwd_report = lambda: {f"bwd_{k}": v
                              for k, v in bwd_plan.describe().items()}
    elif plan.partition is not None:
        from repro.parallel.acu_shard import bwd_gemms
        gx_gemm, gw_gemm = bwd_gemms(ctx, plan.partition)
    else:
        gx_gemm = lambda g, wf: g @ wf.T
        gw_gemm = lambda xf, g: xf.T @ g
    _RESOLVED[key] = lambda: {"op": "gemm", **plan.describe(), **bwd_report()}

    @jax.custom_vjp
    def ste_matmul(x, w, xs, xz, ws, wz):
        xqp = QParams(scale=xs, zero_point=xz, bits=a_bits)
        wqp = QParams(scale=ws, zero_point=wz, bits=w_bits, axis=1)
        wq = acu_operand(quantize(w, wqp), wqp)
        if plan.fused:
            return plan(x, wq, xs, xz, ws)
        xq = acu_operand(quantize(x, xqp), xqp)
        acc = plan(xq, wq)
        return _affine_matmul_dequant(acc, xqp, wqp)

    def fwd(x, w, xs, xz, ws, wz):
        y = ste_matmul(x, w, xs, xz, ws, wz)
        xqp = QParams(scale=xs, zero_point=xz, bits=a_bits)
        wqp = QParams(scale=ws, zero_point=wz, bits=w_bits, axis=1)
        xf = fake_quantize(x, xqp).astype(x.dtype)
        wf = fake_quantize(w, wqp).astype(w.dtype)
        return y, (xf, wf)

    if approx_bwd:
        from .quantization import inline_symmetric_scale

        def bwd(res, g):
            # approximate backward: per-tensor symmetric scales computed on
            # the FULL tensors (under a mesh every shard must see the same
            # scale — amax happens before the shard_map inside gx/gw_bwd);
            # inline_symmetric_scale because these amaxes live inside the
            # differentiated program, where the scale expression must
            # compile identically across eager/jit/SPMD contexts
            xf, wf = res
            g = g.astype(jnp.float32)
            sg = inline_symmetric_scale(jnp.max(jnp.abs(g)), a_bits)
            sx = inline_symmetric_scale(jnp.max(jnp.abs(xf)), a_bits)
            sw = inline_symmetric_scale(jnp.max(jnp.abs(wf)), a_bits)
            gx = gx_bwd(g, wf.astype(jnp.float32).T, sg, sw).astype(xf.dtype)
            gw = gw_bwd(xf.astype(jnp.float32).T, g, sx, sg).astype(wf.dtype)
            return (gx, gw, None, None, None, None)
    else:
        def bwd(res, g):
            xf, wf = res
            g = g.astype(jnp.float32)
            gx = gx_gemm(g, wf.astype(jnp.float32)).astype(xf.dtype)
            gw = gw_gemm(xf.astype(jnp.float32), g).astype(wf.dtype)
            return (gx, gw, None, None, None, None)

    ste_matmul.defvjp(fwd, bwd)
    _STE_CACHE[key] = ste_matmul
    return ste_matmul


def approx_matmul(x: Array, w: Array, cfg: ApproxConfig,
                  xqp: QParams, wqp: QParams) -> Array:
    """2-D approximate GEMM with STE backward. ``x``: (M, K) float,
    ``w``: (K, N) float; ``wqp.axis`` must be 1 (per-out-channel) or None.
    Mesh-aware: resolved against the active MeshContext at call time."""
    if cfg.fake_quant_only:
        return fake_quantize(x, xqp) @ fake_quantize(w, wqp)
    fused = cfg.acu.fused if cfg.fused is None else cfg.fused
    from repro.parallel.sharding import current_mesh_context
    fn = _get_ste_fn(cfg.acu, cfg.a_bits, cfg.w_bits, fused,
                     ctx=current_mesh_context(), approx_bwd=cfg.approx_bwd)
    return fn(x, w, xqp.scale, xqp.zero_point, wqp.scale, wqp.zero_point)


def approx_dense(x: Array, w: Array, b: Optional[Array], cfg: Optional[ApproxConfig],
                 xqp: Optional[QParams] = None, wqp: Optional[QParams] = None) -> Array:
    """Linear layer y = x @ w + b, optionally through the ACU.

    ``x``: (..., K), ``w``: (K, N). With ``cfg=None`` this is an exact matmul
    (the substrate path used by the LM stack unless emulation is enabled).
    """
    if cfg is None:
        y = x @ w
    else:
        lead = x.shape[:-1]
        K = x.shape[-1]
        x2 = x.reshape(-1, K)
        if xqp is None:
            amax = jnp.maximum(jnp.max(jnp.abs(x2)), 1e-6)
            from .quantization import symmetric_qparams
            xqp = symmetric_qparams(amax, cfg.a_bits)
        if wqp is None:
            from .quantization import symmetric_qparams
            wqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(w), axis=0), 1e-9),
                                    cfg.w_bits, axis=1)
        y = approx_matmul(x2, w, cfg, xqp, wqp).reshape(*lead, w.shape[1])
        y = y.astype(x.dtype)   # dequant is f32; keep the model's dtype
    if b is not None:
        if cfg is not None:
            # best-effort: keep dequant-multiply and bias-add as two separate
            # roundings so flat-jit and shard_map-partitioned programs agree;
            # the SPMD partitioner can still FMA-contract them (1-ulp, see
            # docs/sharding.md) — the GEMM+dequant itself is always bitwise
            y = pin_rounding(y)
        y = y + b
    return y


# ---------------------------------------------------------------------------
# Grouped ragged MoE GEMM: ONE pallas_call for all E expert GEMMs
# (kernels/fused_lut_grouped), routed by core/acu.grouped_plan. The resolved
# STE fn is cached per (acu, bits, spec, route, mesh) like the dense fns.
# ---------------------------------------------------------------------------

def _get_grouped_ste_fn(acu: Acu, a_bits: int, w_bits: int,
                        spec: GroupedSpec, ctx, route: Optional[str] = None):
    """Per-ACU custom_vjp grouped GEMM: approximate ragged forward, exact-f32
    STE backward.

    The forward dispatches through :func:`~repro.core.acu.grouped_plan` —
    the ``"fused_grouped"`` route runs every expert GEMM inside one ragged
    Pallas kernel (mesh-wrapped when a partition is active); the ``"vmap"``
    route keeps the per-expert vmapped composition (quantize -> per-expert
    GEMM -> dequant, fused or unfused per :func:`matmul_plan`), which doubles
    as the fused route's bit-exactness oracle since both consume the same
    pinned shared activation scale and mask dead capacity rows to exactly
    zero. The backward is the exact-f32 STE on the fake-quantized residuals
    with the incoming gradient masked to the live rows — dead capacity slots
    emit zero forward, so nothing may flow back through them.
    """
    key = ("grouped", id(acu), a_bits, w_bits, spec, route,
           _mesh_cache_key(ctx))
    if key in _STE_CACHE:
        return _STE_CACHE[key]

    plan = grouped_plan(acu, spec, a_bits=a_bits, mesh=ctx or False,
                        route=route)
    _RESOLVED[key] = lambda: {"op": "grouped_gemm", **plan.describe()}
    E, C, nb = spec.n_experts, spec.cap, spec.n_blocks
    if plan.route != "fused_grouped":
        # per-expert vmapped composition (single-device inner plan — the
        # audited fallback runs replicated, see plan.report)
        mplan = matmul_plan(acu, a_bits=a_bits, mesh=False)

    def _live(counts):
        return jnp.arange(C)[None, :] < jnp.clip(counts, 0, C)[:, None]

    @jax.custom_vjp
    def ste_grouped(xe, w, xs, xz, ws, counts):
        xqp = QParams(scale=xs, zero_point=xz, bits=a_bits)
        if plan.route == "fused_grouped":
            wqp = QParams(scale=ws.reshape(E, 1, -1),
                          zero_point=jnp.zeros((), jnp.float32), bits=w_bits)
            wq = acu_operand(quantize(w, wqp), wqp)
            return plan(xe, wq, xs, xz, ws, counts)

        def one(xg, wg, wsg):
            wqp_e = QParams(scale=wsg,
                            zero_point=jnp.zeros((), jnp.float32),
                            bits=w_bits, axis=1)
            wq_e = acu_operand(quantize(wg, wqp_e), wqp_e)
            if mplan.fused:
                return mplan(xg, wq_e, xs, xz, wsg)
            xq = acu_operand(quantize(xg, xqp), xqp)
            return _affine_matmul_dequant(mplan(xq, wq_e), xqp, wqp_e)

        per_e = jax.vmap(one, in_axes=(0, 0, 0))
        y = jax.vmap(per_e, in_axes=(0, None, None))(
            xe.reshape(nb, E, C, xe.shape[-1]), w, ws)
        y = y.reshape(nb * E, C, y.shape[-1])
        # masking, not slicing: dead capacity rows still produce
        # sum_k LUT[0, w] != 0 under biased-M00 multipliers
        return jnp.where(_live(counts)[..., None], y, 0.0)

    def fwd(xe, w, xs, xz, ws, counts):
        y = ste_grouped(xe, w, xs, xz, ws, counts)
        xqp = QParams(scale=xs, zero_point=xz, bits=a_bits)
        wqp = QParams(scale=ws.reshape(E, 1, -1),
                      zero_point=jnp.zeros((), jnp.float32), bits=w_bits)
        xf = fake_quantize(xe, xqp).astype(xe.dtype)
        wf = fake_quantize(w, wqp).astype(w.dtype)
        return y, (xf, wf, counts)

    def bwd(res, g):
        # exact-f32 STE on the fake-quantized residuals; the incoming
        # gradient is masked to the live rows (the forward emits exactly
        # zero past each group's count, so dead slots carry no gradient)
        xf, wf, counts = res
        g = jnp.where(_live(counts)[..., None], g.astype(jnp.float32), 0.0)
        g4 = g.reshape(nb, E, C, g.shape[-1])
        xf4 = xf.astype(jnp.float32).reshape(nb, E, C, xf.shape[-1])
        wff = wf.astype(jnp.float32)
        gx = jnp.einsum("becn,ekn->beck", g4, wff)
        gx = gx.reshape(xf.shape).astype(xf.dtype)
        gw = jnp.einsum("beck,becn->ekn", xf4, g4).astype(wf.dtype)
        return (gx, gw, None, None, None, None)

    ste_grouped.defvjp(fwd, bwd)
    _STE_CACHE[key] = ste_grouped
    return ste_grouped


def approx_grouped_dense(xe: Array, w: Array, cfg: ApproxConfig,
                         counts: Array, xqp: Optional[QParams] = None,
                         wqp: Optional[QParams] = None,
                         route: Optional[str] = None) -> Array:
    """Ragged grouped MoE GEMM through the ACU: all E expert GEMMs in one
    dispatch.

    ``xe``: (G, C, K) dispatched capacity buffers — ``G = nb * E`` groups
    (dispatch blocks x experts, block-major) of ``C`` capacity rows; group
    ``g`` multiplies expert ``g % E``. ``w``: (E, K, N) per-expert weights;
    ``counts``: (G,) live rows per group — output rows ``>= counts[g]`` are
    exactly 0.0 (dead capacity slots contribute nothing, even under
    biased-M00 multipliers).

    The activation quantizer is ONE per-tensor scale over the whole
    dispatched tensor (not per expert): that is what makes the grouped
    kernel and the per-expert vmapped composition bitwise identical, and it
    matches the dispatch semantics — the rows of every group came from the
    same layer activation tensor. Weight scales stay per-expert
    per-out-channel. ``route`` pins the plan route (``"fused_grouped"`` /
    ``"vmap"``); the default audited fallback applies.

    No ``fake_quant_only`` route: the grouped kernel runs the integer ACU
    GEMM, which contradicts the fake-quant contract — QAT MoE keeps the
    per-expert :func:`approx_dense` path.
    """
    G, C, K = xe.shape
    E, _, N = w.shape
    if G % E != 0:
        raise ValueError(f"groups {G} not a multiple of experts {E}")
    if cfg.fake_quant_only:
        raise ValueError("approx_grouped_dense has no fake-quant route; "
                         "keep the per-expert approx_dense path for QAT")
    # inline_symmetric_scale (multiply form), not symmetric_qparams: these
    # amaxes live inside the (possibly jitted) MoE layer, and the divide
    # form compiles to a reciprocal multiply under SPMD/jit — a 1-ulp scale
    # drift that lands upstream of pin_rounding (see quantization.py)
    from .quantization import inline_symmetric_scale
    if xqp is None:
        xqp = QParams(
            scale=inline_symmetric_scale(
                jnp.maximum(jnp.max(jnp.abs(xe)), 1e-6), cfg.a_bits),
            zero_point=jnp.zeros((), jnp.float32), bits=cfg.a_bits)
    if wqp is None:
        wqp = QParams(
            scale=inline_symmetric_scale(
                jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-9), cfg.w_bits),
            zero_point=jnp.zeros((), jnp.float32), bits=cfg.w_bits)
    ws = jnp.broadcast_to(
        jnp.asarray(wqp.scale, jnp.float32).reshape(E, -1), (E, N))
    spec = GroupedSpec(n_experts=E, cap=C, d_in=K, d_out=N, n_blocks=G // E)
    from repro.parallel.sharding import current_mesh_context
    fn = _get_grouped_ste_fn(cfg.acu, cfg.a_bits, cfg.w_bits, spec,
                             ctx=current_mesh_context(), route=route)
    y = fn(xe, w, xqp.scale, xqp.zero_point, ws,
           jnp.asarray(counts, jnp.int32))
    return y.astype(xe.dtype)


# ---------------------------------------------------------------------------
# Approximate attention: quantize -> LUT-gather QK^T / PV inside the
# streaming-softmax kernel (kernels/flash_attention/approx.py), routed by
# core/acu.attn_plan. The resolved plan is cached per (acu, bits, spec, mesh)
# exactly like the STE GEMM fns.
# ---------------------------------------------------------------------------

def _get_attn_plan(acu: Acu, a_bits: int, spec, ctx):
    from .acu import attn_plan
    key = ("attn", id(acu), a_bits, spec, _mesh_cache_key(ctx))
    if key in _STE_CACHE:
        return _STE_CACHE[key]
    plan = attn_plan(acu, spec, a_bits=a_bits, mesh=ctx or False)
    _STE_CACHE[key] = plan
    _RESOLVED[key] = lambda: {"op": "attention", **plan.describe()}
    return plan


def approx_attention(q: Array, k: Array, v: Array, cfg: ApproxConfig, *,
                     causal: bool = True, window: Optional[int] = None,
                     softcap: Optional[float] = None,
                     rowinfo: Optional[Array] = None) -> Optional[Array]:
    """Attention through the ACU, or ``None`` when the plan audits to the
    exact-substrate route (non-LUT mode, no Pallas, missing table) — the
    caller keeps its float attention, mirroring conv's im2col contract.

    ``q``: (B, Hq, Sq, D); ``k``/``v``: (B, Hkv, Sk, D). Per-tensor symmetric
    scales are calibrated here on the full tensors (under a mesh every shard
    must see the same scales — the amaxes happen before the plan's
    shard_map). Inference-only: no custom_vjp, decode/prefill forward path.
    """
    from .acu import AttnSpec
    from .quantization import inline_symmetric_scale
    from repro.parallel.sharding import current_mesh_context
    spec = AttnSpec(hq=q.shape[1], hkv=k.shape[1], causal=causal,
                    window=window, softcap=softcap)
    ctx = current_mesh_context()
    plan = _get_attn_plan(cfg.acu, cfg.a_bits, spec, ctx)
    if plan.route != "fused_attn":
        return None
    scales = [inline_symmetric_scale(jnp.maximum(jnp.max(jnp.abs(t)), 1e-6),
                                     cfg.a_bits) for t in (q, k, v)]
    return plan(q, k, v, *scales, rowinfo)


def approx_attention_paged(q: Array, k_pool: Array, v_pool: Array,
                           cfg: ApproxConfig, *, page_table: Array,
                           rowinfo: Array, causal: bool = True,
                           window: Optional[int] = None,
                           softcap: Optional[float] = None
                           ) -> Optional[Array]:
    """Attention through the ACU over block-paged KV, or ``None`` when the
    plan audits to the exact-substrate route (the caller then gathers the
    pool blocks back to a contiguous layout and keeps its float attention).

    ``q``: (B, Hq, Sq, D); ``k_pool``/``v_pool``: (Hkv, P, bk, D) physical
    block pools; ``page_table``: (B, n_logical) int32; ``rowinfo``: (B, 3)
    int32 — both REQUIRED. The K/V calibration amaxes run over each row's
    written extent ``[kv_start, kv_len)`` of the blocks its page table
    references, NOT the whole pool nor whole blocks: a prefix-cache hit
    must see exactly the scales a cold run of the same request would
    compute, and the pool's unrelated residents (other requests, stale
    contents of reused blocks) must never perturb them.
    """
    from .acu import AttnSpec
    from .quantization import inline_symmetric_scale
    from repro.parallel.sharding import current_mesh_context
    spec = AttnSpec(hq=q.shape[1], hkv=k_pool.shape[0], causal=causal,
                    window=window, softcap=softcap, bk=k_pool.shape[2],
                    kv_layout="paged")
    ctx = current_mesh_context()
    plan = _get_attn_plan(cfg.acu, cfg.a_bits, spec, ctx)
    if plan.route != "fused_attn_paged":
        return None
    pt = jnp.asarray(page_table, jnp.int32)
    info = jnp.asarray(rowinfo, jnp.int32)
    # only each row's written extent [kv_start, kv_len) counts: a freshly
    # allocated block still holds whatever its previous owner wrote
    t = jnp.arange(pt.shape[1] * k_pool.shape[2]).reshape(1, *pt.shape[1:],
                                                          -1)
    valid = (t >= info[:, 1, None, None]) & (t < info[:, 2, None, None])
    amaxes = (jnp.maximum(jnp.max(jnp.abs(q)), 1e-6),) + tuple(
        jnp.maximum(jnp.max(jnp.where(valid[None, ..., None],
                                      jnp.abs(pool[:, pt]), 0.0)), 1e-6)
        for pool in (k_pool, v_pool))
    scales = [inline_symmetric_scale(a, cfg.a_bits) for a in amaxes]
    return plan(q, k_pool, v_pool, *scales, rowinfo, pt)


# ---------------------------------------------------------------------------
# Conv2D (paper §3.3.1) and separable conv (§3.3.2)
#
# Every approximate conv resolves a ConvPlan (core/acu.py): the fused route
# streams im2col patches inside one Pallas kernel (the patch tensor never
# reaches HBM); the eager im2col composition below is the audited fallback
# and the bit-exactness oracle.
# ---------------------------------------------------------------------------

def _im2col(x: Array, kh: int, kw: int, stride: Sequence[int],
            padding: str | Sequence[tuple[int, int]], dilation: Sequence[int]) -> Array:
    """Extract conv patches: (N, C, H, W) -> (N, Ho*Wo, C*kh*kw)."""
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), tuple(stride), padding,
        rhs_dilation=tuple(dilation),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
    )  # (N, C*kh*kw, Ho, Wo)
    n, ckk, ho, wo = patches.shape
    return patches.reshape(n, ckk, ho * wo).transpose(0, 2, 1), (ho, wo)


def _conv_qparams(x: Array, w: Array, cfg: ApproxConfig,
                  xqp: Optional[QParams], wqp: Optional[QParams]
                  ) -> tuple[QParams, QParams]:
    """Shared quantizers for the groups=1 conv routes: per-tensor activation
    scale calibrated on the *input* (every patch entry is an input pixel or a
    0.0 pad, and 0.0 never raises an amax, so the input bound covers the
    patch tensor) and per-output-channel weight scales. Both the fused
    patch-streaming route and the eager im2col oracle use exactly these, so
    the two stay bitwise comparable end to end."""
    from .quantization import symmetric_qparams
    if xqp is None:
        xqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(x)), 1e-6),
                                cfg.a_bits)
    if wqp is None:
        wqp = symmetric_qparams(
            jnp.maximum(jnp.max(jnp.abs(w), axis=(1, 2, 3)), 1e-9),
            cfg.w_bits, axis=0)
    return xqp, wqp


def _conv_bwd_fns(acu: Acu, plan, a_bits: int, ctx):
    """The *approximate* conv STE backward pair for one resolved plan.

    Returns ``(gx_fn, gw_fn)``: ``gx_fn(g, wf, sg, sw) -> (N, Cin, H, W)``
    and ``gw_fn(xf, g, sx, sg) -> (Cout, Cin, kh, kw)``, both f32, operands
    float residuals with caller-computed per-tensor symmetric scales.

    ``plan.bwd_route == "banded"`` (LUT + Pallas + table): the weight-grad
    streams halo'd output-row bands through the
    ``fused_lut_conv_bwd_w`` kernel — contracting output pixels in-kernel,
    so the im2col patch tensor never exists in HBM — and the input-grad
    composes per-band ``fused_lut_bwd`` GEMMs whose int32 patch-gradient
    blocks scatter-add into an integer canvas (int adds are associative, so
    the band count is bitwise invisible) with ONE combined-scale dequant at
    the end. Under a mesh the weight-grad psums band-shard partials over the
    conv partition's rows axes and the per-band GEMM contraction shards over
    its cols axes (``acu_shard.wrap_conv_bwd_w`` / ``wrap_conv_gx_gemm``),
    bit-identical to single-device.

    Any other ``bwd_route`` falls back to materialized im2col + the dense
    approximate backward GEMMs (:func:`~repro.core.acu.matmul_bwd_plan`) —
    the audited fallback for degenerate geometry.
    """
    from .acu import AcuMode, matmul_bwd_plan
    from .quantization import pin_rounding as _pin
    spec = plan.spec
    n, cin, h, w_in = spec.x_shape
    cout, _, kh, kw = spec.w_shape
    ho, wo = spec.out_spatial
    sh, sw_ = spec.stride
    dh, dw = spec.dilation
    (ph0, ph1), (pw0, pw1) = spec.padding

    banded = (plan.bwd_route == "banded" and acu.mode == AcuMode.LUT
              and acu.use_pallas and acu.lut is not None)
    if not banded:
        bwd_plan = matmul_bwd_plan(acu, a_bits=a_bits, fused=plan.fused,
                                   mesh=ctx or False)
        gx_d, gw_d = bwd_plan.gx, bwd_plan.gw

        def gx_fn(g, wf, sg, sw):
            g2 = g.reshape(-1, cout).astype(jnp.float32)
            wfmat = wf.reshape(cout, -1).astype(jnp.float32)
            _, col_vjp = jax.vjp(
                lambda t: _im2col(t, kh, kw, spec.stride, spec.padding,
                                  spec.dilation)[0],
                jnp.zeros(spec.x_shape, jnp.float32))   # im2col is linear
            gcols = gx_d(g2, wfmat, sg, sw)             # (N*P, C*kh*kw) f32
            (gx,) = col_vjp(gcols.reshape(n, ho * wo, -1))
            return gx

        def gw_fn(xf, g, sx, sg):
            cols, _ = _im2col(xf.astype(jnp.float32), kh, kw, spec.stride,
                              spec.padding, spec.dilation)
            g2 = g.reshape(-1, cout).astype(jnp.float32)
            gw = gw_d(cols.reshape(-1, cols.shape[-1]).T, g2, sx, sg)
            return gw.T.reshape(cout, cin, kh, kw)

        return gx_fn, gw_fn

    from repro.kernels.fused_lut_conv import ops as cops
    from repro.kernels.fused_lut_dense import ops as fops
    bh_t, bn_t, mc_t, _ = plan.bwd_tiling
    part = plan.partition

    def gw_acc(x, g, rm, sx, sg, padding):
        # jnp.asarray stays inside: plans/STE fns are cached across traces
        return cops.fused_lut_conv_bwd_w(
            x, g, jnp.asarray(acu.lut), acu.offset, sx, sg,
            ksize=(kh, kw), stride=spec.stride, padding=padding,
            dilation=spec.dilation, bits=a_bits, bh=bh_t, bn=bn_t, mc=mc_t,
            interpret=acu.interpret, rmask=rm)

    if part is not None:
        from repro.parallel import acu_shard
        gw_call = acu_shard.wrap_conv_bwd_w(gw_acc, ctx, part, spec)
    else:
        gw_call = lambda xf, g, sx, sg: gw_acc(xf, g, None, sx, sg,
                                               spec.padding)

    def gw_fn(xf, g, sx, sg):
        acc = gw_call(xf.astype(jnp.float32), g, sx, sg)  # (kh*kw, Cin, Cout)
        s = _pin(jnp.asarray(sx, jnp.float32) * jnp.asarray(sg, jnp.float32))
        gw = acc.astype(jnp.float32) * s
        return gw.transpose(2, 1, 0).reshape(cout, cin, kh, kw)

    def gx_acc(a, b, sa, sb):
        return fops.fused_lut_bwd(a, b, acu.lut, acu.offset,
                                  sa, sb, bits=a_bits,
                                  interpret=acu.interpret, emit_acc=True)

    band_gemm = gx_acc
    if part is not None:
        from repro.parallel import acu_shard
        band_gemm = acu_shard.wrap_conv_gx_gemm(gx_acc, ctx, part, acu.m00())

    ckk = cin * kh * kw
    # band height for the input-grad: bound the per-band int32 patch-gradient
    # block — the only patch-shaped intermediate — to a slice of the budget
    from repro.kernels.fused_lut_conv.ops import CONV_VMEM_BUDGET
    bh_gx = max(1, min(ho, (CONV_VMEM_BUDGET // 4)
                       // max(1, 4 * n * wo * ckk)))
    hp_c = h + ph0 + ph1
    wp_c = w_in + pw0 + pw1

    def gx_fn(g, wf, sg, sw):
        wfmat = wf.reshape(cout, -1).astype(jnp.float32)    # (Cout, ckk)
        canvas = jnp.zeros((n, cin, hp_c, wp_c), jnp.int32)
        for s0 in range(0, ho, bh_gx):
            bhb = min(bh_gx, ho - s0)
            g_band = g[:, s0:s0 + bhb].reshape(-1, cout).astype(jnp.float32)
            acc = band_gemm(g_band, wfmat, sg, sw)   # (n*bhb*wo, ckk) int32
            acc = acc.reshape(n, bhb, wo, cin, kh, kw)
            for u in range(kh):
                r0 = s0 * sh + u * dh
                for v in range(kw):
                    c0 = v * dw
                    canvas = canvas.at[
                        :, :, r0:r0 + (bhb - 1) * sh + 1:sh,
                        c0:c0 + (wo - 1) * sw_ + 1:sw_,
                    ].add(acc[:, :, :, :, u, v].transpose(0, 3, 1, 2))
        canvas = canvas[:, :, ph0:ph0 + h, pw0:pw0 + w_in]
        s = _pin(jnp.asarray(sg, jnp.float32) * jnp.asarray(sw, jnp.float32))
        return canvas.astype(jnp.float32) * s

    return gx_fn, gw_fn


def _get_conv_ste_fn(acu: Acu, a_bits: int, w_bits: int, plan, ctx=None,
                     approx_bwd: bool = False):
    """Per-(ACU, geometry) custom_vjp conv: fused patch-streaming forward,
    STE backward — exact f32 by default, or through the ACU with
    ``approx_bwd`` (the ApproxTrain regime, see :func:`_conv_bwd_fns`).

    ``plan`` is the caller's already-resolved fused-conv
    :class:`~repro.core.acu.ConvPlan` (the route dispatches through it;
    under an active mesh it runs sharded per the ``acu_conv`` partition).
    The exact backward keeps explicit im2col — the weight-grad GEMM needs
    the patch matrix — but its two GEMMs route through the same spec-matched
    sharded wrappers as the dense STE (``gcols`` row-sharded like the output
    pixels, ``gw`` column-sharded like the output channels). The approximate
    backward follows ``plan.bwd_route`` instead — banded kernels that never
    materialize the patch tensor. Either way sharded QAT gradients stay
    bitwise identical to single-device ones.
    """
    assert plan.route in ("fused_conv", "tiled"), plan.route
    spec = plan.spec
    key = ("conv", plan.route, id(acu), a_bits, w_bits, spec, approx_bwd,
           plan.bwd_route if approx_bwd else None, _mesh_cache_key(ctx))
    if key in _STE_CACHE:
        return _STE_CACHE[key]

    cout, _, kh, kw = spec.w_shape
    if approx_bwd:
        gx_bwd, gw_bwd = _conv_bwd_fns(acu, plan, a_bits, ctx)
    elif plan.partition is not None:
        from repro.parallel.acu_shard import bwd_gemms
        gx_gemm, gw_gemm = bwd_gemms(ctx, plan.partition)
    else:
        gx_gemm = lambda g, wf: g @ wf.T
        gw_gemm = lambda xf, g: xf.T @ g

    @jax.custom_vjp
    def ste_conv(x, w, xs, xz, ws, wz):
        wqp = QParams(scale=ws, zero_point=wz, bits=w_bits, axis=0)
        wq = acu_operand(quantize(w, wqp), wqp)
        return plan(x, wq, xs, xz, ws)          # (N, Ho, Wo, Cout) f32

    def fwd(x, w, xs, xz, ws, wz):
        y = ste_conv(x, w, xs, xz, ws, wz)
        xqp = QParams(scale=xs, zero_point=xz, bits=a_bits)
        wqp = QParams(scale=ws, zero_point=wz, bits=w_bits, axis=0)
        xf = fake_quantize(x, xqp).astype(x.dtype)
        wf = fake_quantize(w, wqp).astype(w.dtype)
        return y, (xf, wf)

    if approx_bwd:
        from .quantization import inline_symmetric_scale

        def bwd(res, g):
            # scales on the FULL tensors (every mesh shard must see the same
            # ones), with the in-graph scale expression that compiles
            # identically across eager/jit/SPMD contexts
            xf, wf = res
            g = g.astype(jnp.float32)           # (N, Ho, Wo, Cout)
            sg = inline_symmetric_scale(jnp.max(jnp.abs(g)), a_bits)
            sx = inline_symmetric_scale(jnp.max(jnp.abs(xf)), a_bits)
            sw = inline_symmetric_scale(jnp.max(jnp.abs(wf)), a_bits)
            gx = gx_bwd(g, wf.astype(jnp.float32), sg, sw).astype(xf.dtype)
            gw = gw_bwd(xf, g, sx, sg).astype(wf.dtype)
            return (gx, gw, None, None, None, None)
    else:
        def bwd(res, g):
            xf, wf = res
            g2 = g.reshape(-1, cout).astype(jnp.float32)        # (N*P, Cout)
            wfmat = wf.reshape(cout, -1).T.astype(jnp.float32)  # (C*kh*kw, Cout)
            colsf, col_vjp = jax.vjp(
                lambda t: _im2col(t, kh, kw, spec.stride, spec.padding,
                                  spec.dilation)[0],
                xf.astype(jnp.float32))
            gcols = gx_gemm(g2, wfmat)                          # (N*P, C*kh*kw)
            gw = gw_gemm(colsf.reshape(-1, colsf.shape[-1]), g2)
            (gx,) = col_vjp(gcols.reshape(colsf.shape))
            return (gx.astype(xf.dtype),
                    gw.T.reshape(wf.shape).astype(wf.dtype),
                    None, None, None, None)

    ste_conv.defvjp(fwd, bwd)
    _STE_CACHE[key] = ste_conv
    return ste_conv


def conv_plan_report(x_shape: Sequence[int], w_shape: Sequence[int],
                     cfg: ApproxConfig, *, stride: Sequence[int] = (1, 1),
                     padding="SAME", dilation: Sequence[int] = (1, 1),
                     groups: int = 1) -> dict:
    """Resolve (without running) the conv route one layer would take under
    the current mesh context — route, fusion, partition spec, and every
    audited fallback. What ``examples/quickstart.py`` prints."""
    from .acu import ConvSpec, conv_plan, resolve_conv_padding
    stride, dilation = tuple(stride), tuple(dilation)
    pad = resolve_conv_padding(padding, tuple(x_shape), tuple(w_shape),
                               stride, dilation)
    spec = ConvSpec(x_shape=tuple(x_shape), w_shape=tuple(w_shape),
                    stride=stride, padding=pad, dilation=dilation,
                    groups=groups)
    fused = cfg.acu.fused if cfg.fused is None else cfg.fused
    return conv_plan(cfg.acu, spec, a_bits=cfg.a_bits,
                     fused=fused).describe()


def conv2d(x: Array, w: Array, b: Optional[Array] = None, *,
           stride: Sequence[int] = (1, 1), padding="SAME",
           dilation: Sequence[int] = (1, 1), groups: int = 1,
           cfg: Optional[ApproxConfig] = None, route: Optional[str] = None,
           xqp: Optional[QParams] = None, wqp: Optional[QParams] = None) -> Array:
    """2-D convolution with the full vanilla-PyTorch parameter surface
    (stride/padding/dilation/groups).

    ``x``: (N, Cin, H, W); ``w``: (Cout, Cin/groups, kh, kw). With an
    ``ApproxConfig`` the execution route is resolved by
    :func:`~repro.core.acu.conv_plan`: LUT-mode Pallas ACUs stream im2col
    patches inside one fused quantize->LUT-GEMM->dequant kernel — the
    whole-image variant when the image fits the VMEM budget, the
    spatially-tiled halo variant above it (ImageNet-scale feature maps) —
    everything else lowers to eager im2col + (approx) GEMM exactly as in
    the paper (§3.3.1, Fig. 3). ``route="im2col"`` pins the eager path
    (benchmark baseline / test oracle); ``route="tiled"`` pins the tiled
    kernel. ``xqp``/``wqp`` override the groups=1 quantizers (``wqp``
    per-output-channel, axis=0).
    """
    n, cin, _, _ = x.shape
    cout, cin_g, kh, kw = w.shape
    assert cin == cin_g * groups, (cin, cin_g, groups)

    if cfg is None:
        # exact substrate path: native conv (XLA picks the fast algorithm)
        pad = padding if isinstance(padding, str) else tuple(padding)
        y = jax.lax.conv_general_dilated(
            x, w, tuple(stride), pad, rhs_dilation=tuple(dilation),
            feature_group_count=groups,
            dimension_numbers=("NCHW", "OIHW", "NCHW"))
        if b is not None:
            y = y + b.reshape(1, -1, 1, 1)
        return y

    from .acu import ConvSpec, conv_plan, resolve_conv_padding
    stride, dilation = tuple(stride), tuple(dilation)
    pad = resolve_conv_padding(padding, x.shape, w.shape, stride, dilation)
    spec = ConvSpec(x_shape=tuple(x.shape), w_shape=tuple(w.shape),
                    stride=stride, padding=pad, dilation=dilation,
                    groups=groups)
    if cfg.fake_quant_only:
        # the fake-quant QAT path runs through approx_dense — the integer
        # LUT kernel would silently break the fake_quantize(x)@fake_quantize(w)
        # contract, so a pinned fused route is a caller error
        if route in ("fused_conv", "tiled"):
            raise ValueError(f"route={route!r} contradicts "
                             f"cfg.fake_quant_only (the fused kernel runs "
                             f"the integer ACU GEMM, not fake-quant)")
        route = "im2col"
    fused = cfg.acu.fused if cfg.fused is None else cfg.fused
    from repro.parallel.sharding import current_mesh_context
    ctx = current_mesh_context()
    plan = conv_plan(cfg.acu, spec, a_bits=cfg.a_bits, fused=fused,
                     mesh=ctx or False, route=route)

    if plan.route in ("fused_conv", "tiled"):
        xqp, wqp = _conv_qparams(x, w, cfg, xqp, wqp)
        fn = _get_conv_ste_fn(cfg.acu, cfg.a_bits, cfg.w_bits, plan, ctx=ctx,
                              approx_bwd=cfg.approx_bwd)
        y = fn(x, w, xqp.scale, xqp.zero_point, wqp.scale, wqp.zero_point)
        y = y.transpose(0, 3, 1, 2).astype(x.dtype)
    elif plan.route == "im2col":
        xqp, wqp = _conv_qparams(x, w, cfg, xqp, wqp)
        cols, (ho, wo) = _im2col(x, kh, kw, stride, pad, dilation)
        wmat = w.reshape(cout, -1).T                       # (C*kh*kw, Cout)
        m = cols.reshape(-1, cols.shape[-1])               # (N*Ho*Wo, C*kh*kw)
        wqp_mat = QParams(scale=wqp.scale, zero_point=wqp.zero_point,
                          bits=wqp.bits, axis=1)
        y = approx_dense(m, wmat, None, cfg, xqp=xqp, wqp=wqp_mat)
        y = y.reshape(n, ho, wo, cout).transpose(0, 3, 1, 2)
    elif plan.route == "im2col_depthwise":
        # depthwise through the ACU: single GEMM against a block-diagonal
        # weight. M[0, x] == 0 for every multiplier family here, so the
        # structural zeros are exact through the ACU.
        cols, (ho, wo) = _im2col(x, kh, kw, stride, pad, dilation)
        m = cols.reshape(-1, cols.shape[-1])               # (N*P, C*kh*kw)
        kk = kh * kw
        wblk = jnp.zeros((cin * kk, cout), x.dtype)
        ch = jnp.repeat(jnp.arange(cin), kk)
        rows = jnp.arange(cin * kk)
        mult = cout // cin
        wflat = w.reshape(cout, kk)  # channel c output o uses its own kernel
        for o_in_c in range(mult):
            cols_idx = ch * mult + o_in_c
            wblk = wblk.at[rows, cols_idx].set(
                wflat[ch * mult + o_in_c, jnp.tile(jnp.arange(kk), cin)])
        y = approx_dense(m, wblk, None, cfg)
        y = y.reshape(n, ho, wo, cout).transpose(0, 3, 1, 2)
    else:
        # grouped conv as ONE vmapped GEMM over the group axis: patch
        # features from a single im2col are channel-major, so each group's
        # block is a contiguous (cpg_in*kh*kw) slice. Traces O(1)
        # approx_dense calls instead of O(groups), and the per-group
        # activation qparams (amax inside the vmapped call) match the old
        # per-group loop bitwise.
        cpg_in, cpg_out = cin // groups, cout // groups
        cols, (ho, wo) = _im2col(x, kh, kw, stride, pad, dilation)
        kk = kh * kw
        m = cols.reshape(n, ho * wo, groups, cpg_in * kk)
        m = m.transpose(2, 0, 1, 3).reshape(groups, n * ho * wo, cpg_in * kk)
        wg = w.reshape(groups, cpg_out, cpg_in * kk).transpose(0, 2, 1)
        yg = jax.vmap(lambda mg, wgg: approx_dense(mg, wgg, None, cfg))(m, wg)
        y = yg.reshape(groups, n, ho * wo, cpg_out).transpose(1, 2, 0, 3)
        y = y.reshape(n, ho, wo, cout).transpose(0, 3, 1, 2)
    if b is not None:
        # same best-effort as approx_dense: keep dequant-multiply and
        # bias-add as two separate roundings across compilation contexts
        # (residual 1-ulp FMA caveat under jitted mesh programs —
        # docs/sharding.md)
        y = pin_rounding(y) + b.reshape(1, -1, 1, 1)
    return y


def separable_conv2d(x: Array, w_dw: Array, w_pw: Array,
                     b: Optional[Array] = None, *, stride=(1, 1), padding="SAME",
                     cfg: Optional[ApproxConfig] = None) -> Array:
    """Depthwise (groups=Cin) + pointwise (1x1) conv — paper eq. (3)."""
    cin = x.shape[1]
    y = conv2d(x, w_dw, None, stride=stride, padding=padding, groups=cin, cfg=cfg)
    return conv2d(y, w_pw, b, stride=(1, 1), padding="VALID", cfg=cfg)
