"""Approximate Compute Units (paper §3.3 / §3.4).

An :class:`Acu` packages one approximate multiplier with an emulation *mode*:

* ``FUNCTIONAL`` — evaluate the multiplier's closed form per scalar product and
  reduce. This is the paper's *unoptimized baseline* regime (the 76.5-min
  ResNet50 row): it materializes (or streams) the full (M, K, N) product
  tensor. Kept as the oracle and the speedup denominator.
* ``LUT`` — the paper's optimized engine, adapted to TPU: the (2^b, 2^b)
  product table lives in VMEM; each GEMM tile does vectorized gathers
  (``kernels/lut_matmul``). Bit-exact.
* ``LOWRANK`` — beyond-paper: exact int MXU matmul + rank-r SVD error
  correction (DESIGN.md §3). Near-exact, with fidelity measured offline.
* ``FACTORED`` — algebraically exact fast path for the truncation family:
  ``M[a,w] = (a & m)(w & m)`` is a single masked int matmul.
* ``EXACT`` — no approximation (quantization-only reference).

All modes consume *shifted-code* integer operands (``code - zero_point``).

Dispatch is two-level: :func:`matmul_plan` (dense GEMMs) and
:func:`conv_plan` (conv2d sites, mirroring it at static geometry) first
resolve (mode, bits, use_pallas, fused) to a kernel — the conv fused routes
are the patch-streaming ``kernels/fused_lut_conv`` kernels (whole-image
inside the VMEM budget, spatially tiled over halo'd output-row bands above
it), which never materialize the im2col patch tensor — then, when a
:class:`~repro.parallel.sharding.MeshContext` is active, wrap it in a
``shard_map`` over the production mesh (``parallel/acu_shard.py``): LUT
replicated, rows over ``("pod", "data")``, columns over ``("model",)``,
optional contraction sharding with an int32 psum before dequant. Every
route stays bit-exact against the single-device jnp oracle.
"""
from __future__ import annotations

import dataclasses
import enum
import functools
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

# the per-core VMEM budget for the fused conv kernels lives with the VMEM
# model in kernels/fused_lut_conv/ops.py (single source of truth);
# re-exported here as the planning-layer API. Images whose whole-image
# working set exceeds it resolve to the spatially-tiled kernel; geometries
# where even a one-row band exceeds it fall back to eager im2col.
from repro.kernels.fused_lut_conv.ops import CONV_VMEM_BUDGET

from .lut import LowRankError, build_lut, factorize_error, trunc_masks
from .multipliers import Multiplier, get_multiplier

Array = jnp.ndarray


class AcuMode(enum.Enum):
    FUNCTIONAL = "functional"
    LUT = "lut"
    LOWRANK = "lowrank"
    FACTORED = "factored"
    EXACT = "exact"


@dataclasses.dataclass(frozen=True)
class Acu:
    multiplier: Multiplier
    mode: AcuMode
    lut: Optional[np.ndarray] = None          # (2^b, 2^b) int32
    lowrank: Optional[LowRankError] = None
    mask: Optional[int] = None                # FACTORED path
    use_pallas: bool = False                  # route GEMMs through Pallas kernels
    interpret: bool | None = None             # None: repro.kernels.runtime default
    lut_chunk: int = 256                      # K-chunk for LUT gathers; 0 = the
                                              # paper's unoptimized baseline
                                              # (full (M,K,N) materialization)
    fused: bool = False                       # default routing for approx_ops:
                                              # single-kernel quantize->LUT
                                              # GEMM->dequant (LUT+Pallas only)

    @property
    def bits(self) -> int:
        return self.multiplier.bits

    @property
    def offset(self) -> int:
        return -self.multiplier.lo  # code shift into table index space

    def m00(self) -> int:
        """The multiplier's product at shifted code (0, 0) — the integer every
        padded-K entry contributes to an accumulator (0 for exact-at-zero
        families; the synthetic biased multipliers exercise the general case)."""
        if self.mode == AcuMode.LUT and self.lut is not None:
            return int(np.asarray(self.lut)[self.offset, self.offset])
        if self.mode in (AcuMode.EXACT, AcuMode.FACTORED, AcuMode.LOWRANK):
            return 0
        return int(self.multiplier(np.zeros((), np.int32),
                                   np.zeros((), np.int32)))

    # ------------------------------------------------------------------
    # elementwise multiply (used by tests and conv inner loops)
    # ------------------------------------------------------------------
    def mul(self, a: Array, w: Array) -> Array:
        if self.mode == AcuMode.EXACT:
            return a.astype(jnp.int32) * w.astype(jnp.int32)
        if self.mode == AcuMode.FACTORED:
            return (a & self.mask) * (w & self.mask)
        if self.mode == AcuMode.LUT:
            tab = jnp.asarray(self.lut)
            return tab[a + self.offset, w + self.offset]
        if self.mode == AcuMode.LOWRANK:
            exact = a.astype(jnp.float32) * w.astype(jnp.float32)
            f = jnp.asarray(self.lowrank.f)[a + self.offset]
            g = jnp.asarray(self.lowrank.g)[w + self.offset]
            return exact + (f * g).sum(-1)
        return self.multiplier(a, w)

    # ------------------------------------------------------------------
    # GEMM: out[m, n] = sum_k M[a[m, k], w[k, n]]
    # ------------------------------------------------------------------
    def matmul(self, a: Array, w: Array) -> Array:
        """Approximate GEMM on integer operands. Returns int32 (exact modes)
        or float32 (LOWRANK — the SVD correction is real-valued).

        Thin wrapper over :func:`matmul_plan` (the explicit dispatch layer);
        always the unfused integer-operand form. Mesh-aware: under an active
        :func:`~repro.parallel.sharding.use_mesh` the GEMM runs sharded.
        """
        return matmul_plan(self, fused=False)(a, w)

    # -- pure-jnp implementations (portable; Pallas kernels mirror these) --

    def _lut_matmul_jnp(self, a: Array, w: Array, k_chunk: int = 256) -> Array:
        tab = jnp.asarray(self.lut).reshape(-1)
        n_codes = self.multiplier.n_codes
        M, K = a.shape
        _, N = w.shape
        ai = (a + self.offset).astype(jnp.int32)
        wi = (w + self.offset).astype(jnp.int32)
        k_chunk = min(k_chunk, K)
        pad = (-K) % k_chunk
        if pad:
            ai = jnp.pad(ai, ((0, 0), (0, pad)), constant_values=self.offset)
            wi = jnp.pad(wi, ((0, pad), (0, 0)), constant_values=self.offset)
        nk = ai.shape[1] // k_chunk
        ai = ai.reshape(M, nk, k_chunk)
        wi = wi.reshape(nk, k_chunk, N)

        def body(acc, inputs):
            ac, wc = inputs  # (M, kc), (kc, N)
            idx = ac[:, :, None] * n_codes + wc[None, :, :]
            acc = acc + jnp.take(tab, idx.reshape(-1)).reshape(M, k_chunk, N).sum(axis=1)
            return acc, None

        init = jnp.zeros((M, N), jnp.int32)
        acc, _ = jax.lax.scan(body, init, (ai.transpose(1, 0, 2), wi))
        if pad:  # padded entries contribute LUT[off, off] = M[0, 0]
            zz = jnp.asarray(self.lut)[self.offset, self.offset].astype(jnp.int32)
            acc = acc - pad * zz
        return acc

    def _lowrank_matmul_jnp(self, a: Array, w: Array) -> Array:
        r = self.lowrank.rank
        K = a.shape[-1]
        exact = jax.lax.dot(
            a.astype(jnp.int8 if self.bits <= 8 else jnp.bfloat16),
            w.astype(jnp.int8 if self.bits <= 8 else jnp.bfloat16),
            preferred_element_type=jnp.int32 if self.bits <= 8 else jnp.float32,
        ).astype(jnp.float32)
        f = jnp.take(jnp.asarray(self.lowrank.f), a + self.offset, axis=0)  # (M,K,r)
        g = jnp.take(jnp.asarray(self.lowrank.g), w + self.offset, axis=0)  # (K,N,r)
        M = a.shape[0]
        N = w.shape[1]
        corr = f.reshape(M, K * r) @ g.transpose(0, 2, 1).reshape(K * r, N)
        return exact + corr

    def _functional_matmul_jnp(self, a: Array, w: Array, k_chunk: int = 32) -> Array:
        M, K = a.shape
        _, N = w.shape
        k_chunk = min(k_chunk, K)
        pad = (-K) % k_chunk
        if pad:
            a = jnp.pad(a, ((0, 0), (0, pad)))
            w = jnp.pad(w, ((0, pad), (0, 0)))
        nk = a.shape[1] // k_chunk
        ar = a.reshape(M, nk, k_chunk).transpose(1, 0, 2)
        wr = w.reshape(nk, k_chunk, N)

        def body(acc, inputs):
            ac, wc = inputs
            prods = self.multiplier(ac[:, :, None], wc[None, :, :])
            return acc + prods.sum(axis=1).astype(jnp.int64), None

        acc, _ = jax.lax.scan(body, jnp.zeros((M, N), jnp.int64), (ar, wr))
        if pad:
            z0 = self.multiplier(jnp.zeros((), jnp.int32), jnp.zeros((), jnp.int32))
            acc = acc - pad * z0.astype(jnp.int64)
        return acc.astype(jnp.int32)


# ---------------------------------------------------------------------------
# explicit dispatch layer: (mode, bits, use_pallas, fused) -> callable
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MatmulPlan:
    """A resolved GEMM route for one ACU.

    ``fused=False`` plans consume shifted integer operands and return the raw
    accumulator: ``plan(a, w) -> int32`` (float32 for LOWRANK). ``fused=True``
    plans run the whole quantize -> LUT GEMM -> dequant pipeline in one Pallas
    kernel: ``plan(x, wq, x_scale, x_zp, w_scale) -> float32`` where ``x`` is
    the float activation matrix and ``wq`` the shifted weight codes.

    ``route`` names the callable ``fn`` runs, recorded where it was
    chosen. ``partition`` records the mesh partition the plan executes
    under (``None`` = single-device); the wrapped ``fn`` already contains
    the ``shard_map`` — callers never change. A fused plan's ``tiles`` fill
    as its kernel is traced (:func:`_tile_log`).
    """

    mode: AcuMode
    bits: int
    use_pallas: bool
    fused: bool
    fn: Callable[..., Array]
    route: str
    partition: Optional[object] = None   # parallel.planner.GemmPartition
    tiles: Optional[dict] = dataclasses.field(default=None, compare=False)

    def __call__(self, *args) -> Array:
        return self.fn(*args)

    def describe(self) -> dict:
        part = self.partition
        d = {"route": self.route, "mode": self.mode.value,
             "bits": self.bits,
             "partition": None if part is None else
                 f"rows{part.rows}x cols{part.cols}x k{part.k}"}
        if self.tiles is not None:
            d["tiles"] = dict(self.tiles)
        return d


def _tile_log(acu: Acu):
    """``(tiles, log)`` for a fused forward, backward or grouped plan:
    ``log(m, k, n)``, called where a kernel is traced with an (m, k) x (k,
    n) GEMM (one shard's under a partition; m is one group's capacity for
    the grouped kernel), enters that shape's ``(bm, bk, bn)`` in ``tiles``
    as ``"MxKxN"``."""
    from repro.kernels.fused_lut_dense.ops import tiles as pick
    from repro.kernels.lut_gather import lut_planes
    planes, tiles = lut_planes(acu.lut), {}

    def log(m: int, k: int, n: int) -> None:
        tiles[f"{m}x{k}x{n}"] = pick(m, k, n, planes)

    return tiles, log


def _no_oracle_on_tpu(what: str, report) -> None:
    """The jnp oracles and exact fallbacks are CPU references: on the TPU a
    plan that would resolve to one raises instead of running it."""
    from repro.kernels.runtime import on_tpu
    if on_tpu():
        raise RuntimeError(f"{what} has no kernel route on the TPU: {report}")


def _resolve_unfused(acu: Acu) -> tuple[str, Callable[[Array, Array], Array]]:
    """The unfused integer-operand GEMM for ``acu`` (native int matmuls, the
    per-mode Pallas kernels, or — off the TPU only — the jnp oracles), with
    the name of its route."""
    if acu.mode == AcuMode.EXACT:
        def fn(a, w):
            if acu.bits <= 8:
                return jax.lax.dot(a.astype(jnp.int8), w.astype(jnp.int8),
                                   preferred_element_type=jnp.int32)
            return a.astype(jnp.int32) @ w.astype(jnp.int32)
        return "exact_int_matmul", fn
    if acu.mode == AcuMode.FACTORED:
        def fn(a, w):
            return (a & acu.mask).astype(jnp.int32) @ \
                   (w & acu.mask).astype(jnp.int32)
        return "factored_int_matmul", fn
    if acu.mode == AcuMode.LUT:
        if acu.use_pallas:
            from repro.kernels.lut_matmul import ops as lops
            return "lut_matmul", lambda a, w: lops.lut_matmul(
                a, w, acu.lut, acu.offset, interpret=acu.interpret)
        _no_oracle_on_tpu("LUT GEMM", "use_pallas=False")
        if acu.lut_chunk == 0:
            # paper's "baseline approximate": LUTs without the
            # vectorization/chunking optimizations — one (M, K, N) gather
            from repro.kernels.lut_matmul.ref import lut_matmul_ref
            return "lut_jnp_oracle", lambda a, w: lut_matmul_ref(
                a, w, jnp.asarray(acu.lut).reshape(-1), acu.offset,
                acu.multiplier.n_codes)
        return "lut_jnp_oracle", lambda a, w: acu._lut_matmul_jnp(
            a, w, k_chunk=acu.lut_chunk)
    if acu.mode == AcuMode.LOWRANK:
        if acu.use_pallas:
            from repro.kernels.err_matmul import ops as eops
            return "err_matmul", lambda a, w: eops.err_matmul(
                a, w, jnp.asarray(acu.lowrank.f), jnp.asarray(acu.lowrank.g),
                acu.offset, interpret=acu.interpret)
        _no_oracle_on_tpu("LOWRANK GEMM", "use_pallas=False")
        return "lowrank_jnp_oracle", acu._lowrank_matmul_jnp
    # FUNCTIONAL: stream over K chunks to bound the (M, Kc, N) intermediate
    _no_oracle_on_tpu("FUNCTIONAL GEMM", "jnp only")
    return "functional_jnp_oracle", acu._functional_matmul_jnp


def _resolve_mesh(mesh):
    """``mesh`` arg -> active MeshContext or None. ``None`` auto-detects the
    ambient :func:`~repro.parallel.sharding.use_mesh` context; ``False``
    forces single-device resolution."""
    if mesh is False:
        return None
    if mesh is None:
        from repro.parallel.sharding import current_mesh_context
        return current_mesh_context()
    return mesh


def matmul_plan(acu: Acu, *, a_bits: Optional[int] = None,
                fused: Optional[bool] = None, mesh=None) -> MatmulPlan:
    """Resolve (mode, bits, use_pallas, fused) x mesh into a concrete GEMM
    callable.

    ``a_bits`` is the activation code width a fused plan quantizes/clips to
    (defaults to the ACU operand width). A fused request that cannot be
    served — non-LUT mode, no Pallas routing, or no table — silently falls
    back to the unfused plan, so callers can request fusion unconditionally
    and keep the pure-jnp implementations as bit-exact oracles. On the TPU
    an unfused plan that would be a jnp oracle raises instead.

    ``mesh``: ``None`` auto-detects the active
    :class:`~repro.parallel.sharding.MeshContext` (plans resolved under
    :func:`~repro.parallel.sharding.use_mesh` run sharded — LUT replicated,
    rows over the ``acu_rows`` axes, columns over ``acu_cols``, optional
    ``acu_k`` contraction sharding with an int32 psum before dequant); a
    :class:`MeshContext` pins one explicitly; ``False`` forces the
    single-device route. Sharded plans stay bit-exact vs their single-device
    counterparts — the wrap only changes where tiles execute.
    """
    fused = acu.fused if fused is None else fused
    a_bits = acu.bits if a_bits is None else a_bits
    ctx = _resolve_mesh(mesh)
    partition = None
    if ctx is not None:
        from repro.parallel import acu_shard
        partition = acu_shard.resolve_partition(
            ctx, float_accum=acu.mode == AcuMode.LOWRANK)

    if fused and acu.mode == AcuMode.LUT and acu.use_pallas \
            and acu.lut is not None:
        from repro.kernels.fused_lut_dense import ops as fops
        tiles, log = _tile_log(acu)

        def fused_call(x, wq, x_scale, x_zp, w_scale, *, emit_acc=False):
            # the host table goes in as is: plans are cached across jit
            # traces (a device constant made in one must not leak into
            # another), and the kernel wrapper reads its digit-plane count
            # from the concrete values
            log(x.shape[0], *wq.shape)
            return fops.fused_lut_dense(x, wq, acu.lut,
                                        acu.offset, x_scale, x_zp, w_scale,
                                        bits=a_bits, interpret=acu.interpret,
                                        emit_acc=emit_acc)
        fn = fused_call
        if partition is not None:
            fn = acu_shard.wrap_fused(
                fused_call,
                lambda *args: fused_call(*args, emit_acc=True),
                ctx, partition, acu.m00())
        return MatmulPlan(mode=acu.mode, bits=acu.bits, use_pallas=True,
                          fused=True, fn=fn, route="fused_lut_dense",
                          partition=partition, tiles=tiles)

    route, fn = _resolve_unfused(acu)
    if partition is not None:
        fn = acu_shard.wrap_unfused(fn, ctx, partition, acu.m00())
    return MatmulPlan(mode=acu.mode, bits=acu.bits, use_pallas=acu.use_pallas,
                      fused=False, fn=fn, route=route, partition=partition)


class MatmulBwdPlan(NamedTuple):
    """The approximate STE backward GEMM pair, the route both run and, for
    the fused route, the tiles of the kernels traced so far (as
    :attr:`MatmulPlan.tiles`)."""

    gx: Callable[..., Array]
    gw: Callable[..., Array]
    route: str
    tiles: Optional[dict] = None

    def describe(self) -> dict:
        d = {"route": self.route}
        if self.tiles is not None:
            d["tiles"] = dict(self.tiles)
        return d


def matmul_bwd_plan(acu: Acu, *, a_bits: Optional[int] = None,
                    fused: Optional[bool] = None, mesh=None
                    ) -> MatmulBwdPlan:
    """Resolve the *approximate* STE backward GEMM pair for one ACU.

    Returns ``(gx_fn, gw_fn, route, tiles)``; each fn is
    ``fn(a, b, sa, sb) -> f32 (M, N)`` computing the approximate GEMM of two **float** operands quantized
    per-tensor symmetric (zero-point 0 — gradients are zero-centred) with a
    single combined-scale dequant ``acc * (sa * sb)``. The caller computes
    ``sa``/``sb`` on the full tensors (``symmetric_qparams(amax, a_bits)``)
    so every mesh shard sees identical scales. The two callables differ only
    in their mesh partition: each backward GEMM is the forward GEMM with
    permuted roles (``gx = g @ wf.T`` contracts the forward's cols,
    ``gw = xf.T @ g`` contracts the forward's rows), so the permuted
    partitions from :func:`~repro.parallel.planner.bwd_gemm_partitions`
    keep the residuals sharded exactly as the forward left them and psum
    the int32 partials over the contraction axes before dequant.

    Fused (LUT + Pallas + table) resolves to the in-kernel-quantizing
    ``fused_lut_bwd`` kernel; everything else quantizes outside and runs
    the mode's unfused integer GEMM — the two are bit-identical for LUT
    mode, making the unfused composition the test oracle. LOWRANK
    (float accumulator) computes replicated under a mesh: its partials
    cannot psum bit-exactly.
    """
    fused = acu.fused if fused is None else fused
    a_bits = acu.bits if a_bits is None else a_bits
    ctx = _resolve_mesh(mesh)
    gx_part = gw_part = None
    if ctx is not None and acu.mode != AcuMode.LOWRANK:
        from repro.parallel import acu_shard
        fwd_part = acu_shard.resolve_partition(ctx)
        if fwd_part is not None:
            from repro.parallel.planner import bwd_gemm_partitions
            gx_part, gw_part = bwd_gemm_partitions(fwd_part)

    if fused and acu.mode == AcuMode.LUT and acu.use_pallas \
            and acu.lut is not None:
        from repro.kernels.fused_lut_dense import ops as fops
        tiles, log = _tile_log(acu)

        def bwd_call(a, b, sa, sb, *, emit_acc=False):
            # host table as is: see fused_call in matmul_plan
            log(*a.shape, b.shape[1])
            return fops.fused_lut_bwd(a, b, acu.lut, acu.offset,
                                      sa, sb, bits=a_bits,
                                      interpret=acu.interpret,
                                      emit_acc=emit_acc)

        def route(part):
            if part is None:
                return lambda a, b, sa, sb: bwd_call(a, b, sa, sb)
            from repro.parallel import acu_shard
            return acu_shard.wrap_fused_bwd(
                bwd_call, lambda *args: bwd_call(*args, emit_acc=True),
                ctx, part, acu.m00())

        return MatmulBwdPlan(route(gx_part), route(gw_part), "fused_lut_bwd",
                             tiles)

    # unfused: quantize outside (full tensors, global scales), run the
    # mode's integer GEMM — sharded via the permuted partition when a mesh
    # is active — dequant once. Bit-identical to the fused kernel for LUT
    # mode (same quantizer expression, same int32 sums, same combined-scale
    # rounding), so this composition doubles as the bit-exactness oracle.
    base_route, base = _resolve_unfused(acu)
    lo = -(1 << (a_bits - 1))
    hi = (1 << (a_bits - 1)) - 1

    def route(part):
        gemm = base
        if part is not None:
            from repro.parallel import acu_shard
            gemm = acu_shard.wrap_unfused(base, ctx, part, acu.m00())

        def fn(a, b, sa, sb):
            from .quantization import pin_rounding
            sa_ = jnp.asarray(sa, jnp.float32)
            sb_ = jnp.asarray(sb, jnp.float32)
            qa = jnp.clip(jnp.round(a.astype(jnp.float32) / sa_), lo, hi
                          ).astype(jnp.int32)
            qb = jnp.clip(jnp.round(b.astype(jnp.float32) / sb_), lo, hi
                          ).astype(jnp.int32)
            acc = gemm(qa, qb)
            return acc.astype(jnp.float32) * pin_rounding(sa_ * sb_)

        return fn

    return MatmulBwdPlan(route(gx_part), route(gw_part),
                         f"quantized_{base_route}")


# ---------------------------------------------------------------------------
# conv planning layer: geometry x (mode, bits, use_pallas, fused) x mesh
# ---------------------------------------------------------------------------

def resolve_conv_padding(padding, x_shape, w_shape, stride, dilation
                         ) -> tuple[tuple[int, int], tuple[int, int]]:
    """Normalize SAME/VALID/explicit conv padding to per-edge pairs, with
    XLA's SAME split (lo = total // 2) so every route — fused kernel, eager
    im2col, exact lax.conv — sees identical geometry."""
    if not isinstance(padding, str):
        (p0, p1) = tuple(padding)
        return (tuple(p0), tuple(p1))
    if padding.upper() == "VALID":
        return ((0, 0), (0, 0))
    if padding.upper() != "SAME":
        raise ValueError(f"unsupported padding {padding!r}")
    pads = []
    for d in range(2):
        size = x_shape[2 + d]
        eff_k = (w_shape[2 + d] - 1) * dilation[d] + 1
        out = -(-size // stride[d])
        total = max((out - 1) * stride[d] + eff_k - size, 0)
        pads.append((total // 2, total - total // 2))
    return (pads[0], pads[1])


@dataclasses.dataclass(frozen=True)
class ConvSpec:
    """Static geometry of one conv2d site (hashable: plan / STE cache key).

    ``x_shape``: (N, Cin, H, W); ``w_shape``: (Cout, Cin/groups, kh, kw);
    ``padding``: explicit ((ph_lo, ph_hi), (pw_lo, pw_hi)) — use
    :func:`resolve_conv_padding` to normalize SAME/VALID first.
    """

    x_shape: tuple[int, int, int, int]
    w_shape: tuple[int, int, int, int]
    stride: tuple[int, int] = (1, 1)
    padding: tuple[tuple[int, int], tuple[int, int]] = ((0, 0), (0, 0))
    dilation: tuple[int, int] = (1, 1)
    groups: int = 1

    @property
    def out_spatial(self) -> tuple[int, int]:
        from repro.kernels.fused_lut_conv.ops import conv_out_size
        return (conv_out_size(self.x_shape[2], self.w_shape[2],
                              self.stride[0], self.dilation[0],
                              self.padding[0]),
                conv_out_size(self.x_shape[3], self.w_shape[3],
                              self.stride[1], self.dilation[1],
                              self.padding[1]))

    @property
    def gemm_shape(self) -> tuple[int, int, int]:
        """(M, K, N) of the implicit im2col GEMM."""
        ho, wo = self.out_spatial
        cout, cg, kh, kw = self.w_shape
        return (self.x_shape[0] * ho * wo, cg * kh * kw, cout)


def _conv_geometry_args(spec: ConvSpec) -> tuple:
    _, c, h, w = spec.x_shape
    cout, _, kh, kw = spec.w_shape
    return (c, h, w, cout, kh, kw, spec.stride[0], spec.stride[1],
            spec.dilation[0], spec.dilation[1], spec.padding)


def _conv_vmem_estimate(spec: ConvSpec, n_codes: int) -> int:
    """Working-set bytes of the whole-image fused conv kernel at this
    geometry, from the kernel's own tile picks and exact padded extents
    (``conv_vmem_bytes`` — one source of truth, including the
    ``(kh-1)*dilation`` halo rows the pre-PR 4 stride-only estimate
    omitted)."""
    from repro.kernels.fused_lut_conv.ops import conv_vmem_bytes
    return conv_vmem_bytes(*_conv_geometry_args(spec), n_codes)


def _fmt_vmem(nbytes: int) -> str:
    """Byte counts in audited report strings: MiB at image scale, KiB below
    (tests resolve tiled plans against shrunken budgets)."""
    if nbytes >= (1 << 20):
        return f"{nbytes >> 20} MiB"
    return f"{nbytes >> 10} KiB"


def _conv_spatial_tiling(spec: ConvSpec, n_codes: int, budget: int
                         ) -> Optional[tuple[int, int, int, int]]:
    """(inner, bh, bn, n_copies) for the spatially-tiled kernel, or None
    when the geometry is degenerate (even a one-row band exceeds the
    budget)."""
    from repro.kernels.fused_lut_conv.ops import pick_conv_spatial_tiling
    return pick_conv_spatial_tiling(*_conv_geometry_args(spec), n_codes,
                                    budget=budget)


@dataclasses.dataclass(frozen=True)
class ConvPlan:
    """A resolved conv2d route for one ACU at one static geometry.

    ``route`` is one of

    * ``"fused_conv"`` — the whole-image patch-streaming Pallas kernel
      (``kernels/fused_lut_conv``): im2col, quantize, LUT-GEMM and dequant in
      one pass, the patch tensor never materialized. ``fn(x, wq, xs, xz, ws)
      -> (N, Ho, Wo, Cout) f32`` with ``x`` the float NCHW activations and
      ``wq`` the (Cout, Cin, kh, kw) shifted weight codes; mesh-wrapped when
      a partition is active (callers never change).
    * ``"tiled"`` — the spatially-tiled variant of the same kernel: grid
      over output-row bands, only the halo'd input rows of one band
      VMEM-resident per step. Same ``fn`` signature and bit-identical
      output; chosen when the whole-image working set exceeds the VMEM
      budget (ImageNet-scale feature maps), with the picked tiling recorded
      in ``tiling`` and named in the report.
    * ``"im2col"`` — eager patch extraction + the dense ``matmul_plan`` route
      (which itself resolves fused/unfused x mesh). The audited fallback for
      non-LUT modes, non-Pallas ACUs, and truly degenerate geometry (even a
      one-row band over budget); also the oracle the fused kernels are
      tested against. ``fn`` is None: the caller composes quantize -> GEMM
      -> dequant as before.
    * ``"im2col_depthwise"`` / ``"im2col_grouped"`` — the block-diagonal and
      single-vmapped-GEMM group routes (PR 2 semantics, bitwise preserved).
      ``fn`` is None.

    ``partition`` is the ``acu_conv`` partition for the fused routes (batch
    x output-pixel rows over ``acu_conv_rows`` — with bands over the same
    axes when the batch alone cannot fill them, see
    ``acu_shard.wrap_fused_conv`` — output channels over ``acu_conv_cols``,
    opt-in input-channel contraction over ``acu_conv_k``), or the dense GEMM
    partition the im2col routes will resolve. ``report`` carries every
    audited fallback decision. ``tiling`` is the resolved
    ``(inner, bh, bn, n_copies)`` spatial tiling for the tiled route.

    ``bwd_route`` resolves where the *approximate* STE backward runs when a
    consumer enables it (``ApproxConfig.approx_bwd``): ``"banded"`` — the
    weight-grad streams halo'd output-row bands through
    ``kernels/fused_lut_conv.fused_lut_conv_bwd_w`` and the input-grad
    composes per-band ``fused_lut_bwd`` GEMMs with an integer scatter, so
    the im2col patch tensor never materializes in the backward either;
    ``"im2col"`` — the audited fallback (degenerate geometry under the same
    VMEM budget) that materializes patches and runs the dense approximate
    backward GEMMs. ``None`` for plans whose forward is not fused (their
    backward composes through the dense STE as before).
    ``bwd_tiling`` is the resolved ``(bh, bn, mc, n_copies)`` banding.
    """

    mode: AcuMode
    bits: int
    use_pallas: bool
    fused: bool
    route: str
    spec: ConvSpec
    fn: Optional[Callable[..., Array]] = None
    partition: Optional[object] = None
    report: tuple[str, ...] = ()
    tiling: Optional[tuple[int, int, int, int]] = None
    bwd_route: Optional[str] = None
    bwd_tiling: Optional[tuple[int, int, int, int]] = None

    def __call__(self, *args) -> Array:
        assert self.fn is not None, f"route {self.route} has no direct kernel"
        return self.fn(*args)

    def describe(self) -> dict:
        """Human-readable resolution report (examples/quickstart.py prints
        this so users can see which path their model took)."""
        part = self.partition
        m, k, n = self.spec.gemm_shape
        tiling = None
        if self.tiling is not None:
            inner, bh, bn, n_copies = self.tiling
            ho, _ = self.spec.out_spatial
            tiling = (f"bands of {bh} output rows ({-(-ho // bh)} bands, "
                      f"{n_copies} halo blocks/band, inner={inner} bn={bn})")
        return {
            "route": self.route,
            "bwd_route": self.bwd_route,
            "mode": self.mode.value,
            "fused": self.fused,
            "gemm": f"M={m} K={k} N={n}",
            "tiling": tiling,
            "partition": None if part is None else
                f"rows{part.rows}x cols{part.cols}x k{part.k} "
                f"({part.n_rows}x{part.n_cols}x{part.n_k} way)",
            "report": list(self.report) + (list(part.report) if part else []),
        }


def conv_plan(acu: Acu, spec: ConvSpec, *, a_bits: Optional[int] = None,
              fused: Optional[bool] = None, mesh=None,
              route: Optional[str] = None,
              vmem_budget: Optional[int] = None) -> ConvPlan:
    """Resolve one conv2d site: geometry x (mode, bits, use_pallas, fused) x
    mesh -> a concrete route. Mirrors :func:`matmul_plan`, with the same
    silent-but-audited fallback contract: a fused request that cannot be
    served by the whole-image kernel (groups, non-LUT mode, no Pallas, no
    table) resolves to the eager im2col route; one that only exceeds the
    VMEM budget resolves to the spatially-tiled kernel (``route="tiled"``,
    the chosen banding named in ``plan.report``); eager im2col remains only
    for truly degenerate geometry where even a one-row band is over budget.

    ``route`` pins a route explicitly (``"im2col"`` forces the eager path —
    the benchmark baseline and test oracle; ``"fused_conv"`` / ``"tiled"``
    raise if that kernel cannot serve the request instead of falling back).
    ``vmem_budget`` overrides :data:`CONV_VMEM_BUDGET` (tests exercise the
    tiled resolution on small geometry with a shrunken budget).
    """
    fused = acu.fused if fused is None else fused
    a_bits = acu.bits if a_bits is None else a_bits
    budget = CONV_VMEM_BUDGET if vmem_budget is None else vmem_budget
    ctx = _resolve_mesh(mesh)
    report: list[str] = []

    cout, cin_g, kh, kw = spec.w_shape
    cin = spec.x_shape[1]
    if route not in (None, "fused_conv", "tiled", "im2col"):
        raise ValueError(f"unknown conv route {route!r}")
    want_fused = fused or route in ("fused_conv", "tiled")
    can_fuse = True
    if spec.groups != 1:
        can_fuse = False
        if want_fused:
            report.append(f"groups={spec.groups}: fused conv serves groups=1 "
                          f"only; grouped route keeps the single-vmapped-GEMM "
                          f"semantics")
    if not (acu.mode == AcuMode.LUT and acu.use_pallas
            and acu.lut is not None):
        can_fuse = False
        if want_fused and spec.groups == 1:
            report.append(f"fused conv needs LUT mode + use_pallas + a built "
                          f"table (have mode={acu.mode.value}, "
                          f"use_pallas={acu.use_pallas})")

    if route == "im2col":
        # pinned before the budget resolution: an im2col-pinned plan must
        # not run (or report) a tiling it will never use
        can_fuse = False
        report.append("route pinned to eager im2col by caller")

    whole_ok = False
    tiling = None
    if can_fuse and want_fused:
        est = _conv_vmem_estimate(spec, acu.multiplier.n_codes)
        whole_ok = est <= budget
        if route == "tiled" or not whole_ok:
            tiling = _conv_spatial_tiling(spec, acu.multiplier.n_codes,
                                          budget)
        if not whole_ok:
            if tiling is not None:
                inner, bh, bn, n_copies = tiling
                ho, _ = spec.out_spatial
                report.append(
                    f"image working set ~{_fmt_vmem(est)} exceeds the "
                    f"{_fmt_vmem(budget)} VMEM budget; spatially tiled over "
                    f"output-row bands (bands of {bh} output rows, "
                    f"{-(-ho // bh)} bands, {n_copies} halo blocks/band)")
            else:
                report.append(
                    f"image working set ~{_fmt_vmem(est)} exceeds the "
                    f"{_fmt_vmem(budget)} VMEM budget and even a one-row "
                    f"band does not fit (degenerate geometry); falling "
                    f"back to eager im2col")
        elif route == "tiled":
            report.append("route pinned to spatially-tiled kernel by caller")

    if route == "fused_conv" and not (can_fuse and whole_ok):
        raise ValueError(f"fused_conv route unavailable: {report}")
    if route == "tiled" and not (can_fuse and tiling is not None):
        raise ValueError(f"tiled route unavailable: {report}")

    serve_tiled = can_fuse and want_fused and tiling is not None \
        and (route == "tiled" or not whole_ok)
    serve_whole = can_fuse and want_fused and whole_ok and route != "tiled"

    if serve_whole or serve_tiled:
        from repro.kernels.fused_lut_conv import ops as cops
        from repro.parallel import acu_shard
        partition = None
        if ctx is not None:
            partition = acu_shard.resolve_conv_partition(
                ctx, float_accum=acu.mode == AcuMode.LOWRANK)
        geom = dict(stride=spec.stride, dilation=spec.dilation)
        if serve_tiled:
            inner, bh, bn, _ = tiling
            kernel_fn = functools.partial(cops.fused_lut_conv_tiled,
                                          inner=inner, bh=bh, bn=bn)
        else:
            kernel_fn = cops.fused_lut_conv

        def fused_call(x, wq, xs, xz, ws, *, emit_acc=False, padding=None):
            # jnp.asarray stays inside: plans are cached across jit traces
            # and a device constant created during one trace must not leak
            # into another. ``padding`` override: the banded mesh wrap
            # pre-pads its halo'd row slabs and calls back with zero row
            # padding (acu_shard.wrap_fused_conv).
            return kernel_fn(
                x, wq, jnp.asarray(acu.lut), acu.offset, xs, xz, ws,
                bits=a_bits, interpret=acu.interpret, emit_acc=emit_acc,
                padding=spec.padding if padding is None else padding, **geom)

        fn = fused_call
        if partition is not None:
            fn = acu_shard.wrap_fused_conv(
                fused_call,
                lambda *args, **kw: fused_call(*args, emit_acc=True, **kw),
                ctx, partition, acu.m00(), kh * kw, spec=spec)

        # resolve where the approximate backward would run, under the same
        # budget: the banded weight-grad kernel when its band model fits,
        # the audited materialized-im2col fallback otherwise. Resolved for
        # every fused plan (it is pure geometry) — only approx_bwd
        # consumers act on it.
        from repro.kernels.fused_lut_conv.ops import pick_conv_bwd_tiling
        bwd_tiling = pick_conv_bwd_tiling(*_conv_geometry_args(spec),
                                          acu.multiplier.n_codes,
                                          budget=budget)
        if bwd_tiling is None:
            report.append("approx backward: even a one-row band exceeds the "
                          "VMEM budget; weight-grad falls back to "
                          "materialized im2col")
        return ConvPlan(mode=acu.mode, bits=acu.bits, use_pallas=True,
                        fused=True,
                        route="tiled" if serve_tiled else "fused_conv",
                        spec=spec, fn=fn, partition=partition,
                        report=tuple(report),
                        tiling=tiling if serve_tiled else None,
                        bwd_route="banded" if bwd_tiling is not None
                        else "im2col",
                        bwd_tiling=bwd_tiling)

    if spec.groups == 1:
        r = "im2col"
    elif spec.groups == cin and cin_g == 1:
        r = "im2col_depthwise"
    else:
        r = "im2col_grouped"
    partition = None
    if ctx is not None:
        from repro.parallel import acu_shard
        partition = acu_shard.resolve_partition(
            ctx, float_accum=acu.mode == AcuMode.LOWRANK)
    return ConvPlan(mode=acu.mode, bits=acu.bits, use_pallas=acu.use_pallas,
                    fused=fused, route=r, spec=spec, partition=partition,
                    report=tuple(report))


# ---------------------------------------------------------------------------
# attention planning layer: GQA geometry x (mode, bits, use_pallas) x mesh
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnSpec:
    """Static geometry of one attention site (hashable: plan cache key).

    ``hq``/``hkv``: query / KV head counts (``hq % hkv == 0``, GQA);
    ``causal``/``window``/``softcap``: the mask/logit statics;
    ``bq``/``bk``: kernel tile sizes (shrunk automatically for short
    sequences by the kernel wrapper). Sequence lengths are deliberately NOT
    part of the spec — the kernel geometry adapts per call, so one plan
    serves prefill and decode.

    ``kv_layout`` selects how the kernel reads KV:

    * ``"contiguous"`` — K/V arrive as per-row ``(B, Hkv, Sk, D)`` tensors.
    * ``"paged"`` — K/V live in a shared physical block pool
      ``(Hkv, P, bk, D)`` and each row reads through an int32 page table;
      ``bk`` is then also the paged block size (the pool's block extent
      must equal it). The serve engine's block allocator owns the pool.
    """

    hq: int
    hkv: int
    causal: bool = True
    window: Optional[int] = None
    softcap: Optional[float] = None
    bq: int = 128
    bk: int = 128
    kv_layout: str = "contiguous"


@dataclasses.dataclass(frozen=True)
class AttnPlan:
    """A resolved attention route for one ACU at one static geometry.

    ``route`` is one of

    * ``"fused_attn"`` — approximate flash attention
      (``kernels/flash_attention.approx``): per-tensor quantize of Q/K/V
      in-kernel, QK^T and PV as int32 LUT-gather GEMMs inside the streaming
      softmax, pad corrections in integer space, dequant folded into the
      running rescale. ``fn(q, k, v, q_scale, k_scale, v_scale, rowinfo)
      -> (B, Hq, Sq, D) f32`` with ``q`` (B, Hq, Sq, D) float, ``k``/``v``
      (B, Hkv, Sk, D), per-tensor scales computed by the caller on the FULL
      tensors (``inline_symmetric_scale`` — mesh shards must see identical
      scales), and ``rowinfo`` (B, 3) int32 ``[q_base, kv_start, kv_len]``
      rows (``None`` = the end-aligned full-sequence default). Mesh-wrapped
      when a partition is active — batch over ``acu_attn_rows``, KV heads
      over ``acu_attn_heads``, no collectives, bit-exact by construction.
    * ``"fused_attn_paged"`` — the same approximate flash attention reading
      KV through a per-row page table
      (``spec.kv_layout == "paged"``): ``fn(q, k_pool, v_pool, q_scale,
      k_scale, v_scale, rowinfo, page_table) -> (B, Hq, Sq, D) f32`` with
      ``k_pool``/``v_pool`` ``(Hkv, P, spec.bk, D)`` physical block pools
      shared by all rows, ``page_table`` ``(B, n_logical)`` int32 logical →
      physical block ids (repeated per query head internally), and
      ``rowinfo`` REQUIRED (there is no sensible full-pool default).
      Bitwise-identical to the contiguous route when the gathered blocks
      hold the same values. Mesh-wrapped like the contiguous route with the
      pool sharded over KV heads and the page table replicated per row
      shard.
    * ``"dense"`` — the audited fallback for non-LUT modes, non-Pallas ACUs
      and missing tables: ``fn`` is None and the caller keeps its exact
      float attention path (models/layers.py) — attention runs exact, only
      the projections/MLP run approximately, mirroring the conv plan's
      eager-im2col contract. Under ``kv_layout == "paged"`` the caller
      additionally gathers pool blocks back to a contiguous layout first
      (exact math is layout-independent, so the gather is just indexing).
    """

    mode: AcuMode
    bits: int
    use_pallas: bool
    route: str
    spec: AttnSpec
    fn: Optional[Callable[..., Array]] = None
    partition: Optional[object] = None
    report: tuple[str, ...] = ()

    def __call__(self, *args) -> Array:
        assert self.fn is not None, f"route {self.route} has no direct kernel"
        return self.fn(*args)

    def describe(self) -> dict:
        part = self.partition
        return {
            "route": self.route,
            "mode": self.mode.value,
            "heads": f"hq={self.spec.hq} hkv={self.spec.hkv} "
                     f"(rep={self.spec.hq // self.spec.hkv})",
            "kv_layout": self.spec.kv_layout
                + (f" (block={self.spec.bk})"
                   if self.spec.kv_layout == "paged" else ""),
            "mask": f"causal={self.spec.causal} window={self.spec.window} "
                    f"softcap={self.spec.softcap}",
            "partition": None if part is None else
                f"rows{part.rows}x heads{part.cols} "
                f"({part.n_rows}x{part.n_cols} way)",
            "report": list(self.report) + (list(part.report) if part else []),
        }


def attn_plan(acu: Acu, spec: AttnSpec, *, a_bits: Optional[int] = None,
              mesh=None, route: Optional[str] = None) -> AttnPlan:
    """Resolve one attention site: GQA geometry x (mode, bits, use_pallas) x
    mesh -> a concrete route. Mirrors :func:`conv_plan`'s silent-but-audited
    fallback contract: an ACU that cannot serve the fused approximate kernel
    (non-LUT mode, no Pallas routing, no table) resolves to ``"dense"`` —
    the caller keeps its exact float attention. ``route`` pins one
    explicitly (``"fused_attn"`` raises if unavailable; ``"dense"`` forces
    the exact path). On the TPU the unpinned fallback raises instead.

    There is no unfused approximate attention route on purpose: the unfused
    composition (``approx_attention_ref``) exists as the bit-exactness
    oracle, not a serving path.
    """
    a_bits = acu.bits if a_bits is None else a_bits
    ctx = _resolve_mesh(mesh)
    report: list[str] = []
    if spec.hq % spec.hkv != 0:
        raise ValueError(f"hq={spec.hq} not a multiple of hkv={spec.hkv}")
    if spec.kv_layout not in ("contiguous", "paged"):
        raise ValueError(f"unknown kv_layout {spec.kv_layout!r}")
    paged = spec.kv_layout == "paged"
    fused_route = "fused_attn_paged" if paged else "fused_attn"
    if route not in (None, "fused_attn", "fused_attn_paged", "dense"):
        raise ValueError(f"unknown attn route {route!r}")
    if route is not None and route.startswith("fused") and route != fused_route:
        raise ValueError(f"route pin {route!r} does not match "
                         f"kv_layout={spec.kv_layout!r} (fused route here "
                         f"is {fused_route!r})")

    can_fuse = acu.mode == AcuMode.LUT and acu.use_pallas \
        and acu.lut is not None
    if not can_fuse and route != "dense":
        report.append(f"fused attention needs LUT mode + use_pallas + a "
                      f"built table (have mode={acu.mode.value}, "
                      f"use_pallas={acu.use_pallas}); attention stays exact")
        if paged:
            report.append("paged KV on the dense route: caller gathers pool "
                          "blocks to a contiguous layout (exact math is "
                          "layout-independent)")
    if route == fused_route and not can_fuse:
        raise ValueError(f"{fused_route} route unavailable: {report}")
    if not can_fuse and route != "dense":
        _no_oracle_on_tpu("attention", report)
    if route == "dense" or not can_fuse:
        if route == "dense":
            report.append("route pinned to exact dense attention by caller")
        return AttnPlan(mode=acu.mode, bits=acu.bits,
                        use_pallas=acu.use_pallas, route="dense", spec=spec,
                        report=tuple(report))

    from repro.kernels.flash_attention.approx import (
        approx_flash_attention, approx_flash_attention_paged)

    rep = spec.hq // spec.hkv

    def attn_call(qf, kf, vf, qs, ks, vs, rowinfo):
        # folded (B*H, S, D) operands; the host table goes in as is (see
        # fused_call in matmul_plan)
        return approx_flash_attention(
            qf, kf, vf, acu.lut, acu.offset, qs, ks, vs,
            bits=a_bits, causal=spec.causal, window=spec.window,
            softcap=spec.softcap, rowinfo=rowinfo, bq=spec.bq, bk=spec.bk,
            interpret=acu.interpret)

    def attn_call_paged(qf, k_pool, v_pool, qs, ks, vs, rowinfo, pt):
        return approx_flash_attention_paged(
            qf, k_pool, v_pool, acu.lut, acu.offset, qs, ks, vs,
            bits=a_bits, causal=spec.causal, window=spec.window,
            softcap=spec.softcap, rowinfo=rowinfo, page_table=pt, rep=rep,
            bq=spec.bq, interpret=acu.interpret)

    partition = None
    if ctx is not None:
        from repro.parallel import acu_shard
        partition = acu_shard.resolve_attn_partition(ctx, hq=spec.hq,
                                                     hkv=spec.hkv)

    def _default_rowinfo(q, k, rowinfo):
        if rowinfo is None:
            b, sq, sk = q.shape[0], q.shape[2], k.shape[2]
            rowinfo = jnp.broadcast_to(
                jnp.array([sk - sq, 0, sk], jnp.int32), (b, 3))
        return jnp.asarray(rowinfo, jnp.int32)

    if paged:
        if partition is not None:
            from repro.parallel import acu_shard
            sharded = acu_shard.wrap_attn_paged(
                attn_call_paged, ctx, partition, hq=spec.hq, hkv=spec.hkv)

            def fn(q, k_pool, v_pool, qs, ks, vs, rowinfo, page_table):
                return sharded(q, k_pool, v_pool, qs, ks, vs,
                               jnp.asarray(rowinfo, jnp.int32),
                               jnp.asarray(page_table, jnp.int32))
        else:
            def fn(q, k_pool, v_pool, qs, ks, vs, rowinfo, page_table):
                b, hq, sq, d = q.shape
                info = jnp.repeat(jnp.asarray(rowinfo, jnp.int32), hq,
                                  axis=0)
                pt = jnp.repeat(jnp.asarray(page_table, jnp.int32), hq,
                                axis=0)
                out = attn_call_paged(q.reshape(b * hq, sq, d), k_pool,
                                      v_pool, qs, ks, vs, info, pt)
                return out.reshape(b, hq, sq, d)
    elif partition is not None:
        from repro.parallel import acu_shard
        sharded = acu_shard.wrap_attn(attn_call, ctx, partition, hq=spec.hq,
                                      hkv=spec.hkv)

        def fn(q, k, v, qs, ks, vs, rowinfo=None):
            return sharded(q, k, v, qs, ks, vs,
                           _default_rowinfo(q, k, rowinfo))
    else:
        def fn(q, k, v, qs, ks, vs, rowinfo=None):
            b, hq, sq, d = q.shape
            hkv, sk = k.shape[1], k.shape[2]
            info = jnp.repeat(_default_rowinfo(q, k, rowinfo), hq, axis=0)
            out = attn_call(q.reshape(b * hq, sq, d),
                            k.reshape(b * hkv, sk, d),
                            v.reshape(b * hkv, sk, d), qs, ks, vs, info)
            return out.reshape(b, hq, sq, d)

    return AttnPlan(mode=acu.mode, bits=acu.bits, use_pallas=True,
                    route=fused_route, spec=spec, fn=fn,
                    partition=partition, report=tuple(report))


# ---------------------------------------------------------------------------
# grouped ragged GEMM plan (MoE expert dispatch)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class GroupedSpec:
    """Static geometry of one MoE grouped-GEMM site (hashable: plan cache
    key).

    ``n_experts``: expert count E; ``cap``: capacity rows per (dispatch
    block, expert) group; ``d_in``/``d_out``: the GEMM contraction / output
    widths; ``n_blocks``: the dispatch block count ``nb`` the MoE router
    resolved (``models/moe._dispatch_blocks`` — its silent power-of-2
    fallback is surfaced here so ``describe()`` reports the block layout the
    kernel actually runs). The grouped operand has ``G = n_blocks *
    n_experts`` groups; group ``g`` multiplies expert ``g % n_experts``.
    """

    n_experts: int
    cap: int
    d_in: int
    d_out: int
    n_blocks: int = 1


@dataclasses.dataclass(frozen=True)
class GroupedPlan:
    """A resolved grouped ragged GEMM route for one ACU at one MoE geometry.

    ``route`` is one of

    * ``"fused_grouped"`` — ONE ``pallas_call`` for all E expert GEMMs
      (``kernels/fused_lut_grouped``): the grid walks groups x row strips
      of the dense kernels' tile rule and a per-group ``groupinfo =
      [row_base, row_count]`` operand skips strips, and the 128-row blocks
      inside a strip, past each group's live token count; in-kernel
      per-tensor activation quantize, shifted-code LUT gathers, int32
      accumulate with integer-space K-pad correction, ONE combined-scale
      dequant.
      ``fn(xe, wq, xs, xz, ws, counts) -> (G, cap, d_out) f32`` with ``xe``
      (G, cap, d_in) float dispatched activations, ``wq`` (E, d_in, d_out)
      shifted int weight codes, ``xs``/``xz`` per-tensor activation qparams
      SHARED across groups (the caller pins ONE scale over the whole
      dispatched tensor so grouped == per-expert-vmap bitwise), ``ws``
      (E, d_out) per-expert weight scales, ``counts`` (G,) int32 live rows.
      Rows ``>= counts[g]`` are exactly 0.0 — dead capacity slots contribute
      nothing even under biased-M00 multipliers (masking, not slicing).
      Mesh-wrapped when a partition is active: experts over the
      ``acu_grouped_experts`` axes (expert parallelism), dispatch blocks
      over ``acu_grouped_rows``, opt-in ``acu_grouped_k`` contraction
      sharding with an int32 psum before the dequant.
    * ``"vmap"`` — the audited fallback (non-LUT mode, no Pallas routing, no
      table): ``fn`` is None and the caller keeps the per-expert vmapped
      ``approx_dense`` composition — which doubles as the bit-exactness
      oracle for the fused route when driven with the same pinned shared
      activation scale and live-row mask.

    The fused route's ``tiles`` fill as its kernel is traced, keyed
    ``"CxKxN"`` by one group's (one shard's) capacity and weight shape, as
    :attr:`MatmulPlan.tiles`.
    """

    mode: AcuMode
    bits: int
    use_pallas: bool
    route: str
    spec: GroupedSpec
    fn: Optional[Callable[..., Array]] = None
    partition: Optional[object] = None
    report: tuple[str, ...] = ()
    tiles: Optional[dict] = dataclasses.field(default=None, compare=False)

    def __call__(self, *args) -> Array:
        assert self.fn is not None, f"route {self.route} has no direct kernel"
        return self.fn(*args)

    def describe(self) -> dict:
        part = self.partition
        d = {
            "route": self.route,
            "mode": self.mode.value,
            "experts": self.spec.n_experts,
            "cap": self.spec.cap,
            "n_blocks": self.spec.n_blocks,
            "gemm": f"({self.spec.n_blocks}x{self.spec.n_experts}, "
                    f"{self.spec.cap}, {self.spec.d_in}) x "
                    f"({self.spec.n_experts}, {self.spec.d_in}, "
                    f"{self.spec.d_out})",
            "partition": None if part is None else
                f"blocks{part.rows}x experts{part.cols}x k{part.k} "
                f"({part.n_rows}x{part.n_cols}x{part.n_k} way)",
            "report": list(self.report) + (list(part.report) if part else []),
        }
        if self.tiles is not None:
            d["tiles"] = dict(self.tiles)
        return d


def grouped_plan(acu: Acu, spec: GroupedSpec, *, a_bits: Optional[int] = None,
                 mesh=None, route: Optional[str] = None) -> GroupedPlan:
    """Resolve one MoE grouped-GEMM site: expert geometry x (mode, bits,
    use_pallas) x mesh -> a concrete route. Mirrors :func:`attn_plan`'s
    silent-but-audited fallback contract: an ACU that cannot serve the fused
    grouped kernel resolves to ``"vmap"`` (the caller keeps its per-expert
    vmapped composition). ``route`` pins one explicitly (``"fused_grouped"``
    raises if unavailable; ``"vmap"`` forces the per-expert path — that is
    how the bit-exactness oracle and the bench baseline are driven).
    """
    a_bits = acu.bits if a_bits is None else a_bits
    ctx = _resolve_mesh(mesh)
    report: list[str] = []
    if route not in (None, "fused_grouped", "vmap"):
        raise ValueError(f"unknown grouped route {route!r}")

    can_fuse = acu.mode == AcuMode.LUT and acu.use_pallas \
        and acu.lut is not None
    if not can_fuse and route != "vmap":
        report.append(f"fused grouped GEMM needs LUT mode + use_pallas + a "
                      f"built table (have mode={acu.mode.value}, "
                      f"use_pallas={acu.use_pallas}); expert GEMMs stay on "
                      f"the per-expert vmapped route")
    if route == "fused_grouped" and not can_fuse:
        raise ValueError(f"fused_grouped route unavailable: {report}")
    if route == "vmap" or not can_fuse:
        if route == "vmap":
            report.append("route pinned to per-expert vmap by caller")
        return GroupedPlan(mode=acu.mode, bits=acu.bits,
                           use_pallas=acu.use_pallas, route="vmap", spec=spec,
                           report=tuple(report))

    from repro.kernels.fused_lut_grouped import ops as gops
    tiles, log = _tile_log(acu)

    def grouped_call(xe, wq, xs, xz, ws, counts, *, emit_acc=False):
        # host table as is: see fused_call in matmul_plan
        log(xe.shape[1], *wq.shape[1:])
        return gops.fused_lut_grouped(xe, wq, acu.lut, acu.offset, xs, xz,
                                      ws, counts,
                                      bits=a_bits, interpret=acu.interpret,
                                      emit_acc=emit_acc)

    partition = None
    fn = grouped_call
    if ctx is not None:
        from repro.parallel import acu_shard
        partition = acu_shard.resolve_grouped_partition(
            ctx, n_experts=spec.n_experts, n_blocks=spec.n_blocks)
        if partition is not None:
            fn = acu_shard.wrap_fused_grouped(
                grouped_call,
                lambda *args: grouped_call(*args, emit_acc=True),
                ctx, partition, acu.m00(), n_experts=spec.n_experts)

    return GroupedPlan(mode=acu.mode, bits=acu.bits, use_pallas=True,
                       route="fused_grouped", spec=spec, fn=fn,
                       partition=partition, report=tuple(report), tiles=tiles)


def make_acu(name: str, mode: AcuMode | str = AcuMode.LUT, rank: int = 8,
             use_pallas: bool = False, interpret: bool | None = None,
             fused: bool = False) -> Acu:
    """Build an ACU from a registered multiplier name.

    Large-bitwidth LUT requests fall back to FUNCTIONAL per the paper §3.4
    ("In case of large bitwidth ... substitute the LUT-based multiplication
    with functional-based multiplication").
    """
    mult = get_multiplier(name)
    mode = AcuMode(mode) if isinstance(mode, str) else mode
    lut = lowrank = None
    mask = None
    if mode == AcuMode.LUT:
        if mult.bits > 10:
            mode = AcuMode.FUNCTIONAL  # LUT would exceed VMEM; paper's fallback
        else:
            lut = build_lut(mult)
    if mode == AcuMode.LOWRANK:
        lowrank = factorize_error(mult, rank)
    if mode == AcuMode.FACTORED:
        mask = trunc_masks(mult)
        if mask is None:
            raise ValueError(f"{name} has no algebraic factorization; "
                             f"use LUT or LOWRANK")
    return Acu(multiplier=mult, mode=mode, lut=lut, lowrank=lowrank,
               mask=mask, use_pallas=use_pallas, interpret=interpret,
               fused=fused)
