"""Pallas TPU kernel: ragged grouped fused LUT-GEMM for MoE expert dispatch.

ONE ``pallas_call`` runs all E expert GEMMs of an MoE layer. The input is the
dispatched capacity buffer flattened to ``(G * Cp, K)`` rows, where each of
the ``G`` groups (``G = nb * E`` dispatch blocks x experts) owns a contiguous
strip of ``Cp`` padded capacity rows and multiplies against the weights of
expert ``g % E``. The grid walks ``(group, row-block, n-block, k-block)`` and
a per-group ``groupinfo = [row_base, row_count]`` operand — the same pattern
as flash-attention's per-row ``rowinfo`` extents — tells the kernel how many
of each group's capacity rows actually hold routed tokens, so row-blocks past
the live count skip the quantize + LUT-gather work entirely instead of
grinding through dead padded slots. That skip is the whole point: a capacity
buffer at ``moe_capacity`` 1.25+ with realistic (skewed) routing is mostly
dead rows.

Inside a live block the body is the established fused recipe, verbatim from
``fused_lut_dense``: per-tensor in-kernel activation quantization of each
128-wide contraction piece, the shared LUT-GEMM core
(:mod:`repro.kernels.lut_gather`), int32 accumulate into a persistent VMEM
scratch tile, integer-space K-pad correction, and ONE combined-scale
dequant (``acc * (xs * ws)``) on the final K step. int32 adds are
associative, so each live row is bit-identical to the per-expert
``fused_lut_dense`` call. Scalars and ``groupinfo`` live in SMEM.

Dead rows (``row >= row_count``) write exactly 0.0. This is a deliberate
contract, not just hygiene: a zero *input* row still produces
``sum_k LUT[off, wq + off] != 0`` under biased-M00 multipliers (masking is
not slicing — same lesson as the attention kernel's masked-key semantics),
and the combine step downstream must be able to rely on dead slots
contributing nothing.

``emit_acc=True`` (the mesh contraction-sharded route) returns the raw int32
accumulator with dead rows zeroed in integer space; the sharded wrapper psums
partials across K shards, applies the mesh-level pad correction, dequantizes
once, and re-masks (the uniform correction un-zeroes dead rows).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.lut_gather import LANES, SMEM, lut_gemm_streamed
from repro.kernels.runtime import resolve_interpret


def _kernel(x_ref, w_ref, lut_ref, xs_ref, xz_ref, m00_ref, ws_ref, info_ref,
            o_ref, acc_ref, *, offset: int, lo: int, hi: int, k_pad: int,
            n_planes: int, emit_acc: bool):
    g = pl.program_id(0)
    m_step = pl.program_id(1)
    k_step = pl.program_id(3)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    bm, bn = acc_ref.shape
    count = info_ref[g, 1]                 # live rows in this group
    live = count - m_step * bm             # live rows at/after this row-block

    @pl.when(live > 0)
    def _accumulate():
        # fused_lut_dense recipe verbatim — only executed for row-blocks that
        # intersect the group's live rows; dead blocks skip straight past the
        # quantize + gather work (the ragged-dispatch win)
        xs = xs_ref[0]                             # per-tensor activation scale
        xz = xz_ref[0]                             # activation zero-point (code)

        def load_a(k0):                            # shifted codes, index space
            x = x_ref[:, pl.ds(k0, LANES)].astype(jnp.float32)
            q = jnp.clip(jnp.round(x / xs + xz), lo, hi).astype(jnp.int32)
            return q - xz.astype(jnp.int32) + offset

        def load_b(k0):                            # expert g % E
            return w_ref[0, pl.ds(k0, LANES), :].astype(jnp.int32) + offset

        acc_ref[...] += lut_gemm_streamed(load_a, load_b,
                                          x_ref.shape[1] // LANES, lut_ref,
                                          m=bm, n=bn, n_planes=n_planes)

    @pl.when(k_step == pl.num_programs(3) - 1)
    def _dequant():
        acc = acc_ref[...]
        if k_pad:  # padded k entries each contributed LUT[off, off] = M[0, 0]
            # applied unconditionally: dead row-blocks never accumulated, so
            # their value here is garbage either way — the row mask below is
            # what guarantees they emit exactly zero
            acc = acc - k_pad * m00_ref[0]
        row = m_step * bm + jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        if emit_acc:
            # contraction sharding: masked int32 partials leave the kernel;
            # the wrapper psums across K shards and dequantizes after
            o_ref[...] = jnp.where(row < count, acc, 0)
        else:
            # one combined-scale multiply, same association as
            # fused_lut_dense so live rows stay bitwise identical to the
            # per-expert route; dead rows write exactly 0.0
            xs = xs_ref[0]
            o_ref[...] = jnp.where(
                row < count, acc.astype(jnp.float32) * (xs * ws_ref[0]), 0.0)


@functools.partial(jax.jit, static_argnames=("offset", "lo", "hi", "k_pad",
                                             "n_planes", "cp", "bm", "bk",
                                             "interpret", "emit_acc"))
def fused_lut_grouped_kernel(x: jnp.ndarray, wq: jnp.ndarray, lut: jnp.ndarray,
                             x_scale: jnp.ndarray, x_zp: jnp.ndarray,
                             m00: jnp.ndarray, w_scale: jnp.ndarray,
                             info: jnp.ndarray, *, offset: int, lo: int,
                             hi: int, cp: int, k_pad: int = 0,
                             n_planes: int = 4, bm: int = 128, bk: int = 128,
                             interpret: bool | None = None,
                             emit_acc: bool = False) -> jnp.ndarray:
    """x: (G * cp, K) float rows, group g owning rows [g*cp, (g+1)*cp);
    wq: (E, K, N) shifted int weight codes (group g uses expert g % E);
    lut: the (R, L) padded table; x_scale/x_zp: shape-(1,) f32; m00:
    shape-(1,) int32 ``LUT[off, off]``; w_scale: (E, 1, N) f32; info: (G, 2)
    int32 ``[row_base, row_count]``. ``K`` and ``N`` are multiples of 128.
    Returns (G * cp, N) float32 with rows >= row_count exactly 0.0 — or the
    raw int32 accumulator (dead rows zeroed) with ``emit_acc=True``."""
    Gm, K = x.shape
    E, _, N = wq.shape
    G = Gm // cp
    bn = LANES
    assert Gm == G * cp and G % E == 0, (Gm, cp, E)
    assert cp % bm == 0 and K % bk == 0 and N % bn == 0 and bk % LANES == 0, (
        f"shape {(cp, K, N)} not divisible by tile {(bm, bk, bn)}")
    mblocks = cp // bm
    return pl.pallas_call(
        functools.partial(_kernel, offset=offset, lo=lo, hi=hi, k_pad=k_pad,
                          n_planes=n_planes, emit_acc=emit_acc),
        name="fused_lut_grouped_kernel",
        grid=(G, mblocks, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda g, m, n, k: (g * mblocks + m, k)),
            pl.BlockSpec((1, bk, bn), lambda g, m, n, k: (g % E, k, n)),
            pl.BlockSpec(lut.shape, lambda g, m, n, k: (0, 0)),
            SMEM, SMEM, SMEM,
            pl.BlockSpec((1, 1, bn), lambda g, m, n, k: (g % E, 0, n)),
            SMEM,
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda g, m, n, k: (g * mblocks + m, n)),
        out_shape=jax.ShapeDtypeStruct((Gm, N),
                                       jnp.int32 if emit_acc else jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        interpret=resolve_interpret(interpret),
    )(x, wq, lut, x_scale, x_zp, m00, w_scale, info)
