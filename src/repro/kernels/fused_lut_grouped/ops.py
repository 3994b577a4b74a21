"""jit'd public wrapper for the ragged grouped fused LUT-GEMM.

Pads capacity / K / N to tile multiples with the same exact-padding
discipline as ``fused_lut_dense`` (zero activation rows quantize to the
zero-point -> shifted code 0 -> ``LUT[off, off]`` per padded k, corrected in
integer space), builds the per-group ``groupinfo = [row_base, row_count]``
operand, and slices the padded output back to ``(G, C, N)``.

The row-block tile shrinks to the smallest multiple of 8 covering the
capacity when ``C < 128`` — MoE capacity buffers are often much shorter than
a dense GEMM's M, and a 128-row tile over a 24-row capacity would throw away
the ragged skip granularity entirely.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.lut_gather import LANES, table_operands

from .kernel import fused_lut_grouped_kernel


def fused_lut_grouped(x: jnp.ndarray, wq: jnp.ndarray, lut: jnp.ndarray,
                      offset: int, x_scale, x_zp, w_scale,
                      counts: jnp.ndarray, *, bits: int = 8, bm: int = 128,
                      bk: int = 256, interpret: bool | None = None,
                      emit_acc: bool = False) -> jnp.ndarray:
    """Ragged grouped approximate GEMM over MoE capacity buffers.

    ``x``: (G, C, K) float dispatched activations — G groups of C capacity
    rows; group ``g`` multiplies against expert ``g % E``. ``wq``: (E, K, N)
    shifted int weight codes; ``lut`` may be (n_codes, n_codes) or flattened;
    ``x_scale``/``x_zp``: per-tensor activation qparams SHARED by all groups
    (the caller pins one scale over the whole dispatched tensor so grouped ==
    per-expert-vmap bitwise); ``w_scale``: (E,) or (E, N) per-expert weight
    scales; ``counts``: (G,) int — live rows per group; row-blocks past a
    group's count are skipped in-kernel.

    Returns (G, C, N) float32 with rows ``>= counts[g]`` exactly 0.0, each
    live row bit-exact vs the per-expert ``fused_lut_dense`` call. With
    ``emit_acc=True`` returns the raw (G, C, N) int32 accumulator (dead rows
    zeroed; tile padding corrected in integer space) for the mesh
    contraction-sharded route.
    """
    tab, n_planes, m00 = table_operands(lut, offset)
    G, C, K = x.shape
    E, _, N = wq.shape
    assert G % E == 0, f"groups {G} not a multiple of experts {E}"
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    xs = jnp.asarray(x_scale, jnp.float32).reshape(1)
    xz = jnp.asarray(x_zp, jnp.float32).reshape(1)
    ws = jnp.broadcast_to(
        jnp.asarray(w_scale, jnp.float32).reshape(E, 1, -1), (E, 1, N))
    bm = min(bm, 128)
    if C < bm:  # keep skip granularity on short capacity buffers
        bm = max(8, -(-C // 8) * 8)
    pc = (-C) % bm
    pk = (-K) % LANES
    pn = (-N) % LANES
    if pc or pk:
        x = jnp.pad(x, ((0, 0), (0, pc), (0, pk)))
    if pk or pn:
        wq = jnp.pad(wq, ((0, 0), (0, pk), (0, pn)))
        ws = jnp.pad(ws, ((0, 0), (0, 0), (0, pn)))
    cp = C + pc
    kp = K + pk
    # single K grid step when the whole row strip fits VMEM comfortably;
    # otherwise a k-tile that divides the (128-multiple) padded K
    bk = kp if kp <= 512 else (bk if kp % bk == 0 else LANES)
    info = jnp.stack(
        [jnp.arange(G, dtype=jnp.int32) * cp,
         jnp.clip(counts.astype(jnp.int32), 0, C)], axis=1)
    out = fused_lut_grouped_kernel(
        x.reshape(G * cp, kp), wq, tab, xs, xz, m00, ws, info,
        offset=offset, lo=lo, hi=hi, k_pad=pk, cp=cp, n_planes=n_planes,
        bm=bm, bk=bk, interpret=interpret, emit_acc=emit_acc)
    return out.reshape(G, cp, N + pn)[:, :C, :N]
