"""Pallas TPU kernel: LUT-gather GEMM (paper §4, TPU adaptation).

``out[m, n] = sum_k LUT[a[m, k] + off, w[k, n] + off]``

The padded product table is pinned in VMEM for the whole grid (BlockSpec
maps every grid step to the same full-table block — the Mosaic pipeline keeps
it resident, the TPU analogue of AdaPT "populating the CPU cache with the
LUTs"). Each (bm, bk) x (bk, bn) tile runs the shared LUT-GEMM core
(:mod:`repro.kernels.lut_gather`: VPU lane gathers of the table — the AVX2
``vgather`` role — feeding an exact one-hot int8 matmul) over 128-wide
contraction pieces and accumulates into the (bm, bn) output tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.lut_gather import LANES, lut_gemm_streamed
from repro.kernels.runtime import resolve_interpret


def _kernel(a_ref, w_ref, lut_ref, o_ref, *, offset: int, n_planes: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bm, bk = a_ref.shape
    bn = w_ref.shape[1]
    o_ref[...] += lut_gemm_streamed(
        lambda k0: a_ref[:, pl.ds(k0, LANES)].astype(jnp.int32) + offset,
        lambda k0: w_ref[pl.ds(k0, LANES), :].astype(jnp.int32) + offset,
        bk // LANES, lut_ref, m=bm, n=bn, n_planes=n_planes)


@functools.partial(jax.jit, static_argnames=("offset", "n_planes", "bm", "bk",
                                             "bn", "interpret"))
def lut_matmul_kernel(a: jnp.ndarray, w: jnp.ndarray, lut: jnp.ndarray, *,
                      offset: int, n_planes: int = 4, bm: int = 128,
                      bk: int = 128, bn: int = 128,
                      interpret: bool | None = None) -> jnp.ndarray:
    """a: (M, K) int, w: (K, N) int (signed codes); lut: the (R, L) padded
    table. Every dim must be a multiple of its tile, ``bk`` of 128."""
    M, K = a.shape
    _, N = w.shape
    assert M % bm == 0 and K % bk == 0 and N % bn == 0 and bk % LANES == 0, (
        f"shape {(M, K, N)} not divisible by tile {(bm, bk, bn)}")
    return pl.pallas_call(
        functools.partial(_kernel, offset=offset, n_planes=n_planes),
        name="lut_matmul_kernel",
        grid=(M // bm, N // bn, K // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec(lut.shape, lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.int32),
        interpret=resolve_interpret(interpret),
    )(a, w, lut)
