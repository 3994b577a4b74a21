"""Pallas TPU kernel: low-rank error-corrected approximate GEMM (beyond-paper).

``out = A @ W  +  fA @ gW^T``  where  ``fA[m, (k,r)] = f[a[m,k]+off, r]`` and
``gW[(k,r), n] = g[w[k,n]+off, r]`` — DESIGN.md §3.

The exact term runs on the MXU (int8 x int8 -> int32). The correction term is
two tiny 1-D VMEM gathers (256 x r tables) plus one (bm, bk*r) x (bk*r, bn)
MXU matmul — the 2-D LUT gather of the faithful kernel is gone entirely,
moving emulation from VPU-gather-bound to MXU-bound.

VMEM @ defaults (bm=bn=128, bk=128, r=8): f/g tables 2*256*8*4 = 16 KiB,
fA tile 128*1024*4 = 512 KiB, gW tile 512 KiB, operand/acc tiles < 200 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(a_ref, w_ref, f_ref, g_ref, o_ref, *, offset: int, rank: int):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    a = a_ref[...]                                  # (bm, bk) int8/int32 codes
    w = w_ref[...]                                  # (bk, bn)
    bm, bk = a.shape
    bn = w.shape[1]

    # exact MXU term
    exact = jnp.dot(a.astype(jnp.int8), w.astype(jnp.int8),
                    preferred_element_type=jnp.int32).astype(jnp.float32)

    # low-rank error correction: 1-D gathers + MXU matmul
    f = f_ref[...]                                  # (n_codes, r) f32
    g = g_ref[...]                                  # (n_codes, r) f32
    fa = jnp.take(f, a.astype(jnp.int32).reshape(-1) + offset, axis=0)
    fa = fa.reshape(bm, bk * rank)                  # (bm, bk*r)
    gw = jnp.take(g, w.astype(jnp.int32).reshape(-1) + offset, axis=0)
    gw = gw.reshape(bk, bn, rank).transpose(0, 2, 1).reshape(bk * rank, bn)
    corr = jnp.dot(fa, gw, preferred_element_type=jnp.float32)

    o_ref[...] += exact + corr


@functools.partial(jax.jit, static_argnames=("offset", "rank", "bm", "bk",
                                             "bn", "interpret"))
def err_matmul_kernel(a: jnp.ndarray, w: jnp.ndarray, f: jnp.ndarray,
                      g: jnp.ndarray, *, offset: int, rank: int,
                      bm: int = 128, bk: int = 128, bn: int = 128,
                      interpret: bool | None = None) -> jnp.ndarray:
    M, K = a.shape
    _, N = w.shape
    n_codes = f.shape[0]
    bm, bk, bn = min(bm, M), min(bk, K), min(bn, N)
    assert M % bm == 0 and K % bk == 0 and N % bn == 0, (M, K, N, bm, bk, bn)
    grid = (M // bm, N // bn, K // bk)
    return pl.pallas_call(
        functools.partial(_kernel, offset=offset, rank=rank),
        name="err_matmul_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, k: (i, k)),
            pl.BlockSpec((bk, bn), lambda i, j, k: (k, j)),
            pl.BlockSpec((n_codes, rank), lambda i, j, k: (0, 0)),
            pl.BlockSpec((n_codes, rank), lambda i, j, k: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, k: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(a, w, f, g)
