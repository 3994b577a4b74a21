"""Pallas TPU kernel: RWKV-6 WKV recurrence (data-dependent decay).

    out_t = r_t · (S + u ⊙ (k_tᵀ v_t));   S ← diag(w_t) S + k_tᵀ v_t

Grid is (B*H,); each step holds the (hd, hd) state in VMEM scratch and walks
the time axis with `fori_loop` — the sequential-scan structure is inherent
(data-dependent decay defeats associative reformulation at full fidelity),
so the kernel's job is keeping the state resident and the per-step math on
the VPU/MXU instead of bouncing (B,H,hd,hd) through HBM every step, which is
what the pure-jnp `lax.scan` does.

VMEM @ defaults (hd=64, T-block=256): r/k/v/w tiles 4*256*64*4 = 256 KiB,
state 16 KiB, out tile 64 KiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, o_ref, sT_ref, *,
            seq: int):
    r = r_ref[...][0]        # (T, hd)
    k = k_ref[...][0]
    v = v_ref[...][0]
    w = w_ref[...][0]
    u = u_ref[...][0]        # (hd,)
    hd = r.shape[-1]

    def step(t, carry):
        s = carry            # (hd, hd)
        kt = jax.lax.dynamic_slice(k, (t, 0), (1, hd))[0]
        vt = jax.lax.dynamic_slice(v, (t, 0), (1, hd))[0]
        rt = jax.lax.dynamic_slice(r, (t, 0), (1, hd))[0]
        wt = jax.lax.dynamic_slice(w, (t, 0), (1, hd))[0]
        kv = kt[:, None] * vt[None, :]                   # (hd, hd)
        out = rt @ (s + u[:, None] * kv)                 # (hd,)
        o_ref[:, pl.ds(t, 1), :] = out[None, None, :]
        return wt[:, None] * s + kv

    s = jax.lax.fori_loop(0, seq, step, s0_ref[...][0])
    sT_ref[...] = s[None]


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv_kernel(r: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
               u: jnp.ndarray, s0: jnp.ndarray, *,
               interpret: bool | None = None):
    """r/k/v/w: (BH, T, hd) f32 with heads folded h-major (BH = B*H, row
    b*H + h); u: (H, hd) per-head bonus; s0: (BH, hd, hd).

    Returns (out (BH, T, hd), sT (BH, hd, hd)).
    """
    bh, t, hd = r.shape
    grid = (bh,)
    io_spec = pl.BlockSpec((1, t, hd), lambda b: (b, 0, 0))
    st_spec = pl.BlockSpec((1, hd, hd), lambda b: (b, 0, 0))
    n_heads = u.shape[0]  # u: (H, hd); grid cell b uses head b % H
    u_spec = pl.BlockSpec((1, hd), lambda b: (b % n_heads, 0))
    return pl.pallas_call(
        functools.partial(_kernel, seq=t),
        name="wkv_kernel",
        grid=grid,
        in_specs=[io_spec, io_spec, io_spec, io_spec, u_spec, st_spec],
        out_specs=[io_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, t, hd), jnp.float32),
                   jax.ShapeDtypeStruct((bh, hd, hd), jnp.float32)],
        interpret=resolve_interpret(interpret),
    )(r, k, v, w, u, s0)
