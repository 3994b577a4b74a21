"""Pallas TPU kernel: flash attention (online-softmax, causal / sliding-window).

Substrate hot-spot for the LM architectures: O(S) memory attention. Grid is
(batch*heads, q_blocks); each step scans KV blocks with running (m, l, acc)
online-softmax state. Causal masking skips fully-masked KV blocks via the
block index bound; sliding-window masking (gemma2 local layers) and logit
soft-capping are fused in.

VMEM @ defaults (bq=bk=256, d=128): q/k/v tiles 3*256*128*4 = 384 KiB +
scores 256*256*4 = 256 KiB + state — comfortably inside 16 MiB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.runtime import resolve_interpret

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq_k: int,
            causal: bool, window: int | None, softcap: float | None,
            scale: float):
    qi = pl.program_id(1)
    q = q_ref[...][0].astype(jnp.float32) * scale        # (bq, d)
    d = q.shape[-1]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)

    n_kv = seq_k // bk
    if causal:
        # last kv block that any query in this q block can see
        n_kv_eff = jnp.minimum(n_kv, (qi + 1) * bq // bk + 1)
    else:
        n_kv_eff = n_kv

    k_all = k_ref[...][0]                                # (seq_k, d), VMEM-resident
    v_all = v_ref[...][0]

    def body(ki, carry):
        m, l, acc = carry
        k = jax.lax.dynamic_slice(k_all, (ki * bk, 0), (bk, d)
                                  ).astype(jnp.float32)  # (bk, d)
        v = jax.lax.dynamic_slice(v_all, (ki * bk, 0), (bk, d)
                                  ).astype(jnp.float32)
        s = q @ k.T                                      # (bq, bk)
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + p.sum(axis=-1)
        acc_new = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc_new

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv_eff, body, (m0, l0, acc0))
    out = acc / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out[None].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "softcap",
                                             "bq", "bk", "rep", "interpret"))
def flash_attention_kernel(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                           causal: bool = True, window: int | None = None,
                           softcap: float | None = None, bq: int = 256,
                           bk: int = 256, rep: int = 1,
                           interpret: bool | None = None) -> jnp.ndarray:
    """q: (B*Hq, Sq, D), k/v: (B*Hkv, Sk, D) — heads pre-folded into batch.

    GQA never materializes repeated KV: ``rep = Hq // Hkv`` query-head rows
    share one KV row through the BlockSpec index map (``b // rep``), so K/V
    stay at their (B*Hkv, Sk, D) HBM footprint.
    """
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    assert bh == bh_kv * rep, (bh, bh_kv, rep)
    bq = min(bq, sq)
    bk = min(bk, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, sk, bq, bk)
    scale = 1.0 / (d ** 0.5)
    grid = (bh, sq // bq)
    return pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, seq_k=sk, causal=causal,
                          window=window, softcap=softcap, scale=scale),
        name="flash_attention_kernel",
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec((1, sk, d), lambda b, i: (b // rep, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        interpret=resolve_interpret(interpret),
    )(q, k, v)
