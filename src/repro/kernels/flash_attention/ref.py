"""Pure-jnp oracles: naive exact attention, and the unfused approximate
composition the approx kernel must match bitwise.

The approximate oracle is deliberately NOT an independent re-derivation of
the float arithmetic: XLA CPU contracts ``a*b + c`` into an FMA under jit,
straight through ``optimization_barrier`` (see the approx module docstring),
so two independently-written online-softmax loops land 1 ulp apart. Instead
the oracle drives the same :func:`~.approx._online_block` the kernel runs,
inside the same ``fori_loop`` shape, under jit — identical loop-body jaxprs
compile to identical machine code, which is the bitwise contract. What the
oracle independently exercises is the *orchestration*: python loops over
(row, q-block) instead of a Pallas grid, whole-array indexing instead of
BlockSpec pipelines, and the GQA ``b // rep`` mapping as plain indexing —
and the *integer GEMMs*: the block takes its LUT GEMM as an argument, and
the oracle passes a plain table gather (:func:`_take_gemm`) where the
kernel passes the lane-gather/one-hot core. Integer results are exact, so
the shared float body still pins the bits.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def attention_ref(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, *,
                  causal: bool = True, window: int | None = None,
                  softcap: float | None = None) -> jnp.ndarray:
    """q: (BH, Sq, D), k/v: (BH, Sk, D). Queries are aligned to the END of the
    key sequence when Sq != Sk (decode convention)."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) / (d ** 0.5)
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    q_pos = jnp.arange(sq)[:, None] + (sk - sq)
    k_pos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask[None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)


def _take_gemm(tab):
    """``gemm(a, b)[m, n] = sum_k tab[a[m, k], b[k, n]]`` in int32 by one
    ``jnp.take`` of the flattened (R, L) table — the oracle's LUT GEMM."""
    flat = tab.reshape(-1)
    width = tab.shape[1]

    def gemm(a, b):
        idx = a[:, :, None] * width + b[None, :, :]
        return jnp.take(flat, idx).sum(axis=1, dtype=jnp.int32)
    return gemm


def _attend_rows(qp, lut, info, sqs, sks, svs, score_scale, pv_scale, m00,
                 kv_of_row, *, n_kv: int, causal: bool, window: int | None,
                 softcap: float | None, seq_k_real: int, d_real: int,
                 n_planes: int, offset: int, lo: int, hi: int, bq: int,
                 bk: int):
    """Python (row, q-block) orchestration of ``_online_block``;
    ``kv_of_row(b)`` -> the row's ``load_kv(ki)``. ``n_planes`` is the
    kernel's digit-plane count; the gather needs none."""
    from .approx import NEG_INF, _online_block, _quantize_sym, \
        causal_block_bound

    bh, sq_p, dp = qp.shape
    out_rows = []
    for b in range(bh):
        q_base, kv_start, kv_len = info[b, 0], info[b, 1], info[b, 2]
        q_blocks = []
        for qi in range(sq_p // bq):
            qf = qp[b, qi * bq:(qi + 1) * bq].astype(jnp.float32)
            qq = _quantize_sym(qf, sqs[0], lo, hi, offset)
            q_pos = (q_base + qi * bq
                     + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
            if causal:
                n_kv_eff = causal_block_bound(q_base, qi, bq, bk, n_kv)
            else:
                n_kv_eff = n_kv
            body = functools.partial(
                _online_block, qq=qq, q_pos=q_pos, load_kv=kv_of_row(b),
                gemm=_take_gemm(lut), m00=m00[0], sks=sks[0], svs=svs[0],
                score_scale=score_scale[0], pv_scale=pv_scale[0],
                kv_start=kv_start, kv_len=kv_len, bq=bq, bk=bk,
                seq_k_real=seq_k_real, d_real=d_real, offset=offset, lo=lo,
                hi=hi, causal=causal, window=window, softcap=softcap)
            m0 = jnp.full((bq,), NEG_INF, jnp.float32)
            l0 = jnp.zeros((bq,), jnp.float32)
            acc0 = jnp.zeros((bq, dp), jnp.float32)
            m, l, acc = jax.lax.fori_loop(0, n_kv_eff, body, (m0, l0, acc0))
            q_blocks.append(acc * pv_scale[0] / jnp.maximum(l, 1e-30)[:, None])
        out_rows.append(jnp.concatenate(q_blocks, axis=0))
    return jnp.stack(out_rows)


def _block_loader(k_all, v_all, bk: int, starts):
    """``load_kv`` over whole (S, dp) arrays; ``starts(ki)`` -> first row."""
    def load_kv(ki):
        start = starts(ki)
        dp = k_all.shape[-1]
        return (jax.lax.dynamic_slice(k_all, (start, 0), (bk, dp)
                                      ).astype(jnp.float32),
                jax.lax.dynamic_slice(v_all, (start, 0), (bk, dp)
                                      ).astype(jnp.float32))
    return load_kv


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "seq_k_real", "d_real", "n_planes",
    "offset", "lo", "hi", "bq", "bk", "rep"))
def _approx_ref_core(qp, kp, vp, lut, info, sqs, sks, svs, score_scale,
                     pv_scale, m00, *, rep: int, **statics):
    bk = statics["bk"]
    return _attend_rows(
        qp, lut, info, sqs, sks, svs, score_scale, pv_scale, m00,
        lambda b: _block_loader(kp[b // rep], vp[b // rep], bk,
                                lambda ki: ki * bk),
        n_kv=kp.shape[1] // bk, **statics)


def approx_attention_ref(q, k, v, lut, offset, q_scale, k_scale, v_scale, *,
                         bits: int = 8, causal: bool = True,
                         window: int | None = None,
                         softcap: float | None = None, rowinfo=None,
                         bq: int = 128, bk: int = 128):
    """Unfused oracle for ``approx_flash_attention`` — same operand
    preparation (``prepare_approx_attention``), same per-KV-block update
    (``_online_block``), different orchestration. Bitwise-identical output
    by construction; see the module docstring for why sharing the block
    update is load-bearing."""
    from .approx import prepare_approx_attention

    sq, d = q.shape[1], q.shape[2]
    operands, statics = prepare_approx_attention(
        q, k, v, lut, offset, q_scale, k_scale, v_scale, bits=bits,
        rowinfo=rowinfo, bq=bq, bk=bk)
    out = _approx_ref_core(*operands, causal=causal, window=window,
                           softcap=softcap, **statics)
    return out[:, :sq, :d]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "softcap", "d_real", "n_planes", "offset", "lo", "hi",
    "bq", "bk", "rep"))
def _approx_paged_ref_core(qp, kp, vp, lut, info, page_table, sqs, sks, svs,
                           score_scale, pv_scale, m00, *, rep: int,
                           **statics):
    bk = statics["bk"]
    hkv = kp.shape[0]
    n_logical = page_table.shape[1]

    def kv_of_row(b):
        pt = page_table[b]
        return _block_loader(
            kp[(b // rep) % hkv], vp[(b // rep) % hkv], bk,
            lambda ki: jax.lax.dynamic_index_in_dim(
                pt, ki, keepdims=False).astype(jnp.int32) * bk)

    return _attend_rows(qp, lut, info, sqs, sks, svs, score_scale, pv_scale,
                        m00, kv_of_row, n_kv=n_logical,
                        seq_k_real=n_logical * bk, **statics)


def approx_attention_paged_ref(q, k_pool, v_pool, lut, offset, q_scale,
                               k_scale, v_scale, *, rowinfo, page_table,
                               rep: int, bits: int = 8, causal: bool = True,
                               window: int | None = None,
                               softcap: float | None = None, bq: int = 128):
    """Unfused oracle for ``approx_flash_attention_paged`` — same operand
    preparation (``prepare_approx_attention_paged``), same per-KV-block
    update with the same ``kv_blocks`` page-table indirection, python
    orchestration. Bitwise-identical output by construction."""
    from .approx import prepare_approx_attention_paged

    sq, d = q.shape[1], q.shape[2]
    operands, statics = prepare_approx_attention_paged(
        q, k_pool, v_pool, lut, offset, q_scale, k_scale, v_scale,
        bits=bits, rowinfo=rowinfo, page_table=page_table, bq=bq)
    out = _approx_paged_ref_core(*operands, causal=causal, window=window,
                                 softcap=softcap, rep=rep, **statics)
    return out[:, :sq, :d]
