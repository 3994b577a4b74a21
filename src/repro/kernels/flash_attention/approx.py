"""Approximate flash attention: LUT-gather GEMMs inside the online softmax.

Extends the fused quantize->LUT-GEMM->dequant scheme (kernels/fused_lut_dense)
into the streaming-softmax loop. Per (batch*head, q_block) grid step:

* Q is quantized in-kernel (per-tensor symmetric, shifted ACU codes) once;
* each KV block quantizes K/V in-kernel, computes QK^T as an int32 LUT-gather
  GEMM over the head dim (d-pad corrected with ``(dp - d) * LUT[off, off]``
  in integer space), dequantizes with ONE pre-pinned combined scale
  ``pin(pin(sq*sk) / sqrt(d))`` folded together with the 1/sqrt(d) softmax
  scale, then applies softcap/masking and the running (m, l, acc) rescale;
* the probabilities are quantized to static-scale codes ``round(p * hi)``
  (p is in [0, 1] post-softmax, so the scale needs no amax) and PV is a
  second int32 LUT-gather GEMM over the key block, Sk-pad corrected in int
  space, added unscaled into the float accumulator rescale; the pre-pinned
  ``pin(sv / hi)`` dequant scale is applied once, with the final 1/l.

Emulation semantics (what "approximate attention on the ACU" means here):

* *structural* padding this wrapper introduces (head-dim pad, Sk pad to
  the key-block multiple) is corrected in integer space, so
  the result is independent of the tile geometry — exactly like the dense
  and conv kernels;
* *masked keys inside the row's written extent* ``[kv_start, kv_len)``
  (causally-future or out-of-window keys) get probability 0.0, which
  quantizes to code 0 — and the ACU still multiplies code 0 by the key's V
  codes, contributing ``LUT[0, v]`` per masked key. That is the faithful
  hardware emulation (a real ACU array multiplies everything in the tile);
  for every registered multiplier ``M[0, x] == 0`` so the contribution
  vanishes, and for biased synthetic multipliers the oracle reproduces it
  bit-for-bit;
* *positions outside the written extent* (left-pad slots below
  ``kv_start``, cache positions at/above ``kv_len``) hold no key: their V
  enters the PV GEMM as the zero code, contributing ``M[0, 0]`` each, so
  whatever a reused cache block still holds there is never observable;
* the causal block-skip bound (blocks no query in the tile can see are never
  executed) is part of the defined semantics, and the oracle replicates it.

The running max/exp/rescale stays in float32, and float32 online-softmax
arithmetic is where the bitwise contract gets subtle. On the CPU, XLA
contracts ``a*b + c`` into an FMA under jit — straight through
``optimization_barrier`` — so instead of pinning each multiply the
*structure* is pinned: the entire per-KV-block update lives in
:func:`_online_block`, shared verbatim by the Pallas kernel and the jnp
oracle, and a loop body is its own computation that surrounding context
cannot re-fuse. On the TPU the kernel is compiled by Mosaic and the oracle
by XLA; their elementwise float ops round identically, but a reduction's
association order is each compiler's own, so the one float reduction (the
softmax row sum) is an explicit pairwise tree of adds (:func:`_row_sum`).
Both LUT GEMMs are exact integer arithmetic, passed to the block as
``gemm``: the kernel runs the shared core (:mod:`repro.kernels.lut_gather`),
the oracle a plain table gather, and the integer results are equal. The two
GEMMs compile differently, so no float multiply of a GEMM result may feed
an add directly (XLA CPU might contract the pair into an FMA in one program
and not the other): that is why the PV accumulator sums raw int32 products and the
PV scale waits for the end. Scales are pinned with ``pin_rounding``
OUTSIDE the kernel and passed in as SMEM scalars, so single-device and
sharded runs also see identical bits.

GQA shares KV through the BlockSpec index map (``b // rep``) — repeated K/V
never exist in HBM.

Paged KV (:func:`approx_flash_attention_paged`): the serving engines store
KV in fixed-size *physical blocks* drawn from a global pool instead of one
contiguous row per sequence, and the kernel reads them through a per-row
page table. The per-row ``rowinfo=[q_base, kv_start, kv_len]`` extents
already decouple logical from physical layout, so this is not a kernel
rewrite: the KV block size *is* the kernel's ``bk`` tile, the pool arrives
as one ``(Hkv, P*bk, D)`` operand (each grid row selects its KV head via
the same ``(b // rep)`` BlockSpec index map, now mod ``Hkv``), and the only
change inside the loop is where logical block ``ki`` starts —
``page_table[ki] * bk`` instead of ``ki * bk``, read by the ``load_kv``
callback ``_online_block`` takes. The paged oracle
(:func:`~.ref.approx_attention_paged_ref`) drives the same body with the
same page table, so paged == contiguous == oracle bitwise whenever the
gathered blocks hold the same values as the contiguous layout inside each
row's written extent; a recycled block's stale tail past ``kv_len`` is
never observable.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.quantization import pin_rounding
from repro.kernels.lut_gather import (SMEM, lut_gemm, round_up,
                                     table_operands)
from repro.kernels.runtime import resolve_interpret

from .kernel import NEG_INF


def _quantize_sym(x, scale, lo, hi, offset):
    """Per-tensor symmetric quantize to shifted ACU codes (zero-point 0)."""
    return jnp.clip(jnp.round(x / scale), lo, hi).astype(jnp.int32) + offset


def _row_sum(p):
    """Sum over the last (power-of-two) axis as an explicit pairwise tree of
    elementwise adds. A reduction's association order is the compiler's
    choice — Mosaic and XLA:TPU sum lanes in different orders — while
    elementwise adds round the same everywhere, so the kernel and its XLA
    oracle agree bit for bit on the chip too."""
    w = p.shape[-1]
    assert w & (w - 1) == 0, f"row width {w} is not a power of two"
    while w > 1:
        w //= 2
        p = p[:, :w] + p[:, w:2 * w]
    return p[:, 0]


def attn_scales(q_scale, k_scale, v_scale, d_real: int, hi: int):
    """The two combined dequant scales, pinned outside the kernel.

    ``score = pin(pin(sq*sk) * (1/sqrt(d)))`` dequantizes the QK^T int32
    accumulator straight into softmax logits; ``pv = pin(sv * (1/hi))``
    dequantizes the PV accumulator (p codes carry the static 1/hi scale).
    """
    inv_sqrt_d = jnp.float32(1.0 / math.sqrt(d_real))
    score = pin_rounding(pin_rounding(q_scale * k_scale) * inv_sqrt_d)
    pv = pin_rounding(v_scale * jnp.float32(1.0 / hi))
    return score, pv


def _online_block(ki, carry, *, qq, q_pos, load_kv, gemm, m00, sks, svs,
                  score_scale, pv_scale, kv_start, kv_len, bq: int, bk: int,
                  seq_k_real: int, d_real: int, offset: int, lo: int,
                  hi: int, causal: bool, window: int | None,
                  softcap: float | None):
    """One KV block of the approximate online softmax — the shared core.

    Kernel and oracle both drive this exact function inside the same
    ``fori_loop`` shape; its body compiles once per program as its own XLA
    computation, which is what makes the two bitwise-identical (module
    docstring: FMA contraction cannot be fenced op-by-op on XLA CPU).

    ``load_kv(ki)`` returns the float (bk, dp) K and V blocks of *logical*
    block ``ki``: the kernel reads them from its VMEM refs (through the page
    table for the paged layout), the oracle slices whole arrays. Masking,
    positions and pad corrections always speak logical coordinates, so the
    two layouts agree bit for bit when the gathered blocks hold the same
    values. ``gemm(a, b)`` is the int32 LUT GEMM ``sum_k LUT[a[m, k], b[k,
    n]]`` over index-space codes: the kernel passes the shared core
    (:func:`~repro.kernels.lut_gather.lut_gemm`), the oracle a plain
    gather.
    """
    m, l, acc = carry
    kf, vf = load_kv(ki)
    dp = kf.shape[-1]
    kq = _quantize_sym(kf, sks, lo, hi, offset)
    # outside [kv_start, kv_len) there is no key: V enters as the zero code
    kv_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bk, dp), 0)
    vq = jnp.where((kv_pos >= kv_start) & (kv_pos < kv_len),
                   _quantize_sym(vf, svs, lo, hi, offset), offset)

    s_int = gemm(qq, kq.T)                                     # (bq, bk)
    s_int = s_int - (dp - d_real) * m00
    s = s_int.astype(jnp.float32) * score_scale
    if softcap is not None:
        s = softcap * jnp.tanh(s / softcap)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (k_pos >= kv_start) & (k_pos < kv_len)
    if causal:
        mask &= k_pos <= q_pos
    if window is not None:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[:, None])
    alpha = jnp.exp(m - m_new)
    # the normalizer accumulates the FLOAT probabilities; only the PV
    # contraction runs on the ACU
    l_new = alpha * l + _row_sum(p)
    pq = jnp.clip(jnp.round(p * hi), 0, hi).astype(jnp.int32) + offset
    pv_int = gemm(pq, vq)                                      # (bq, dp)
    pv_int = pv_int - jnp.clip((ki + 1) * bk - seq_k_real, 0, bk) * m00
    acc_new = acc * alpha[:, None] + pv_int.astype(jnp.float32)
    return m_new, l_new, acc_new


def causal_block_bound(q_base, qi: int, bq: int, bk: int, n_kv: int):
    """Index one past the last kv block any query row of tile ``qi`` can see
    (``q_base`` shifts the tile to its absolute cache position). Part of the
    defined semantics: blocks beyond the bound are never executed, which is
    observable under biased multipliers (``M[0, x] != 0``), so the oracle
    uses the same bound."""
    return jnp.minimum(n_kv, (q_base + (qi + 1) * bq - 1) // bk + 1)


def _attend(q_ref, lut_ref, info_ref, sq_ref, sk_ref, sv_ref, ss_ref,
            pvs_ref, m00_ref, o_ref, load_kv, *, n_kv: int, bq: int, bk: int,
            seq_k_real: int, d_real: int, n_planes: int, offset: int, lo: int,
            hi: int, causal: bool, window: int | None,
            softcap: float | None):
    """The kernel body both layouts share: quantize the q tile, walk the
    visible KV blocks through ``_online_block``, normalize, store."""
    b = pl.program_id(0)
    qi = pl.program_id(1)
    dp = q_ref.shape[-1]
    q_base, kv_start, kv_len = info_ref[b, 0], info_ref[b, 1], info_ref[b, 2]

    qf = q_ref[0].astype(jnp.float32)                          # (bq, dp)
    qq = _quantize_sym(qf, sq_ref[0], lo, hi, offset)
    q_pos = (q_base + qi * bq
             + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0))
    if causal:
        n_kv_eff = causal_block_bound(q_base, qi, bq, bk, n_kv)
    else:
        n_kv_eff = n_kv

    body = functools.partial(
        _online_block, qq=qq, q_pos=q_pos, load_kv=load_kv,
        gemm=functools.partial(lut_gemm, lut=lut_ref, n_planes=n_planes),
        m00=m00_ref[0], sks=sk_ref[0], svs=sv_ref[0], score_scale=ss_ref[0],
        pv_scale=pvs_ref[0], kv_start=kv_start, kv_len=kv_len, bq=bq, bk=bk,
        seq_k_real=seq_k_real, d_real=d_real, offset=offset, lo=lo, hi=hi,
        causal=causal, window=window, softcap=softcap)
    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, dp), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, n_kv_eff, body, (m0, l0, acc0))
    out = acc * pvs_ref[0] / jnp.maximum(l, 1e-30)[:, None]
    o_ref[...] = out[None]


def _approx_kernel(q_ref, k_ref, v_ref, lut_ref, info_ref, sq_ref, sk_ref,
                   sv_ref, ss_ref, pvs_ref, m00_ref, o_ref, *, bk: int,
                   seq_k: int, **statics):
    def load_kv(ki):
        start = pl.multiple_of(ki * bk, bk)
        return (k_ref[0, pl.ds(start, bk), :].astype(jnp.float32),
                v_ref[0, pl.ds(start, bk), :].astype(jnp.float32))

    _attend(q_ref, lut_ref, info_ref, sq_ref, sk_ref, sv_ref, ss_ref, pvs_ref,
            m00_ref, o_ref, load_kv, n_kv=seq_k // bk, bk=bk, **statics)


@functools.partial(jax.jit, static_argnames=(
    "seq_k_real", "d_real", "n_planes", "offset", "lo", "hi", "causal",
    "window", "softcap", "bq", "bk", "rep", "interpret"))
def approx_flash_attention_kernel(q, k, v, lut, rowinfo, sqs, sks, svs,
                                  score_scale, pv_scale, m00, *,
                                  seq_k_real: int, d_real: int, n_planes: int,
                                  offset: int, lo: int, hi: int, causal: bool,
                                  window: int | None, softcap: float | None,
                                  bq: int, bk: int, rep: int,
                                  interpret: bool | None = None):
    """Pre-padded entry: q (B*Hq, Sq_p, Dp) f32, k/v (B*Hkv, Sk_p, Dp),
    ``lut`` the (R, L) padded table, ``rowinfo`` (B*Hq, 3) int32 rows
    ``[q_base, kv_start, kv_len]``, five (1,)-shaped f32 scale operands and
    the (1,) int32 ``M[0, 0]``. Returns (B*Hq, Sq_p, Dp) float32."""
    bh, sq_p, dp = q.shape
    bh_kv, sk_p, _ = k.shape
    assert bh == bh_kv * rep, (bh, bh_kv, rep)
    assert sq_p % bq == 0 and sk_p % bk == 0, (sq_p, sk_p, bq, bk)
    return pl.pallas_call(
        functools.partial(_approx_kernel, bq=bq, bk=bk, seq_k=sk_p,
                          seq_k_real=seq_k_real, d_real=d_real,
                          n_planes=n_planes, offset=offset, lo=lo, hi=hi,
                          causal=causal, window=window, softcap=softcap),
        name="approx_flash_attention_kernel",
        grid=(bh, sq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, sk_p, dp), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec((1, sk_p, dp), lambda b, i: (b // rep, 0, 0)),
            pl.BlockSpec(lut.shape, lambda b, i: (0, 0)),
            SMEM, SMEM, SMEM, SMEM, SMEM, SMEM, SMEM,
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, k, v, lut, rowinfo, sqs, sks, svs, score_scale, pv_scale, m00)


def prepare_approx_attention(q, k, v, lut, offset, q_scale, k_scale, v_scale,
                             *, bits: int, rowinfo, bq: int, bk: int):
    """Shared padding/geometry/scale resolution for the kernel wrapper AND
    the jnp oracle — both must see byte-identical padded operands and
    statics for the bitwise contract to be meaningful.

    Returns ``(operands, statics)``: operands is the tuple the kernel takes
    positionally; statics is a dict of the static keyword arguments.
    """
    tab, n_planes, m00 = table_operands(lut, offset)
    bh, sq, d = q.shape
    bh_kv, sk, _ = k.shape
    rep = bh // bh_kv
    assert bh == bh_kv * rep, (bh, bh_kv)
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    # q tiles align to 8 sublanes, kv blocks to the 128-lane tile; small
    # sequences shrink the block instead of padding to the full default
    bq = min(bq, round_up(sq, 8))
    bk = min(bk, round_up(sk, 128))
    dp = round_up(d, 16)
    sq_p = round_up(sq, bq)
    sk_p = round_up(sk, bk)
    qf = jnp.asarray(q, jnp.float32)
    kf = jnp.asarray(k, jnp.float32)
    vf = jnp.asarray(v, jnp.float32)
    if sq_p != sq or dp != d:
        qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, dp - d)))
    if sk_p != sk or dp != d:
        kf = jnp.pad(kf, ((0, 0), (0, sk_p - sk), (0, dp - d)))
        vf = jnp.pad(vf, ((0, 0), (0, sk_p - sk), (0, dp - d)))
    if rowinfo is None:
        # decode convention: queries end-aligned to the key sequence
        row = jnp.array([sk - sq, 0, sk], jnp.int32)
        rowinfo = jnp.broadcast_to(row, (bh, 3))
    rowinfo = jnp.asarray(rowinfo, jnp.int32)
    assert rowinfo.shape == (bh, 3), rowinfo.shape
    sqs = jnp.asarray(q_scale, jnp.float32).reshape(1)
    sks = jnp.asarray(k_scale, jnp.float32).reshape(1)
    svs = jnp.asarray(v_scale, jnp.float32).reshape(1)
    score_scale, pv_scale = attn_scales(sqs, sks, svs, d, hi)
    operands = (qf, kf, vf, tab, rowinfo, sqs, sks, svs, score_scale,
                pv_scale, m00)
    statics = dict(seq_k_real=sk, d_real=d, n_planes=n_planes, offset=offset,
                   lo=lo, hi=hi, bq=bq, bk=bk, rep=rep)
    return operands, statics


def approx_flash_attention(q, k, v, lut, offset, q_scale, k_scale, v_scale, *,
                           bits: int = 8, causal: bool = True,
                           window: int | None = None,
                           softcap: float | None = None, rowinfo=None,
                           bq: int = 128, bk: int = 128,
                           interpret: bool | None = None):
    """Approximate GQA flash attention on the ACU.

    ``q``: (B*Hq, Sq, D) float; ``k``/``v``: (B*Hkv, Sk, D) float with
    ``Hq % Hkv == 0`` folded into the leading dim; ``lut`` the ACU product
    table ((n, n) or flattened) with shifted-code ``offset``;
    ``q_scale``/``k_scale``/``v_scale`` per-tensor symmetric scales (compute
    with ``inline_symmetric_scale`` so they are pinned and context-safe).
    ``rowinfo``: optional (B*Hq, 3) int32 ``[q_base, kv_start, kv_len]`` —
    the absolute cache position of query row 0, and the half-open valid key
    range (serving: left-pad offset and written-cache length). Defaults to
    the end-aligned decode convention over the full key sequence.
    Returns (B*Hq, Sq, D) float32, bitwise-identical to
    ``approx_attention_ref``.
    """
    sq, d = q.shape[1], q.shape[2]
    operands, statics = prepare_approx_attention(
        q, k, v, lut, offset, q_scale, k_scale, v_scale, bits=bits,
        rowinfo=rowinfo, bq=bq, bk=bk)
    out = approx_flash_attention_kernel(
        *operands, causal=causal, window=window, softcap=softcap,
        interpret=interpret, **statics)
    return out[:, :sq, :d]


# ---------------------------------------------------------------------------
# paged KV: same online softmax, KV read through a per-row page table
# ---------------------------------------------------------------------------

def _approx_paged_kernel(q_ref, k_ref, v_ref, lut_ref, info_ref, pt_ref,
                         sq_ref, sk_ref, sv_ref, ss_ref, pvs_ref, m00_ref,
                         o_ref, *, bk: int, n_logical: int, **statics):
    """Paged twin of ``_approx_kernel``: ``k_ref``/``v_ref`` hold one KV
    head's slice of the physical block pool, ``pt_ref`` (SMEM) the page
    tables; logical block ``ki`` of row ``b`` starts at
    ``page_table[b, ki] * bk`` in the pool. ``seq_k_real`` is always the
    full logical extent (``n_logical * bk``) — pool blocks are whole by
    construction, so there is no structural tail pad to correct; validity
    lives entirely in ``kv_len``."""
    b = pl.program_id(0)

    def load_kv(ki):
        start = pl.multiple_of(pt_ref[b, ki] * bk, bk)
        return (k_ref[0, pl.ds(start, bk), :].astype(jnp.float32),
                v_ref[0, pl.ds(start, bk), :].astype(jnp.float32))

    _attend(q_ref, lut_ref, info_ref, sq_ref, sk_ref, sv_ref, ss_ref, pvs_ref,
            m00_ref, o_ref, load_kv, n_kv=n_logical, bk=bk,
            seq_k_real=n_logical * bk, **statics)


@functools.partial(jax.jit, static_argnames=(
    "d_real", "n_planes", "offset", "lo", "hi", "causal", "window", "softcap",
    "bq", "bk", "rep", "interpret"))
def approx_flash_attention_paged_kernel(q, k_pool, v_pool, lut, rowinfo,
                                        page_table, sqs, sks, svs,
                                        score_scale, pv_scale, m00, *,
                                        d_real: int, n_planes: int,
                                        offset: int, lo: int, hi: int,
                                        causal: bool, window: int | None,
                                        softcap: float | None, bq: int,
                                        bk: int, rep: int,
                                        interpret: bool | None = None):
    """Pre-padded paged entry: q (B*Hq, Sq_p, Dp) f32; ``k_pool``/``v_pool``
    (Hkv, P*bk, Dp) — the physical block pool, one row per KV head, blocks
    laid out back to back; ``rowinfo`` (B*Hq, 3) int32
    ``[q_base, kv_start, kv_len]`` in *logical* coordinates; ``page_table``
    (B*Hq, n_logical) int32 mapping each row's logical block to a physical
    block index into the pool. Returns (B*Hq, Sq_p, Dp) float32."""
    bh, sq_p, dp = q.shape
    hkv, pool_len, _ = k_pool.shape
    n_logical = page_table.shape[1]
    assert page_table.shape[0] == bh and rowinfo.shape == (bh, 3)
    assert sq_p % bq == 0 and pool_len % bk == 0, (sq_p, pool_len, bq, bk)
    return pl.pallas_call(
        functools.partial(_approx_paged_kernel, bq=bq, bk=bk,
                          n_logical=n_logical, d_real=d_real,
                          n_planes=n_planes, offset=offset, lo=lo, hi=hi,
                          causal=causal, window=window, softcap=softcap),
        name="approx_flash_attention_paged_kernel",
        grid=(bh, sq_p // bq),
        in_specs=[
            pl.BlockSpec((1, bq, dp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, pool_len, dp),
                         lambda b, i: ((b // rep) % hkv, 0, 0)),
            pl.BlockSpec((1, pool_len, dp),
                         lambda b, i: ((b // rep) % hkv, 0, 0)),
            pl.BlockSpec(lut.shape, lambda b, i: (0, 0)),
            SMEM, SMEM, SMEM, SMEM, SMEM, SMEM, SMEM, SMEM,
        ],
        out_specs=pl.BlockSpec((1, bq, dp), lambda b, i: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq_p, dp), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(q, k_pool, v_pool, lut, rowinfo, page_table, sqs, sks, svs,
      score_scale, pv_scale, m00)


def prepare_approx_attention_paged(q, k_pool, v_pool, lut, offset, q_scale,
                                   k_scale, v_scale, *, bits: int, rowinfo,
                                   page_table, bq: int):
    """Shared padding/geometry/scale resolution for the paged kernel AND its
    jnp oracle (mirror of :func:`prepare_approx_attention`). The KV block
    size is fixed by the pool layout (``bk = pool block extent``), so only
    q-side geometry adapts; the pool's head dim is padded like the
    contiguous operands."""
    tab, n_planes, m00 = table_operands(lut, offset)
    bh, sq, d = q.shape
    hkv, n_phys, bk, _ = k_pool.shape
    page_table = jnp.asarray(page_table, jnp.int32)
    rowinfo = jnp.asarray(rowinfo, jnp.int32)
    assert rowinfo.shape == (bh, 3), rowinfo.shape
    assert page_table.shape[0] == bh, (page_table.shape, bh)
    lo = -(1 << (bits - 1))
    hi = (1 << (bits - 1)) - 1
    bq = min(bq, round_up(sq, 8))
    dp = round_up(d, 16)
    sq_p = round_up(sq, bq)
    qf = jnp.asarray(q, jnp.float32)
    kp = jnp.asarray(k_pool, jnp.float32).reshape(hkv, n_phys * bk, d)
    vp = jnp.asarray(v_pool, jnp.float32).reshape(hkv, n_phys * bk, d)
    if sq_p != sq or dp != d:
        qf = jnp.pad(qf, ((0, 0), (0, sq_p - sq), (0, dp - d)))
    if dp != d:
        kp = jnp.pad(kp, ((0, 0), (0, 0), (0, dp - d)))
        vp = jnp.pad(vp, ((0, 0), (0, 0), (0, dp - d)))
    sqs = jnp.asarray(q_scale, jnp.float32).reshape(1)
    sks = jnp.asarray(k_scale, jnp.float32).reshape(1)
    svs = jnp.asarray(v_scale, jnp.float32).reshape(1)
    score_scale, pv_scale = attn_scales(sqs, sks, svs, d, hi)
    operands = (qf, kp, vp, tab, rowinfo, page_table, sqs, sks, svs,
                score_scale, pv_scale, m00)
    statics = dict(d_real=d, n_planes=n_planes, offset=offset, lo=lo, hi=hi,
                   bq=bq, bk=bk)
    return operands, statics


def approx_flash_attention_paged(q, k_pool, v_pool, lut, offset, q_scale,
                                 k_scale, v_scale, *, rowinfo, page_table,
                                 rep: int, bits: int = 8, causal: bool = True,
                                 window: int | None = None,
                                 softcap: float | None = None, bq: int = 128,
                                 interpret: bool | None = None):
    """Approximate GQA flash attention over block-paged KV.

    ``q``: (B*Hq, Sq, D) float; ``k_pool``/``v_pool``: (Hkv, P, bk, D) —
    the physical KV block pool shared by every sequence (``P`` physical
    blocks of ``bk`` positions each, per KV head); ``page_table``:
    (B*Hq, n_logical) int32, each row mapping its logical KV blocks to
    physical block indices (entries past the row's allocation may point at
    any pool block: nothing past ``kv_len`` is observable); ``rowinfo``: (B*Hq, 3) int32 logical
    ``[q_base, kv_start, kv_len]`` — REQUIRED here, there is no full-pool
    default that makes sense. ``rep = Hq // Hkv`` maps query row
    ``b`` to pool row ``(b // rep) % Hkv``.

    Bitwise-identical to ``approx_attention_paged_ref``, and to the
    contiguous :func:`approx_flash_attention` at ``bk = block size`` when
    the gathered blocks hold the same values as the contiguous layout.
    """
    sq, d = q.shape[1], q.shape[2]
    operands, statics = prepare_approx_attention_paged(
        q, k_pool, v_pool, lut, offset, q_scale, k_scale, v_scale,
        bits=bits, rowinfo=rowinfo, page_table=page_table, bq=bq)
    out = approx_flash_attention_paged_kernel(
        *operands, causal=causal, window=window, softcap=softcap, rep=rep,
        interpret=interpret, **statics)
    return out[:, :sq, :d]
