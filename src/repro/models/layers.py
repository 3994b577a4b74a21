"""Shared transformer building blocks (pure functional JAX).

Every GEMM goes through :func:`repro.core.approx_ops.approx_dense`, so the
paper's ACU emulation is a first-class switch on any architecture
(``cfg=None`` -> exact bf16 substrate path).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.approx_ops import (ApproxConfig, approx_attention,
                                   approx_attention_paged, approx_dense,
                                   conv2d)
from repro.parallel.sharding import shard

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# conv building block (vision stacks, GAN generators, audio frontends)
# ---------------------------------------------------------------------------

def conv2d_block(x: Array, w: Array, b: Optional[Array] = None, *,
                 stride=(1, 1), padding="SAME", dilation=(1, 1),
                 groups: int = 1, acfg: Optional[ApproxConfig] = None,
                 activation=None) -> Array:
    """Conv2d + optional bias + optional activation — the shared conv
    call site for every model in this package.

    Routing is resolved per layer by :func:`repro.core.acu.conv_plan`:
    LUT-mode Pallas ACUs run the fused patch-streaming
    im2col->quantize->LUT-GEMM->dequant kernel (the patch tensor never
    reaches HBM) — whole-image resident inside the VMEM budget, spatially
    tiled over halo'd output-row bands above it, so ImageNet-scale (224^2)
    feature maps stay fused — and everything else takes the audited eager
    im2col fallback; under an active mesh the plan shards batch x
    output-row-band rows over ``acu_conv_rows`` and output channels over
    ``acu_conv_cols``. ``acfg=None`` is the exact substrate conv.
    """
    y = conv2d(x, w, b, stride=stride, padding=padding, dilation=dilation,
               groups=groups, cfg=acfg)
    return y if activation is None else activation(y)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rms_norm(x: Array, scale: Array, eps: float = 1e-6,
             plus_one: bool = False) -> Array:
    """RMSNorm; ``plus_one`` = gemma-style (1 + w) parameterization."""
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    w = (1.0 + scale.astype(jnp.float32)) if plus_one else scale.astype(jnp.float32)
    return (y * w).astype(x.dtype)


def layer_norm(x: Array, scale: Array, bias: Array, eps: float = 1e-5) -> Array:
    x32 = x.astype(jnp.float32)
    mu = x32.mean(-1, keepdims=True)
    var = ((x32 - mu) ** 2).mean(-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


# ---------------------------------------------------------------------------
# rotary embeddings (RoPE and Qwen2-VL M-RoPE)
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float = 10000.0) -> Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: Array, positions: Array, theta: float = 10000.0) -> Array:
    """x: (B, S, H, D); positions: (B, S) int."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (d/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, d/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: Array, positions: Array, sections=(16, 24, 24),
                theta: float = 10000.0) -> Array:
    """Qwen2-VL multimodal RoPE. positions: (3, B, S) — (temporal, h, w) ids.

    The d/2 rotary frequency channels are partitioned into ``sections``
    (t/h/w); each partition rotates by its own position stream. For text-only
    tokens all three streams are equal and M-RoPE reduces to RoPE.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                        # (d/2,)
    # build per-channel position selector
    sec = jnp.concatenate([jnp.full((s,), i, jnp.int32)
                           for i, s in enumerate(sections)])  # (d/2,)
    pos = jnp.take_along_axis(
        positions.astype(jnp.float32).transpose(1, 2, 0),      # (B, S, 3)
        sec[None, None, :].astype(jnp.int32) * jnp.ones(
            (*positions.shape[1:], 1), jnp.int32), axis=-1)    # (B, S, d/2)
    angles = pos * freqs
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def _mask_scores(s: Array, q_pos: Array, k_pos: Array, causal: bool,
                 window: Optional[int],
                 pad_mask: Optional[Array] = None) -> Array:
    if q_pos.ndim == 2:
        # per-row query positions (continuous batching: every slot decodes
        # at its own cache offset) — the structural mask gains a batch dim
        mask = jnp.ones((q_pos.shape[0], *s.shape[-2:]), bool)
        if causal:
            mask &= k_pos[None, None, :] <= q_pos[:, :, None]
        if window is not None:
            mask &= k_pos[None, None, :] > q_pos[:, :, None] - window
        if pad_mask is not None:
            mask &= pad_mask[:, None, :]
        return jnp.where(mask[:, None, None], s, -1e30)
    mask = jnp.ones(s.shape[-2:], bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window is not None:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    if pad_mask is not None:
        # (B, Tk) valid-key mask (serving: left-pad slots are False) joins
        # the (cq, Tk) structural mask batched: (B, 1, 1, cq, Tk)
        return jnp.where(mask[None, None, None] & pad_mask[:, None, None, None, :],
                         s, -1e30)
    return jnp.where(mask, s, -1e30)


def gqa_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                  window: Optional[int] = None, softcap: Optional[float] = None,
                  q_offset: int = 0, chunk: int = 512,
                  impl: str = "chunked", causal_blocking: bool = False,
                  pad_mask: Optional[Array] = None) -> Array:
    """Grouped-query attention.

    q: (B, S, Hq, D); k/v: (B, T, Hkv, D); returns (B, S, Hq, D).
    ``q_offset``: absolute position of q[0] within the key sequence (decode) —
    an int/scalar, or a (B,) int vector when every batch row sits at its own
    cache position (continuous batching).
    ``chunked`` processes q in blocks of ``chunk`` for O(S·chunk) score memory.
    ``pad_mask``: optional (B, T) bool, False keys are never attended (batched
    serving masks left-pad slots out of every query row).
    """
    b, s_len, hq, d = q.shape
    t_len = k.shape[1]
    hkv = k.shape[2]
    rep = hq // hkv
    scale = 1.0 / (d ** 0.5)
    qg = q.reshape(b, s_len, hkv, rep, d)
    per_row = jnp.ndim(q_offset) == 1

    def q_positions(start: int, length: int) -> Array:
        pos = jnp.arange(length) + start
        if per_row:
            return pos[None, :] + jnp.asarray(q_offset, jnp.int32)[:, None]
        return pos + q_offset

    def block(q_blk: Array, q_pos: Array, k_blk: Array, v_blk: Array,
              k_pos: Array, pm: Optional[Array]) -> Array:
        # q_blk: (B, cq, Hkv, rep, D) -> scores (B, Hkv, rep, cq, Tk)
        sc = jnp.einsum("bqhrd,bthd->bhrqt", q_blk.astype(jnp.float32),
                        k_blk.astype(jnp.float32)) * scale
        if softcap is not None:
            sc = softcap * jnp.tanh(sc / softcap)
        sc = _mask_scores(sc, q_pos, k_pos, causal, window, pm)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("bhrqt,bthd->bqhrd", p, v_blk.astype(jnp.float32))
        return o

    if impl == "naive" or s_len <= chunk or s_len % chunk != 0:
        out = block(qg, q_positions(0, s_len), k, v,
                    jnp.arange(t_len), pad_mask)
    else:
        # statically unrolled q-block loop (NOT lax.map): keeps score memory at
        # O(S*chunk) while every block appears in the HLO, so cost_analysis
        # counts the true attention FLOPs (DESIGN.md §7 — scan bodies are
        # counted once). XLA reuses the temp buffers across blocks.
        n_blk = s_len // chunk
        outs = []
        for i in range(n_blk):
            q_blk = jax.lax.dynamic_slice_in_dim(qg, i * chunk, chunk, axis=1)
            pos = q_positions(i * chunk, chunk)
            if causal_blocking and causal and isinstance(q_offset, int) \
                    and q_offset == 0 and s_len == t_len:
                # §Perf hillclimb: a causal q-block only sees keys < its end;
                # slicing K/V per block drops ~half the attention FLOPs.
                hi = (i + 1) * chunk
                if window is not None:
                    lo = max(0, i * chunk - window)
                else:
                    lo = 0
                k_blk = k[:, lo:hi]
                v_blk = v[:, lo:hi]
                k_pos = jnp.arange(lo, hi)
                pm = None if pad_mask is None else pad_mask[:, lo:hi]
            else:
                k_blk, v_blk, k_pos, pm = k, v, jnp.arange(t_len), pad_mask
            outs.append(block(q_blk, pos, k_blk, v_blk, k_pos, pm))
        out = jnp.concatenate(outs, axis=1)
    return out.reshape(b, s_len, hq, d).astype(q.dtype)


@jax.named_scope("attn")
def attention_block(x: Array, p: dict, cfg, acfg: Optional[ApproxConfig],
                    positions: Array, *, kv: Optional[tuple] = None,
                    cache=None, cache_pos: Optional[Array] = None,
                    window: Optional[int] = None, causal: bool = True,
                    pad_mask: Optional[Array] = None,
                    page_table: Optional[Array] = None):
    """Full attention sub-layer: qkv proj -> rope -> attention -> out proj.

    ``cache``: optional (k_cache, v_cache) of shape (B, Smax, Hkv, D);
    returns (out, new_cache). ``kv``: cross-attention source (B, T, D).
    ``pad_mask``: (B, T) bool over the key length (the full cache when one is
    threaded) — False slots never contribute to any query.

    ``page_table`` switches the cache to the block-paged layout: ``cache``
    is then (k_pool, v_pool) of shape (Hkv, P, block, D) — a physical block
    pool shared by every row — and ``page_table`` (B, n_logical) int32 maps
    each row's logical KV blocks to pool blocks. New K/V append through the
    table (decode: per-row scatter at ``cache_pos``; prefill: batch-1
    block-aligned chunks of at most one block), attention reads through it
    (fused paged kernel, or an exact gather fallback when the plan audits
    to dense). No left-padding exists in the paged scheme, so ``pad_mask``
    is ignored here.
    """
    b, s_len, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    src = x if kv is None else kv
    t0 = src.shape[1]
    q = approx_dense(x, p["wq"], p.get("bq"), acfg)
    k = approx_dense(src, p["wk"], p.get("bk"), acfg)
    if cfg.qk_norm:  # over the whole (h * hd)-wide projection, before heads
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(b, s_len, h, hd)
    k = k.reshape(b, t0, hkv, hd)
    v = approx_dense(src, p["wv"], p.get("bv"), acfg).reshape(b, t0, hkv, hd)

    if kv is None and cfg.rope != "none":
        if cfg.rope == "mrope":
            mpos = jnp.broadcast_to(positions[None], (3, *positions.shape))
            q = apply_mrope(q, mpos, cfg.mrope_sections, cfg.rope_theta)
            k = apply_mrope(k, mpos, cfg.mrope_sections, cfg.rope_theta)
        else:
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)

    q = shard(q, "batch", None, "heads", None)
    k = shard(k, "batch", "seq_kv", "kv_heads", None)
    v = shard(v, "batch", "seq_kv", "kv_heads", None)

    if page_table is not None:
        assert cache is not None and kv is None, \
            "paged KV needs a (k_pool, v_pool) self-attention cache"
        kc, vc = cache
        hkv_p, _, blk, _ = kc.shape
        pt = jnp.asarray(page_table, jnp.int32)
        pos = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32).reshape(-1), (b,))
        if s_len == 1:
            # decode: each row scatters its one new KV into its own tail
            # block (CoW in the engine guarantees tail blocks are private)
            phys = jnp.take_along_axis(pt, (pos // blk)[:, None], axis=1)[:, 0]
            off = pos % blk
            kc = kc.at[:, phys, off].set(
                jnp.swapaxes(k[:, 0], 0, 1).astype(kc.dtype))
            vc = vc.at[:, phys, off].set(
                jnp.swapaxes(v[:, 0], 0, 1).astype(vc.dtype))
        else:
            # block-aligned chunked prefill: one request, one chunk starting
            # on a block boundary and fitting inside a single block
            assert b == 1 and s_len <= blk, (b, s_len, blk)
            phys = pt[0, pos[0] // blk]
            off = pos[0] % blk
            kc = jax.lax.dynamic_update_slice(
                kc, jnp.swapaxes(k[0], 0, 1)[:, None].astype(kc.dtype),
                (0, phys, off, 0))
            vc = jax.lax.dynamic_update_slice(
                vc, jnp.swapaxes(v[0], 0, 1)[:, None].astype(vc.dtype),
                (0, phys, off, 0))
        cache = (kc, vc)
        rowinfo = jnp.stack([pos, jnp.zeros_like(pos), pos + s_len], axis=1)
        fused = None
        if acfg is not None and not acfg.fake_quant_only:
            fused = approx_attention_paged(
                q.transpose(0, 2, 1, 3), kc, vc, acfg, page_table=pt,
                rowinfo=rowinfo, causal=causal, window=window,
                softcap=cfg.softcap_attn)
        if fused is not None:
            out = fused.transpose(0, 2, 1, 3).astype(q.dtype)
        else:
            # exact fallback: gather the referenced blocks back into a
            # contiguous (B, n_logical*block, Hkv, D) view — exact math is
            # layout-independent, and positions >= kv_len are masked out
            n_log = pt.shape[1]
            kg = jnp.moveaxis(kc[:, pt].reshape(hkv_p, b, n_log * blk, hd),
                              0, 2)
            vg = jnp.moveaxis(vc[:, pt].reshape(hkv_p, b, n_log * blk, hd),
                              0, 2)
            pm = jnp.arange(n_log * blk)[None, :] < (pos + s_len)[:, None]
            out = gqa_attention(q, kg, vg, causal=causal,
                                softcap=cfg.softcap_attn, window=window,
                                q_offset=pos, chunk=cfg.attn_chunk,
                                impl=cfg.attn_impl, pad_mask=pm)
        out = out.reshape(b, s_len, h * hd)
        out = approx_dense(out, p["wo"], p.get("bo"), acfg)
        return out, cache

    q_offset = 0
    if cache is not None:
        kc, vc = cache
        if kv is None:  # self-attention: append to cache
            if jnp.ndim(cache_pos) == 1:
                # continuous batching: every slot writes at its own offset
                upd = jax.vmap(lambda c, new, p0: jax.lax.
                               dynamic_update_slice_in_dim(c, new, p0, axis=0))
                kc = upd(kc, k.astype(kc.dtype), cache_pos)
                vc = upd(vc, v.astype(vc.dtype), cache_pos)
            else:
                kc = jax.lax.dynamic_update_slice_in_dim(kc, k.astype(kc.dtype), cache_pos, axis=1)
                vc = jax.lax.dynamic_update_slice_in_dim(vc, v.astype(vc.dtype), cache_pos, axis=1)
            k, v = kc, vc
            cache = (kc, vc)
        q_offset = cache_pos
        # mask out not-yet-written cache slots via causal masking at q_offset

    if acfg is not None and not acfg.fake_quant_only and kv is None \
            and cache is not None:
        # ACU route: fused quantize->LUT-gather QK^T / PV inside the
        # streaming-softmax kernel (core/acu.attn_plan). Falls through to the
        # exact-substrate gqa_attention when the plan audits to "dense".
        b_rows = jnp.broadcast_to(
            jnp.asarray(cache_pos, jnp.int32).reshape(-1), (b,))
        if pad_mask is not None:
            # serving pad is left-contiguous: first True marks the kv start
            kv_start = jnp.argmax(pad_mask, axis=1).astype(jnp.int32)
        else:
            kv_start = jnp.zeros((b,), jnp.int32)
        rowinfo = jnp.stack([b_rows, kv_start, b_rows + s_len], axis=1)
        fused = approx_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
            v.transpose(0, 2, 1, 3), acfg, causal=causal, window=window,
            softcap=cfg.softcap_attn, rowinfo=rowinfo)
        if fused is not None:
            out = fused.transpose(0, 2, 1, 3).astype(q.dtype)
            out = out.reshape(b, s_len, h * hd)
            out = approx_dense(out, p["wo"], p.get("bo"), acfg)
            return out, cache

    out = gqa_attention(q, k, v, causal=causal and kv is None, window=window,
                        softcap=cfg.softcap_attn, q_offset=q_offset,
                        chunk=cfg.attn_chunk, impl=cfg.attn_impl,
                        causal_blocking=getattr(cfg, "attn_causal_blocking", False),
                        pad_mask=pad_mask)
    out = out.reshape(b, s_len, h * hd)
    out = approx_dense(out, p["wo"], p.get("bo"), acfg)
    return out, cache


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

@jax.named_scope("mlp")
def mlp_block(x: Array, p: dict, cfg, acfg: Optional[ApproxConfig]) -> Array:
    """Gated (SwiGLU/GeGLU) or plain-GELU MLP, TP-sharded on the hidden dim."""
    if cfg.mlp_type in ("swiglu", "geglu"):
        gate = approx_dense(x, p["w_gate"], None, acfg)
        up = approx_dense(x, p["w_up"], None, acfg)
        act = jax.nn.silu(gate) if cfg.mlp_type == "swiglu" else jax.nn.gelu(gate)
        h = act * up
    else:
        h = jax.nn.gelu(approx_dense(x, p["w_up"], p.get("b_up"), acfg))
    h = shard(h, "batch", None, "mlp")
    return approx_dense(h, p["w_down"], p.get("b_down"), acfg)


# ---------------------------------------------------------------------------
# embedding / head
# ---------------------------------------------------------------------------

def embed(tokens: Array, table: Array) -> Array:
    return jnp.take(table, tokens, axis=0)


@jax.named_scope("lm_head")
def lm_head(x: Array, w: Array, acfg: Optional[ApproxConfig],
            softcap: Optional[float] = None) -> Array:
    logits = approx_dense(x, w, None, acfg)
    logits = shard(logits, "batch", None, "vocab")
    if softcap is not None:
        logits = softcap * jnp.tanh(logits / softcap)
    return logits


def cross_entropy(logits: Array, labels: Array, n_valid_vocab: int) -> Array:
    """Mean next-token CE; padded vocab columns masked out."""
    v = logits.shape[-1]
    if n_valid_vocab < v:
        neg = jnp.full((v - n_valid_vocab,), -1e30, logits.dtype)
        logits = logits.at[..., n_valid_vocab:].set(neg)
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
