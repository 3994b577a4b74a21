"""Decoder-only LM engine: init / forward / prefill / decode for every
assigned architecture via the layer-pattern system.

Layers are stacked into repeating *groups* (``cfg.pattern``) and the forward
pass is a ``lax.scan`` over groups — HLO stays one-group-sized regardless of
depth (compile time, and the roofline extractor's two-point unroll method
depends on this structure; see DESIGN.md §7).
"""
from __future__ import annotations

import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx_ops import ApproxConfig
from repro.models import layers as L
from repro.models.mamba import MambaState, mamba_block
from repro.models.moe import moe_block
from repro.models.rwkv import RwkvState, rwkv_block
from repro.parallel.sharding import shard

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _norm_params(cfg: ModelConfig, shape_d: int, g: int) -> dict:
    if cfg.norm == "ln":
        return {"w": jnp.ones((g, shape_d), jnp.float32),
                "b": jnp.zeros((g, shape_d), jnp.float32)}
    init = jnp.zeros if cfg.norm == "rms1p" else jnp.ones
    return {"w": init((g, shape_d), jnp.float32)}


def _dense_init(key, g, din, dout, cfg, scale=None):
    scale = scale or (din ** -0.5)
    return (jax.random.normal(key, (g, din, dout), jnp.float32) * scale
            ).astype(cfg.param_dtype)


def _init_attn(key, cfg: ModelConfig, g: int, cross: bool = False) -> dict:
    ks = jax.random.split(key, 8)
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    p = {
        "wq": _dense_init(ks[0], g, d, h * hd, cfg),
        "wk": _dense_init(ks[1], g, d, hkv * hd, cfg),
        "wv": _dense_init(ks[2], g, d, hkv * hd, cfg),
        "wo": _dense_init(ks[3], g, h * hd, d, cfg),
    }
    if cfg.qkv_bias and not cross:
        p["bq"] = jnp.zeros((g, h * hd), cfg.param_dtype)
        p["bk"] = jnp.zeros((g, hkv * hd), cfg.param_dtype)
        p["bv"] = jnp.zeros((g, hkv * hd), cfg.param_dtype)
    if cfg.qk_norm:  # over the whole projection, as OLMoE normalises
        p["q_norm"] = jnp.ones((g, h * hd), jnp.float32)
        p["k_norm"] = jnp.ones((g, hkv * hd), jnp.float32)
    return p


def _init_mlp(key, cfg: ModelConfig, g: int) -> dict:
    ks = jax.random.split(key, 3)
    d, f = cfg.d_model, cfg.d_ff
    if cfg.mlp_type in ("swiglu", "geglu"):
        return {"w_gate": _dense_init(ks[0], g, d, f, cfg),
                "w_up": _dense_init(ks[1], g, d, f, cfg),
                "w_down": _dense_init(ks[2], g, f, d, cfg)}
    return {"w_up": _dense_init(ks[0], g, d, f, cfg),
            "b_up": jnp.zeros((g, f), cfg.param_dtype),
            "w_down": _dense_init(ks[1], g, f, d, cfg),
            "b_down": jnp.zeros((g, d), cfg.param_dtype)}


def _init_moe(key, cfg: ModelConfig, g: int) -> dict:
    ks = jax.random.split(key, 4)
    d, f, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    s = d ** -0.5
    return {
        "router": (jax.random.normal(ks[0], (g, d, e), jnp.float32) * s
                   ).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (g, e, d, f), jnp.float32) * s
                   ).astype(cfg.param_dtype),
        "w_up": (jax.random.normal(ks[2], (g, e, d, f), jnp.float32) * s
                 ).astype(cfg.param_dtype),
        "w_down": (jax.random.normal(ks[3], (g, e, f, d), jnp.float32) * (f ** -0.5)
                   ).astype(cfg.param_dtype),
    }


def _init_mamba(key, cfg: ModelConfig, g: int) -> dict:
    ks = jax.random.split(key, 6)
    d, di, ds = cfg.d_model, cfg.mamba_d_inner, cfg.mamba_d_state
    dtr, dc = cfg.mamba_dt_rank, cfg.mamba_d_conv
    return {
        "in_proj": _dense_init(ks[0], g, d, 2 * di, cfg),
        "conv_w": (jax.random.normal(ks[1], (g, dc, di), jnp.float32) * 0.1
                   ).astype(cfg.param_dtype),
        "conv_b": jnp.zeros((g, di), cfg.param_dtype),
        "x_proj": _dense_init(ks[2], g, di, dtr + 2 * ds, cfg),
        "dt_proj": _dense_init(ks[3], g, dtr, di, cfg),
        "dt_bias": jnp.full((g, di), -4.6, cfg.param_dtype),  # softplus ~ 0.01
        "A_log": jnp.log(jnp.broadcast_to(
            jnp.arange(1, ds + 1, dtype=jnp.float32), (g, di, ds))),
        "Dskip": jnp.ones((g, di), cfg.param_dtype),
        "out_proj": _dense_init(ks[4], g, di, d, cfg),
    }


def _init_rwkv(key, cfg: ModelConfig, g: int) -> dict:
    ks = jax.random.split(key, 12)
    d, f = cfg.d_model, cfg.d_ff
    h, hd = cfg.rwkv_n_heads, cfg.rwkv_head_dim
    lora_r = max(32, d // 32)
    decay_r = max(64, d // 16)
    p = {
        "ln1_w": jnp.ones((g, d), jnp.float32), "ln1_b": jnp.zeros((g, d), jnp.float32),
        "ln2_w": jnp.ones((g, d), jnp.float32), "ln2_b": jnp.zeros((g, d), jnp.float32),
        "lora_A": _dense_init(ks[0], g, d, lora_r, cfg),
        "Wdecay_A": _dense_init(ks[1], g, d, decay_r, cfg),
        "Wdecay_B": (jax.random.normal(ks[2], (g, decay_r, d), jnp.float32) * 1e-2
                     ).astype(cfg.param_dtype),
        "decay_base": jnp.full((g, d), 0.5, jnp.float32),
        "bonus": jnp.zeros((g, d), jnp.float32),
        "Wr": _dense_init(ks[3], g, d, d, cfg),
        "Wk": _dense_init(ks[4], g, d, d, cfg),
        "Wv": _dense_init(ks[5], g, d, d, cfg),
        "Wg": _dense_init(ks[6], g, d, d, cfg),
        "Wo": _dense_init(ks[7], g, d, d, cfg),
        "ln_w": jnp.ones((g, d), jnp.float32), "ln_b": jnp.zeros((g, d), jnp.float32),
        "Wk_cm": _dense_init(ks[8], g, d, f, cfg),
        "Wv_cm": _dense_init(ks[9], g, f, d, cfg),
        "Wr_cm": _dense_init(ks[10], g, d, d, cfg),
    }
    for mu in ("mu_r", "mu_k", "mu_v", "mu_g", "mu_w", "cm_mu_k", "cm_mu_r"):
        p[mu] = jnp.full((g, d), 0.5, jnp.float32)
    for b in ("lora_B_r", "lora_B_k", "lora_B_v", "lora_B_g", "lora_B_w"):
        p[b] = jnp.zeros((g, lora_r, d), cfg.param_dtype)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    """Full parameter pytree; group-stacked leaves of shape (n_groups, ...)."""
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    g = cfg.n_groups
    d, v = cfg.d_model, cfg.vocab_padded
    groups: dict[str, Any] = {}
    for i, kind in enumerate(cfg.pattern):
        ki = jax.random.split(keys[i], 4)
        blk: dict[str, Any] = {"norm1": _norm_params(cfg, d, g)}
        if kind.startswith("attn"):
            blk["attn"] = _init_attn(ki[0], cfg, g)
            blk["norm2"] = _norm_params(cfg, d, g)
            if cfg.post_norm:
                blk["post_norm1"] = _norm_params(cfg, d, g)
                blk["post_norm2"] = _norm_params(cfg, d, g)
            blk["mlp"] = (_init_moe(ki[1], cfg, g) if kind.endswith("moe")
                          else _init_mlp(ki[1], cfg, g))
        elif kind.startswith("mamba"):
            blk["mamba"] = _init_mamba(ki[0], cfg, g)
            blk["norm2"] = _norm_params(cfg, d, g)
            blk["mlp"] = (_init_moe(ki[1], cfg, g) if kind.endswith("moe")
                          else _init_mlp(ki[1], cfg, g))
        elif kind == "rwkv":
            blk = {"rwkv": _init_rwkv(ki[0], cfg, g)}
        else:
            raise ValueError(kind)
        groups[f"b{i}"] = blk
    params = {
        "embed": (jax.random.normal(keys[-3], (v, d), jnp.float32) * (d ** -0.5)
                  ).astype(cfg.param_dtype),
        "groups": groups,
        "final_norm": _norm_params(cfg, d, 1),
    }
    if not cfg.tie_embed:
        params["lm_head"] = _dense_init(keys[-2], 1, d, v, cfg)[0]
    return params


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _norm(x, p, cfg: ModelConfig):
    if cfg.norm == "ln":
        return L.layer_norm(x, p["w"], p["b"])
    return L.rms_norm(x, p["w"], cfg.norm_eps, plus_one=(cfg.norm == "rms1p"))


def _apply_block(x, blk, kind, cfg, acfg, positions, cache, cache_pos, decode,
                 pad_mask=None, page_table=None):
    """One layer; returns (x, new_cache_entry)."""
    new_cache = cache
    if kind.startswith("attn"):
        window = cfg.window_size if kind == "attn_local" else None
        h = _norm(x, blk["norm1"], cfg)
        attn_cache = cache["attn"] if cache is not None else None
        a, attn_cache = L.attention_block(
            h, blk["attn"], cfg, acfg, positions, cache=attn_cache,
            cache_pos=cache_pos, window=window, pad_mask=pad_mask,
            page_table=page_table)
        if cfg.post_norm:
            a = _norm(a, blk["post_norm1"], cfg)
        if cfg.parallel_block:
            m = mlp_apply(h, blk["mlp"], kind, cfg, acfg)
            x = x + a + m
        else:
            x = x + a
            h2 = _norm(x, blk["norm2"], cfg)
            m = mlp_apply(h2, blk["mlp"], kind, cfg, acfg)
            if cfg.post_norm:
                m = _norm(m, blk["post_norm2"], cfg)
            x = x + m
        if cache is not None:
            new_cache = {**cache, "attn": attn_cache}
    elif kind.startswith("mamba"):
        h = _norm(x, blk["norm1"], cfg)
        st = cache["mamba"] if cache is not None else None
        m, st = mamba_block(h, blk["mamba"], cfg, acfg, state=st, decode=decode)
        x = x + m
        h2 = _norm(x, blk["norm2"], cfg)
        x = x + mlp_apply(h2, blk["mlp"], kind, cfg, acfg)
        if cache is not None:
            new_cache = {**cache, "mamba": st}
    elif kind == "rwkv":
        st = cache["rwkv"] if cache is not None else None
        x, st = rwkv_block(x, blk["rwkv"], cfg, acfg, state=st, decode=decode)
        if cache is not None:
            new_cache = {**cache, "rwkv": st}
    return x, new_cache


def mlp_apply(h, p, kind, cfg, acfg):
    if kind.endswith("moe"):
        return moe_block(h, p, cfg, acfg)
    return L.mlp_block(h, p, cfg, acfg)


def apply_model(params: dict, tokens: Array, cfg: ModelConfig, *,
                acfg: Optional[ApproxConfig] = None, cache: Optional[dict] = None,
                cache_pos: int | Array = 0, decode: bool = False,
                last_only: bool = False, pos_offset: Optional[Array] = None,
                pad_mask: Optional[Array] = None,
                page_table: Optional[Array] = None):
    """Token ids -> logits. With ``cache``, also threads KV/SSM state.

    cache: {"groups": pytree stacked (n_groups, ...)}; returns (logits, cache).

    Batched serving with left-padded prompts passes ``pos_offset`` (B,) —
    each row's pad count, subtracted from RoPE positions so every request
    sees positions 0..len-1 regardless of wave padding — and ``pad_mask``
    (B, T) over the key length so pad slots never contribute attention mass
    (attention layers only; recurrent blocks still ingest pads).

    ``page_table`` (B, n_logical) int32 switches attention caches to the
    block-paged layout (:func:`init_paged_cache`): one physical pool per
    layer shared by all rows, the same table threaded to every attention
    layer (the engine allocates blocks per slot, not per layer).
    """
    b, s = tokens.shape
    with jax.named_scope("embed"):
        x = L.embed(tokens, params["embed"])
        if cfg.embed_scale:
            x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    x = shard(x, "batch", None, None)
    cp = jnp.asarray(cache_pos)
    # cache_pos may be a (B,) vector — continuous batching, every slot decodes
    # at its own cache offset — or the usual scalar (wave serving / training)
    positions = jnp.arange(s)[None, :] + (cp[:, None] if cp.ndim == 1 else cp)
    if pos_offset is not None:
        positions = jnp.maximum(positions - pos_offset[:, None], 0)
    positions = jnp.broadcast_to(positions, (b, s))

    group_cache = cache["groups"] if cache is not None else None

    def group_body(xc, scanned):
        x = xc
        gp, gc = scanned
        new_gc = gc
        for i, kind in enumerate(cfg.pattern):
            blk_cache = None if gc is None else gc[f"b{i}"]
            x, blk_cache = _apply_block(x, gp[f"b{i}"], kind, cfg, acfg,
                                        positions, blk_cache, cache_pos, decode,
                                        pad_mask, page_table)
            if new_gc is not None:
                new_gc = {**new_gc, f"b{i}": blk_cache}
        return x, new_gc

    body = group_body
    if cfg.remat and cache is None:
        policy = (jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(group_body, policy=policy)

    if group_cache is None:
        x, _ = jax.lax.scan(lambda c, gp: body(c, (gp, None)),
                            x, params["groups"], unroll=cfg.scan_unroll)
        new_cache = None
    else:
        x, new_groups = jax.lax.scan(body, x, (params["groups"], group_cache),
                                     unroll=cfg.scan_unroll)
        new_cache = {"groups": new_groups}

    if last_only:
        # serving prefill: only the last position's logits are needed —
        # skips a (B, S, V) logits tensor and its GEMM
        x = x[:, -1:]
    with jax.named_scope("final_norm"):
        x = _norm(x, jax.tree.map(lambda a: a[0], params["final_norm"]), cfg)
    head = params["embed"].T if cfg.tie_embed else params["lm_head"]
    logits = L.lm_head(x, head, acfg, softcap=cfg.softcap_final)
    return logits, new_cache


def loss_fn(params, tokens, labels, cfg: ModelConfig,
            acfg: Optional[ApproxConfig] = None) -> Array:
    logits, _ = apply_model(params, tokens, cfg, acfg=acfg)
    with jax.named_scope("loss"):
        return L.cross_entropy(logits, labels, cfg.vocab_size)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_cache(cfg: ModelConfig, batch: int, max_seq: int,
               dtype=None) -> dict:
    """Decode cache pytree, group-stacked like params."""
    dtype = dtype or cfg.param_dtype
    g = cfg.n_groups
    groups = {}
    for i, kind in enumerate(cfg.pattern):
        if kind.startswith("attn"):
            kv = jnp.zeros((g, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype)
            groups[f"b{i}"] = {"attn": (kv, kv)}
        elif kind.startswith("mamba"):
            groups[f"b{i}"] = {"mamba": MambaState(
                conv=jnp.zeros((g, batch, cfg.mamba_d_conv - 1, cfg.mamba_d_inner), dtype),
                ssm=jnp.zeros((g, batch, cfg.mamba_d_inner, cfg.mamba_d_state), jnp.float32),
            )}
        elif kind == "rwkv":
            hd = cfg.rwkv_head_dim
            groups[f"b{i}"] = {"rwkv": RwkvState(
                tm_shift=jnp.zeros((g, batch, 1, cfg.d_model), dtype),
                wkv=jnp.zeros((g, batch, cfg.rwkv_n_heads, hd, hd), jnp.float32),
                cm_shift=jnp.zeros((g, batch, 1, cfg.d_model), dtype),
            )}
    return {"groups": groups}


def init_paged_cache(cfg: ModelConfig, n_blocks: int, block_size: int,
                     dtype=None) -> dict:
    """Block-paged decode cache: per attention layer one physical pool
    ``(n_groups, Hkv, n_blocks, block_size, head_dim)`` shared by every
    sequence; rows address it through the ``page_table`` threaded into
    :func:`apply_model`. Physical block 0 is the engine's permanently-zero
    *null block* (page tables default to it, so unallocated logical blocks
    gather zeros — matching what a contiguous cache holds past its fill).
    Only attention layers page; recurrent state is O(1) per slot and keeps
    its dense layout.
    """
    dtype = dtype or cfg.param_dtype
    g = cfg.n_groups
    groups = {}
    for i, kind in enumerate(cfg.pattern):
        if kind.startswith("attn"):
            shape = (g, cfg.n_kv_heads, n_blocks, block_size, cfg.head_dim)
            # distinct arrays: an aliased (pool, pool) pair breaks buffer
            # donation in the serve engine's jitted steps
            groups[f"b{i}"] = {"attn": (jnp.zeros(shape, dtype),
                                        jnp.zeros(shape, dtype))}
        else:
            raise NotImplementedError("paged cache covers attention-only "
                                      f"patterns; got {kind!r}")
    return {"groups": groups}
