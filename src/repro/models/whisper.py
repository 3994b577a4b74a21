"""Whisper-style encoder-decoder backbone (audio frontend stubbed per spec:
``input_specs()`` provides precomputed post-conv frame embeddings).

Encoder: non-causal self-attention stack over (B, enc_ctx, D) frames with
sinusoidal positions. Decoder: causal self-attention + cross-attention to the
encoder output, learned positions. Both stacks scan over layer groups like
the decoder-only engine.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core.approx_ops import ApproxConfig
from repro.models import layers as L
from repro.models.transformer import _init_attn, _init_mlp, _norm_params
from repro.parallel.sharding import shard

Array = jnp.ndarray


def _sinusoid(n: int, d: int) -> Array:
    pos = jnp.arange(n)[:, None].astype(jnp.float32)
    dim = jnp.arange(d // 2)[None, :].astype(jnp.float32)
    inv = jnp.exp(-dim * (jnp.log(10000.0) / (d // 2 - 1)))
    ang = pos * inv
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def init_params(key, cfg: ModelConfig) -> dict:
    keys = jax.random.split(key, 8)
    g_enc, g_dec = cfg.n_enc_layers, cfg.n_layers
    d, v = cfg.d_model, cfg.vocab_padded
    enc = {
        "attn": _init_attn(keys[0], cfg, g_enc),
        "mlp": _init_mlp(keys[1], cfg, g_enc),
        "norm1": _norm_params(cfg, d, g_enc),
        "norm2": _norm_params(cfg, d, g_enc),
    }
    dec = {
        "self_attn": _init_attn(keys[2], cfg, g_dec),
        "cross_attn": _init_attn(keys[3], cfg, g_dec, cross=True),
        "mlp": _init_mlp(keys[4], cfg, g_dec),
        "norm1": _norm_params(cfg, d, g_dec),
        "norm_x": _norm_params(cfg, d, g_dec),
        "norm2": _norm_params(cfg, d, g_dec),
    }
    return {
        "embed": (jax.random.normal(keys[5], (v, d), jnp.float32) * d ** -0.5
                  ).astype(cfg.param_dtype),
        "dec_pos": (jax.random.normal(keys[6], (cfg.max_dec_pos, d), jnp.float32)
                    * 0.01).astype(cfg.param_dtype),
        "enc": enc,
        "dec": dec,
        "enc_norm": _norm_params(cfg, d, 1),
        "final_norm": _norm_params(cfg, d, 1),
        "lm_head": (jax.random.normal(keys[7], (d, v), jnp.float32) * d ** -0.5
                    ).astype(cfg.param_dtype),
    }


def _norm(x, p, cfg):
    if cfg.norm == "ln":
        return L.layer_norm(x, p["w"], p["b"])
    return L.rms_norm(x, p["w"], cfg.norm_eps)


def encode(params: dict, frames: Array, cfg: ModelConfig,
           acfg: Optional[ApproxConfig] = None) -> Array:
    """frames: (B, enc_ctx, D) stub embeddings -> (B, enc_ctx, D)."""
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)[None]
    x = shard(x, "batch", None, None)
    dummy_pos = jnp.zeros(frames.shape[:2], jnp.int32)

    def body(x, gp):
        h = _norm(x, gp["norm1"], cfg)
        a, _ = L.attention_block(h, gp["attn"], cfg, acfg, dummy_pos,
                                 causal=False)
        x = x + a
        x = x + L.mlp_block(_norm(x, gp["norm2"], cfg), gp["mlp"], cfg, acfg)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)
    x, _ = jax.lax.scan(body, x, params["enc"], unroll=cfg.scan_unroll)
    return _norm(x, jax.tree.map(lambda a: a[0], params["enc_norm"]), cfg)


def decode(params: dict, tokens: Array, enc_out: Array, cfg: ModelConfig, *,
           acfg: Optional[ApproxConfig] = None, cache: Optional[dict] = None,
           cache_pos: int | Array = 0, last_only: bool = False):
    """tokens: (B, S) -> logits; cross-attends to enc_out (B, T, D)."""
    b, s = tokens.shape
    x = L.embed(tokens, params["embed"])
    pos_emb = jax.lax.dynamic_slice_in_dim(params["dec_pos"],
                                           jnp.asarray(cache_pos), s, axis=0)
    x = x + pos_emb[None]
    x = shard(x, "batch", None, None)
    positions = jnp.broadcast_to(jnp.arange(s)[None] + cache_pos, (b, s))
    group_cache = cache["groups"] if cache is not None else None

    def body(x, scanned):
        gp, gc = scanned
        h = _norm(x, gp["norm1"], cfg)
        sc = None if gc is None else gc["self"]
        a, sc = L.attention_block(h, gp["self_attn"], cfg, acfg, positions,
                                  cache=sc, cache_pos=cache_pos)
        x = x + a
        hx = _norm(x, gp["norm_x"], cfg)
        cx, _ = L.attention_block(hx, gp["cross_attn"], cfg, acfg, positions,
                                  kv=enc_out, causal=False)
        x = x + cx
        x = x + L.mlp_block(_norm(x, gp["norm2"], cfg), gp["mlp"], cfg, acfg)
        return x, (None if gc is None else {"self": sc})

    bodyfn = body
    if cfg.remat and cache is None:
        bodyfn = jax.checkpoint(body, policy=jax.checkpoint_policies.nothing_saveable)

    if group_cache is None:
        x, _ = jax.lax.scan(lambda c, gp: bodyfn(c, (gp, None)), x,
                            params["dec"], unroll=cfg.scan_unroll)
        new_cache = None
    else:
        x, new_groups = jax.lax.scan(bodyfn, x, (params["dec"], group_cache),
                                     unroll=cfg.scan_unroll)
        new_cache = {"groups": new_groups}

    if last_only:
        x = x[:, -1:]
    x = _norm(x, jax.tree.map(lambda a: a[0], params["final_norm"]), cfg)
    logits = L.lm_head(x, params["lm_head"], acfg)
    return logits, new_cache


def loss_fn(params, frames, tokens, labels, cfg, acfg=None):
    enc_out = encode(params, frames, cfg, acfg)
    logits, _ = decode(params, tokens, enc_out, cfg, acfg=acfg)
    return L.cross_entropy(logits, labels, cfg.vocab_size)


def init_cache(cfg: ModelConfig, batch: int, max_seq: int, dtype=None) -> dict:
    dtype = dtype or cfg.param_dtype
    kv = jnp.zeros((cfg.n_layers, batch, max_seq, cfg.n_kv_heads, cfg.head_dim), dtype)
    return {"groups": {"self": (kv, kv)}}
