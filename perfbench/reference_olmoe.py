"""The plain reference for OLMoE (allenai/OLMoE-1B-7B-0924): its forward
pass in straightforward ``jax.numpy`` at float32 (``highest`` matmul
precision), with every projection, each expert's gate, up and down, and
the head through the configuration's approximate multiplier.

It imports nothing of the program; the multiplier's bit-plane products,
the quantization scheme and the norms come from :mod:`perfbench.reference`.
Per layer, as published:

    a = RMSNorm(x);  q = RMSNorm_q(a Wq);  k = RMSNorm_k(a Wk)   (full width)
    x += Wo attention(rope(q), rope(k), a Wv)                   (causal)
    m = RMSNorm(x);  p = softmax(m Wr);  top-k of p, not renormalised
    x += sum over the k routed experts e of p_e Wd_e (silu(m Wg_e) * m Wu_e)

Every routed assignment is computed (dropless). Departures, each there to
state the emulated accelerator's arithmetic rather than the model's:

* the router runs exact (float32), as the program's does;
* each expert GEMM quantizes its activations with one per-tensor scale
  taken over the rows routed to some expert (the program's grouped kernel
  takes one scale over its dispatched tensor, whose dead rows are zero),
  and its weights per expert and output channel;
* every expert is computed on every token, in blocks of experts, and the
  unrouted products are weighted by zero: the simple form of dropless
  routing.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from perfbench.reference import (F32, _codes, _fwd_scale, _rms, _rope,
                                 approx_dense, int8_store, multiplier_einsum,
                                 token_nll)

EXPERT_BLOCK = 8      # experts computed at once
QUERY_BLOCK = 512     # query rows of one attention block


def _attention(q, k, v):
    """Exact causal softmax attention over blocks of query rows; q (B, S,
    H, D), k/v (B, S, Hkv, D)."""
    b, s, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2).astype(F32)
    v = jnp.repeat(v, rep, axis=2).astype(F32)
    blk = math.gcd(s, QUERY_BLOCK)
    kpos = jnp.arange(s)

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * blk, blk, 1).astype(F32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qb, k) / d ** 0.5
        qpos = i * blk + jnp.arange(blk)
        p = jax.nn.softmax(jnp.where(kpos[None, :] <= qpos[:, None], sc, -1e30),
                           axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v)

    out = jax.lax.map(one, jnp.arange(s // blk))       # (S/blk, B, blk, H, D)
    return out.transpose(1, 0, 2, 3, 4).reshape(b, s, h, d).astype(q.dtype)


def make_logits_batch(cfg: dict, dtype, store=None):
    """``logits(params, tokens)``: (B, S, V) float32 logits and each
    layer's count of routed rows per expert (L, E), over the program's
    parameter layout, stored tensors in ``dtype``, or in float32 passed
    through ``store`` after every op when it is given."""
    mult = cfg["multiplier"]
    dense2 = approx_dense(multiplier_einsum(mult), dtype)
    up_gemm = multiplier_einsum(mult, "tk,ekn->etn")
    down_gemm = multiplier_einsum(mult, "etk,ekn->etn")
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or cfg["hidden_size"] // h
    n_exp, top = cfg["num_experts"], cfg["num_experts_per_tok"]
    eb = math.gcd(n_exp, EXPERT_BLOCK)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    st = store or (lambda x: x)

    def wt(w):  # a stored weight, at the precision under test
        return st(w.astype(dtype)).astype(F32)

    def dense(x, w):
        lead = x.shape[:-1]
        return st(dense2(x.reshape(-1, x.shape[-1]), wt(w)).reshape(*lead, -1))

    def expert_gemm(gemm, a, amax, w):
        """Each expert's product over every row; ``a`` (.., T, K) float,
        ``w`` (eb, K, N); one activation scale from ``amax``."""
        xs = _fwd_scale(jnp.maximum(amax, 1e-6))
        w = wt(w)
        ws = _fwd_scale(jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-9))
        y = gemm(_codes(a.astype(F32), xs), _codes(w, ws[:, None, :]))
        return st((y.astype(F32) * (xs * ws)[:, None, :]).astype(dtype))

    def moe(x, p):
        """x (T, D) -> (T, D), and the routed rows per expert (E,)."""
        t, d = x.shape
        logits = x.astype(F32) @ p["router"].astype(F32)
        probs = jax.nn.softmax(logits, axis=-1)
        top_p, top_e = jax.lax.top_k(probs, top)
        if cfg["norm_topk_prob"]:
            top_p = top_p / top_p.sum(-1, keepdims=True)
        weight = jnp.zeros((t, n_exp), F32).at[
            jnp.arange(t)[:, None], top_e].set(top_p)        # (T, E)
        routed = jnp.zeros((t, n_exp), bool).at[
            jnp.arange(t)[:, None], top_e].set(True)
        x_amax = jnp.max(jnp.where(routed.any(1)[:, None],
                                   jnp.abs(x.astype(F32)), 0.0))

        def blocks(w):
            return w.reshape(n_exp // eb, eb, *w.shape[1:])

        def hidden(_, w):                                   # (eb, T, F)
            g = expert_gemm(up_gemm, x, x_amax, w["w_gate"])
            u = expert_gemm(up_gemm, x, x_amax, w["w_up"])
            return None, st(st(jax.nn.silu(g.astype(F32)).astype(dtype)) * u)

        _, hid = jax.lax.scan(hidden, None, {"w_gate": blocks(p["w_gate"]),
                                             "w_up": blocks(p["w_up"])})
        hid = hid.reshape(n_exp, t, -1)                     # (E, T, F)
        h_amax = jnp.max(jnp.where(routed.T[..., None],
                                   jnp.abs(hid.astype(F32)), 0.0))

        def down(acc, blk):
            hb, wd, wb = blk
            y = expert_gemm(down_gemm, hb, h_amax, wd)     # (eb, T, D)
            return acc + jnp.einsum("te,etd->td", wb, y.astype(F32)), None

        out, _ = jax.lax.scan(down, jnp.zeros((t, d), F32),
                              (blocks(hid), blocks(p["w_down"]),
                               blocks(weight.T).transpose(0, 2, 1)))
        return st(out.astype(dtype)), routed.sum(0)

    def layer(x, p):
        b, s, d = x.shape
        pos = jnp.arange(s)
        a = st(_rms(x, p["norm1"], eps))
        q = st(_rms(dense(a, p["wq"]), p["q_norm"], eps))
        k = st(_rms(dense(a, p["wk"]), p["k_norm"], eps))
        q = st(_rope(q.reshape(b, s, h, hd), pos, theta))
        k = st(_rope(k.reshape(b, s, hkv, hd), pos, theta))
        v = dense(a, p["wv"]).reshape(b, s, hkv, hd)
        x = st(x + dense(st(_attention(q, k, v)).reshape(b, s, h * hd), p["wo"]))
        m = st(_rms(x, p["norm2"], eps))
        y, load = moe(m.reshape(b * s, d), p)
        return st(x + y.reshape(b, s, d)), load

    def logits(params, tokens):
        g = params["groups"]["b0"]
        stacked = {"norm1": g["norm1"]["w"], "norm2": g["norm2"]["w"],
                   **g["attn"], **g["mlp"]}
        stacked = {n: (st(w.astype(dtype)) if w.ndim <= 3 else w)
                   for n, w in stacked.items()}
        x = st(params["embed"][tokens].astype(dtype))
        x, loads = jax.lax.scan(layer, x, stacked)
        x = st(_rms(x, st(params["final_norm"]["w"][0].astype(dtype)), eps))
        return dense(x, params["lm_head"]).astype(F32), loads

    return logits


def score(cfg: dict, params, batches, precision: str = "float32"):
    """Each token's next-token NLL (B, S) over ``batches``, scored as the
    batches they came in (the quantization scales are per batch), and each
    layer's routed rows per expert (L, E), with every stored tensor in
    ``precision``: a float dtype, or ``int8`` (per-tensor symmetric int8
    round trips in float32)."""
    if precision == "int8":
        dtype, store = F32, int8_store
    else:
        dtype, store = jnp.dtype(precision), None
    logits = make_logits_batch(cfg, dtype, store)

    @jax.jit
    def one(p, tokens, labels):
        with jax.default_matmul_precision("highest"):
            lg, loads = logits(p, tokens)
            return token_nll(lg, labels), loads

    out = [one(params, jnp.asarray(b["tokens"]), jnp.asarray(b["labels"]))
           for b in batches]
    return ([np.asarray(n) for n, _ in out], [np.asarray(c) for _, c in out])
