"""OLMoE through the normal scoring path, against its plain reference.

A tiny OLMoE (d 64, 4 heads, 8 experts top-2, 2 layers, vocab 512) runs
``apply_model`` with the approximate config, LUT kernels interpreted:
full-width QK-norm, the published epsilon, unrenormalised top-k weights
and dropless routing, each checked on its own, and the whole forward
against ``perfbench/reference_olmoe.py``.
"""
import dataclasses
import pathlib
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

from perfbench import olmoe, reference, reference_olmoe, traffic  # noqa: E402
from perfbench.common import BENCH_DIR, load_json  # noqa: E402
from repro.configs import get_config, reduced_config  # noqa: E402
from repro.launch.specs import make_acfg  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models.moe import _route, dispatch_geometry, moe_block  # noqa: E402
from repro.models.transformer import _init_moe, apply_model, init_params  # noqa: E402

TINY = {"hidden_size": 64, "intermediate_size": 32, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
        "num_experts": 8, "num_experts_per_tok": 2, "vocab_size": 512}
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def tiny():
    cfg = load_json(BENCH_DIR / "configs" / "olmoe-1b-7b.json")
    cfg.update(TINY)
    mix = load_json(BENCH_DIR / "mixes" / "eval-1x4096.json")
    mix["seq_len"] = 64
    return cfg, mix


def test_config_is_the_published_model():
    cfg = get_config("olmoe-1b-7b")
    assert (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim, cfg.d_ff, cfg.vocab_size) == (16, 2048, 16, 16, 128,
                                                        1024, 50304)
    assert (cfg.n_experts, cfg.moe_top_k) == (64, 8)
    assert cfg.qk_norm and cfg.norm_eps == 1e-5
    assert not cfg.moe_norm_topk and cfg.moe_capacity is None
    geo = dispatch_geometry(cfg, 4096)
    assert geo["dropless"] and geo["capacity"] == geo["tokens_per_block"]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_program_nll_matches_reference(tiny, dtype):
    """Per-token NLLs of the program (``fused_lut_dense`` and
    ``fused_lut_grouped`` interpreted) against the reference. In float32
    both compute the same integer GEMMs on the same codes, so they agree to
    float32 rounding of the norms, softmaxes and dequant scales (6e-8 a
    token, 1e-6 at most, on seeds 8 and 9, measured), unless a value
    within an ulp of a rounding boundary flips one code (seed 7: a mean of
    1.5e-4, 9.3e-3 at most): 1e-3 holds a flip or two and lies far below
    the bfloat16 program's gap. In bfloat16 the program's stored
    tensors round and flip codes (0.018-0.026 a token over six seeds,
    measured); 0.08 lies halfway to the float8 e5m2 control's 0.15-0.18
    at this size."""
    cfg, mix = tiny
    mcfg = olmoe.program_config(cfg, dtype)
    params = olmoe.init_weights(cfg, 7, mcfg.param_dtype)
    b = next(traffic.train_batches(mix, cfg["vocab_size"], 7))
    acfg = make_acfg("mul8s_1L2H:lut")
    logits, _ = jax.jit(lambda p, t: apply_model(p, t, mcfg, acfg=acfg))(
        params, b["tokens"])
    got = np.asarray(reference.token_nll(logits.astype(jnp.float32),
                                         b["labels"]))
    want = reference_olmoe.score(cfg, params, [b])[0][0]
    gap = np.abs(got - want).mean()
    assert gap < (1e-3 if dtype == "float32" else 0.08), gap


def _attn_cfg(qk_norm=True):
    return dataclasses.replace(reduced_config("olmoe-1b-7b"), norm_eps=1e-5,
                               qk_norm=qk_norm)


def _hand_attention(x, p, cfg, norm):
    """Exact attention with ``norm(q_flat, k_flat)`` applied by hand."""
    b, s, _ = x.shape
    h, hd = cfg.n_heads, cfg.head_dim
    q, k = norm(x @ p["wq"], x @ p["wk"])
    pos = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
    q = L.apply_rope(q.reshape(b, s, h, hd), pos, cfg.rope_theta)
    k = L.apply_rope(k.reshape(b, s, h, hd), pos, cfg.rope_theta)
    v = (x @ p["wv"]).reshape(b, s, h, hd)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / hd ** 0.5
    sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -1e30)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
    return o.reshape(b, s, h * hd) @ p["wo"]


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def test_qk_norm_is_over_the_whole_projection():
    """q and k are normalised over all h * hd lanes with one weight of
    that width, before the split into heads; a per-head norm differs."""
    cfg = _attn_cfg()
    d, h, hd = cfg.d_model, cfg.n_heads, cfg.head_dim
    ks = jax.random.split(KEY, 7)
    p = {n: jax.random.normal(k, (d, h * hd)) * d ** -0.5
         for n, k in zip(("wq", "wk", "wv"), ks)}
    p["wo"] = jax.random.normal(ks[3], (h * hd, d)) * (h * hd) ** -0.5
    p["q_norm"] = 1 + 0.5 * jax.random.normal(ks[4], (h * hd,))
    p["k_norm"] = 1 + 0.5 * jax.random.normal(ks[5], (h * hd,))
    x = jax.random.normal(ks[6], (2, 8, d))
    pos = jnp.broadcast_to(jnp.arange(8)[None], (2, 8))
    with jax.default_matmul_precision("highest"):
        got, _ = L.attention_block(x, p, cfg, None, pos)
        full = _hand_attention(x, p, cfg, lambda q, k: (
            _rms(q, p["q_norm"], 1e-5), _rms(k, p["k_norm"], 1e-5)))

        def per_head(q, k):
            q, k = q.reshape(2, 8, h, hd), k.reshape(2, 8, h, hd)
            return (_rms(q, p["q_norm"].reshape(h, hd), 1e-5).reshape(2, 8, -1),
                    _rms(k, p["k_norm"].reshape(h, hd), 1e-5).reshape(2, 8, -1))
        heads = _hand_attention(x, p, cfg, per_head)
    np.testing.assert_allclose(got, full, rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(got - heads)).max() > 1e-2


def _moe_cfg(**kw):
    return dataclasses.replace(reduced_config("olmoe-1b-7b"), **kw)


def _moe_params(cfg, key=KEY):
    return jax.tree.map(lambda a: a[0], _init_moe(key, cfg, 1))


def _dense_moe(x, p, weight):
    """Every expert on every token, combined by ``weight`` (T, E)."""
    xf = x.reshape(-1, x.shape[-1])
    ye = jnp.stack([(jax.nn.silu(xf @ p["w_gate"][e]) * (xf @ p["w_up"][e]))
                    @ p["w_down"][e] for e in range(weight.shape[1])], 1)
    return (weight[..., None] * ye).sum(1).reshape(x.shape)


def test_unrenormalised_topk_weights():
    """``moe_norm_topk=False``: each routed expert is weighted by its
    softmax probability, the k weights summing below 1."""
    cfg = _moe_cfg(moe_norm_topk=False)
    p = _moe_params(cfg)
    x = jax.random.normal(KEY, (2, 8, cfg.d_model))
    xf = x.reshape(16, -1)
    with jax.default_matmul_precision("highest"):
        probs = jax.nn.softmax(xf @ p["router"], -1)
        _, top_p, top_e = _route(xf, p["router"], cfg.moe_top_k, False)
        want_p, want_e = jax.lax.top_k(probs, cfg.moe_top_k)
        np.testing.assert_array_equal(top_e, want_e)
        np.testing.assert_allclose(top_p, want_p, rtol=1e-6)
        assert float(top_p.sum(-1).max()) < 1.0
        weight = jnp.zeros((16, cfg.n_experts)).at[
            jnp.arange(16)[:, None], top_e].set(top_p)
        out = moe_block(x, p, cfg, None)
        np.testing.assert_allclose(out, _dense_moe(x, p, weight),
                                   rtol=1e-4, atol=1e-5)


def test_dropless_keeps_every_assignment():
    """Every token routed to the same two experts: capacity factor 1.25
    drops half the assignments, dropless (``moe_capacity=None``) keeps them all and
    equals every expert computed on every token."""
    cfg = _moe_cfg(moe_capacity=None, moe_norm_topk=False)
    p = _moe_params(cfg)
    p["router"] = jnp.full_like(p["router"], -1.0).at[:, :2].set(1.0)
    x = jnp.abs(jax.random.normal(KEY, (2, 16, cfg.d_model))) + 0.5
    with jax.default_matmul_precision("highest"):
        out, stats = moe_block(x, p, cfg, None, return_stats=True)
        _, dropped = moe_block(x, p, dataclasses.replace(cfg, moe_capacity=1.25),
                               None, return_stats=True)
        probs = jax.nn.softmax(x.reshape(32, -1) @ p["router"], -1)
        weight = probs * (jnp.arange(cfg.n_experts) < 2)
        ref = _dense_moe(x, p, weight)
    assert float(stats["dropped_frac"]) == 0.0
    assert float(dropped["dropped_frac"]) >= 0.5
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-5)


@pytest.fixture(scope="module")
def moe_ops():
    cfg = reduced_config("olmoe-1b-7b")
    params = jax.eval_shape(lambda: init_params(KEY, cfg))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    fn = jax.jit(lambda p, t: apply_model(p, t, cfg)[0])
    text = fn.lower(params, tokens).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", ["route", "dispatch", "experts", "combine"])
def test_moe_parts_are_scoped(moe_ops, scope):
    assert any(f"moe/{scope}/" in n for n in moe_ops), scope
