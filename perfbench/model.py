"""A configuration file -> the program's model config, and the cell's
weights made on the device from the seed.

The weights are the benchmark's data: one jitted call draws them from the
seed, in the program's parameter layout (layers stacked on a leading axis),
so the program and the plain reference read the same values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench.common import BenchError, rng_key

# the decoder kinds the program's transformer and the reference both run
_LLAMA = ("LlamaForCausalLM",)


def check_supported(cfg: dict) -> None:
    if cfg.get("architectures") != list(_LLAMA) or cfg.get("hidden_act") != "silu":
        raise BenchError(f"{cfg['name']}: only a SwiGLU llama decoder is wired")


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def program_config(cfg: dict, dtype: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    check_supported(cfg)
    if cfg["rms_norm_eps"] != 1e-6:
        raise BenchError("the program's RMSNorm epsilon is 1e-6")
    return ModelConfig(
        name=cfg["name"], family="dense",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
        d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
        tie_embed=cfg["tie_word_embeddings"], rope_theta=cfg["rope_theta"],
        dtype=dtype)


def acu_spec(cfg: dict) -> str:
    """The program's ``mult:mode`` ACU spec of the configuration."""
    m = cfg["multiplier"]
    return f"{m['name']}:{m['mode']}"


def n_params(cfg: dict) -> int:
    """Parameters that enter a matrix product per token: every projection,
    the feed-forward and the output head (the tied embedding once)."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = d * h * hd * 2 + d * hkv * hd * 2 + 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * v


def gemm_shapes(cfg: dict, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of every approximate GEMM one forward pass over ``rows``
    tokens makes: per layer q, k, v, o, gate, up, down, then the head."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd = head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = [(rows, d, h * hd), (rows, d, hkv * hd), (rows, d, hkv * hd),
             (rows, h * hd, d), (rows, d, f), (rows, d, f), (rows, f, d)]
    return layer * cfg["num_hidden_layers"] + [(rows, d, v)]


@functools.partial(jax.jit, static_argnames=("shapes", "dtype"))
def _draw(key, shapes, dtype):
    """Normal(0, fan_in^-1/2) for matrices, ones for norm weights."""
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, (shape, fan_in) in zip(keys, shapes):
        if fan_in:
            w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        else:
            w = jnp.ones(shape, jnp.float32)
        out.append(w.astype(dtype))
    return out


def init_weights(cfg: dict, seed: int, dtype) -> dict:
    """The program's parameter pytree for a SwiGLU llama decoder with tied
    embeddings, drawn on the device from ``seed``."""
    check_supported(cfg)
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    L, hd = cfg["num_hidden_layers"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    spec = [
        ("embed", (v, d), d),
        ("norm1", (L, d), 0), ("norm2", (L, d), 0),
        ("wq", (L, d, h * hd), d), ("wk", (L, d, hkv * hd), d),
        ("wv", (L, d, hkv * hd), d), ("wo", (L, h * hd, d), h * hd),
        ("w_gate", (L, d, f), d), ("w_up", (L, d, f), d),
        ("w_down", (L, f, d), f), ("final_norm", (1, d), 0),
    ]
    if not cfg["tie_word_embeddings"]:
        spec.append(("lm_head", (d, v), d))
    ws = dict(zip([s[0] for s in spec],
                  _draw(rng_key(seed), tuple((s[1], s[2]) for s in spec),
                        jnp.dtype(dtype))))
    params = {
        "embed": ws["embed"],
        "groups": {"b0": {
            "norm1": {"w": ws["norm1"]},
            "attn": {k: ws[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": {"w": ws["norm2"]},
            "mlp": {k: ws[k] for k in ("w_gate", "w_up", "w_down")},
        }},
        "final_norm": {"w": ws["final_norm"]},
    }
    if "lm_head" in ws:
        params["lm_head"] = ws["lm_head"]
    return params
