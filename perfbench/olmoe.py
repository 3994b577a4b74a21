"""An OLMoE configuration file -> the program's model config, the cell's
weights made on the device from the seed, and the counts of its work.

The weights are the benchmark's data: one jitted call draws them from the
seed, in the program's parameter layout (layers stacked on a leading axis,
experts on the next), so the program and the plain reference read the same
values.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from perfbench import counts
from perfbench.common import BenchError, rng_key

_OLMOE = ("OlmoeForCausalLM",)


def check_supported(cfg: dict) -> None:
    if cfg.get("architectures") != list(_OLMOE) or cfg.get("hidden_act") != "silu":
        raise BenchError(f"{cfg['name']}: not an OLMoE decoder with SwiGLU experts")


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def program_config(cfg: dict, dtype: str):
    """The program's ``ModelConfig``: QK-norm over the whole projection, the
    published epsilon, top-k weights as the router gives them, dropless
    routing in one dispatch block (one chip, no mesh)."""
    from repro.configs.base import ModelConfig
    check_supported(cfg)
    try:
        return ModelConfig(
            name=cfg["name"], family="moe",
            n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
            n_heads=cfg["num_attention_heads"],
            n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim(cfg),
            d_ff=cfg["intermediate_size"], vocab_size=cfg["vocab_size"],
            pattern=("attn_moe",), n_experts=cfg["num_experts"],
            moe_top_k=cfg["num_experts_per_tok"], qk_norm=True,
            norm_eps=cfg["rms_norm_eps"],
            moe_norm_topk=cfg["norm_topk_prob"], moe_capacity=None,
            moe_shard_dispatch=False,
            tie_embed=cfg["tie_word_embeddings"], rope_theta=cfg["rope_theta"],
            dtype=dtype)
    except TypeError as e:  # a program without these options
        raise BenchError(f"the program cannot run {cfg['name']}: {e}") from e


def n_params(cfg: dict) -> int:
    """Parameters that enter a matrix product per token: the attention
    projections, the router, the k experts a token is routed to, and the
    output head."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    hd, e, k = head_dim(cfg), cfg["num_experts"], cfg["num_experts_per_tok"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = 2 * d * h * hd + 2 * d * hkv * hd + d * e + k * 3 * d * f
    return cfg["num_hidden_layers"] * layer + d * v


def dense_shapes(cfg: dict, rows: int) -> list[tuple[int, int, int]]:
    """(M, K, N) of the forward's approximate GEMMs on ``fused_lut_dense``
    over ``rows`` tokens: per layer q, k, v, o, then the head."""
    d, v, hd = cfg["hidden_size"], cfg["vocab_size"], head_dim(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layer = [(rows, d, h * hd), (rows, d, hkv * hd), (rows, d, hkv * hd),
             (rows, h * hd, d)]
    return layer * cfg["num_hidden_layers"] + [(rows, d, v)]


def expert_shapes(cfg: dict, rows: int) -> list[tuple[int, int, int, int]]:
    """(E, M, K, N) of the forward's grouped expert GEMMs over ``rows``
    tokens: per layer gate, up and down, each over the ``rows * k`` routed
    rows spread evenly over the E experts (M rows each)."""
    d, f, e = cfg["hidden_size"], cfg["intermediate_size"], cfg["num_experts"]
    m = rows * cfg["num_experts_per_tok"] / e
    return [(e, m, d, f), (e, m, d, f), (e, m, f, d)] * cfg["num_hidden_layers"]


def dense_work(cfg: dict, rows: int, act_bytes: int) -> list:
    return [counts.gemm_fwd(m, k, n, act_bytes)
            for m, k, n in dense_shapes(cfg, rows)]


def expert_work(cfg: dict, rows: int, act_bytes: int) -> list:
    """Each expert's GEMM over its share of the routed rows, its weights
    read once."""
    return [counts.gemm_fwd(m, k, n, act_bytes)
            for e, m, k, n in expert_shapes(cfg, rows) for _ in range(e)]


@functools.partial(jax.jit, static_argnames=("shapes",))
def _draw(key, shapes):
    """Normal(0, fan_in^-1/2) for matrices, ones for norm weights."""
    keys = jax.random.split(key, len(shapes))
    out = []
    for k, (shape, fan_in, dtype) in zip(keys, shapes):
        if fan_in:
            w = jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5
        else:
            w = jnp.ones(shape, jnp.float32)
        out.append(w.astype(dtype))
    return out


def init_weights(cfg: dict, seed: int, dtype) -> dict:
    """The program's parameter pytree, drawn on the device from ``seed``:
    matrices in ``dtype``, the router and the norm weights in float32 as
    the program keeps them.

    Embedding rows are Normal(0, 1), not Normal(0, d^-1/2): with random
    weights a long context's attention averages nearly one vector at every
    position, and at the smaller scale that shared vector outweighs each
    token's own row, so nearly every token picks the same experts. At
    unit scale the router spreads the rows over the experts about evenly,
    as the published model's load-balanced router does."""
    check_supported(cfg)
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    L, hd, e = cfg["num_hidden_layers"], head_dim(cfg), cfg["num_experts"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    w, f32 = jnp.dtype(dtype).name, "float32"
    spec = [
        ("embed", (v, d), 1, w), ("lm_head", (d, v), d, w),
        ("norm1", (L, d), 0, f32), ("norm2", (L, d), 0, f32),
        ("wq", (L, d, h * hd), d, w), ("wk", (L, d, hkv * hd), d, w),
        ("wv", (L, d, hkv * hd), d, w), ("wo", (L, h * hd, d), h * hd, w),
        ("q_norm", (L, h * hd), 0, f32), ("k_norm", (L, hkv * hd), 0, f32),
        ("router", (L, d, e), d, f32),
        ("w_gate", (L, e, d, f), d, w), ("w_up", (L, e, d, f), d, w),
        ("w_down", (L, e, f, d), f, w), ("final_norm", (1, d), 0, f32),
    ]
    ws = dict(zip([s[0] for s in spec],
                  _draw(rng_key(seed), tuple(s[1:] for s in spec))))
    return {
        "embed": ws["embed"],
        "lm_head": ws["lm_head"],
        "groups": {"b0": {
            "norm1": {"w": ws["norm1"]},
            "attn": {k: ws[k] for k in ("wq", "wk", "wv", "wo",
                                        "q_norm", "k_norm")},
            "norm2": {"w": ws["norm2"]},
            "mlp": {k: ws[k] for k in ("router", "w_gate", "w_up", "w_down")},
        }},
        "final_norm": {"w": ws["final_norm"]},
    }
