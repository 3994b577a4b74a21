"""Abstract input specs + jit-able step functions for every (arch x shape).

``input_specs`` returns ShapeDtypeStruct stand-ins (weak-type-correct,
shardable, zero allocation); ``build_step`` returns the function to lower
plus matching in_shardings — the dry-run and the roofline extractor both
consume these.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ModelConfig
from repro.configs.shapes import ShapeSpec
from repro.models import transformer as T
from repro.models import whisper as W
from repro.optim.adamw import AdamW, cosine_schedule
from repro.parallel import planner
from repro.parallel.sharding import use_mesh


def make_acfg(acu_spec, *, approx_bwd: bool = False):
    """'mult:mode[:rank]' -> ApproxConfig (e.g. mul8s_1L2H:lut,
    mul8s_trunc2:factored, mul8s_1L2H:lowrank:8).

    The ACU takes the Pallas kernel routes; LUT mode runs the fused
    quantize -> LUT-GEMM -> dequant kernel, and with ``approx_bwd`` the STE
    backward GEMMs run the fused backward kernel too."""
    if not acu_spec:
        return None
    from repro.core.acu import AcuMode, make_acu
    from repro.core.approx_ops import ApproxConfig
    parts = acu_spec.split(":")
    name = parts[0]
    mode = AcuMode(parts[1] if len(parts) > 1 else "lut")
    rank = int(parts[2]) if len(parts) > 2 else 8
    acu = make_acu(name, mode, rank=rank, use_pallas=True,
                   fused=mode == AcuMode.LUT)
    return ApproxConfig(acu=acu, approx_bwd=approx_bwd)


@dataclasses.dataclass
class StepBundle:
    fn: Callable                 # jit-able step
    args: tuple                  # ShapeDtypeStructs
    in_shardings: tuple
    out_shardings: Any
    donate_argnums: tuple
    meta: dict


def _sds(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def abstract_params(cfg: ModelConfig):
    init = W.init_params if cfg.enc_dec else T.init_params
    return jax.eval_shape(lambda k: init(k, cfg), jax.random.PRNGKey(0))


def pick_microbatches(cfg: ModelConfig, global_batch: int, seq: int,
                      mesh) -> int:
    """Gradient-accumulation factor: keep per-microbatch saved activations
    (scan carries + attention temps) within ~4 GiB/device. Statically
    unrolled (Python loop), so cost_analysis sees every microbatch."""
    shards = 1
    for a in ("pod", "data"):
        if a in mesh.axis_names and global_batch % (shards * mesh.shape[a]) == 0:
            shards *= mesh.shape[a]
    b_local = max(global_batch // shards, 1)
    # saved carry per group per microbatch-row: S x d x 2 bytes
    bytes_full = b_local * seq * cfg.d_model * 2 * max(cfg.n_groups, 1)
    n_micro = 1
    while n_micro < b_local and bytes_full / n_micro > 4e9:
        n_micro *= 2
    while b_local % n_micro != 0:
        n_micro //= 2
    return max(n_micro, 1)


def make_optimizer(cfg: ModelConfig) -> AdamW:
    return AdamW(lr=cosine_schedule(3e-4, 200, 10000), weight_decay=0.01,
                 clip_norm=1.0)


def build_step(cfg: ModelConfig, shape: ShapeSpec, mesh, *,
               acfg=None) -> StepBundle:
    """Construct (fn, abstract args, shardings) for one cell."""
    b, s = shape.global_batch, shape.seq_len
    params = abstract_params(cfg)
    pplan = planner.param_specs(cfg, params, mesh,
                                mode="train" if shape.kind == "train" else "serve")
    pshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), pplan.specs,
                          is_leaf=lambda x: isinstance(x, P))
    tok_spec = planner.batch_spec(mesh, b, extra_dims=1)
    tok_shard = NamedSharding(mesh, tok_spec)
    meta = {"plan_report": pplan.report, "kind": shape.kind}
    if cfg.n_experts:
        # static MoE dispatch geometry under this mesh (resolved block
        # count, per-block capacity) — the dry-run surfaces it per cell
        from repro.models.moe import dispatch_geometry
        with use_mesh(mesh):
            meta["moe_dispatch"] = dispatch_geometry(
                cfg, b * (1 if shape.kind == "decode" else s))

    if shape.kind == "train":
        opt = make_optimizer(cfg)
        opt_state = jax.eval_shape(opt.init, params)
        ospecs = planner.opt_state_specs(pplan, opt_state)
        oshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), ospecs,
                              is_leaf=lambda x: isinstance(x, P))
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)

        if cfg.enc_dec:
            frames = jax.ShapeDtypeStruct((b, cfg.enc_ctx, cfg.d_model),
                                          cfg.param_dtype)
            fr_shard = NamedSharding(mesh, planner.batch_spec(mesh, b, extra_dims=2))

            def train_step(params, opt_state, frames, tokens, labels):
                with use_mesh(mesh):
                    loss, grads = jax.value_and_grad(W.loss_fn)(
                        params, frames, tokens, labels, cfg, acfg)
                    new_params, new_state = opt.update(grads, opt_state, params)
                return new_params, new_state, loss

            return StepBundle(
                fn=train_step, args=(params, opt_state, frames, toks, toks),
                in_shardings=(pshard, oshard, fr_shard, tok_shard, tok_shard),
                out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
                donate_argnums=(0, 1), meta=meta)

        n_micro = pick_microbatches(cfg, b, s, mesh)
        meta["n_microbatches"] = n_micro

        def train_step(params, opt_state, tokens, labels):
            with use_mesh(mesh):
                if n_micro == 1:
                    loss, grads = jax.value_and_grad(T.loss_fn)(
                        params, tokens, labels, cfg, acfg)
                else:
                    # statically-unrolled gradient accumulation: every
                    # microbatch appears in the HLO (roofline-correct) and
                    # the backward working set shrinks by n_micro
                    mb = b // n_micro
                    loss = 0.0
                    grads = jax.tree.map(
                        lambda p: jnp.zeros(p.shape, jnp.float32), params)
                    for i in range(n_micro):
                        tk = jax.lax.dynamic_slice_in_dim(tokens, i * mb, mb)
                        lb = jax.lax.dynamic_slice_in_dim(labels, i * mb, mb)
                        li, gi = jax.value_and_grad(T.loss_fn)(
                            params, tk, lb, cfg, acfg)
                        loss = loss + li / n_micro
                        grads = jax.tree.map(
                            lambda a, g: a + g.astype(jnp.float32) / n_micro,
                            grads, gi)
                new_params, new_state = opt.update(grads, opt_state, params)
            return new_params, new_state, loss

        return StepBundle(
            fn=train_step, args=(params, opt_state, toks, toks),
            in_shardings=(pshard, oshard, tok_shard, tok_shard),
            out_shardings=(pshard, oshard, NamedSharding(mesh, P())),
            donate_argnums=(0, 1), meta=meta)

    # ---- serving shapes ---------------------------------------------------
    long_ctx = shape.name.startswith("long")
    if shape.kind == "prefill":
        toks = jax.ShapeDtypeStruct((b, s), jnp.int32)
        cache = jax.eval_shape(
            lambda: (W.init_cache if cfg.enc_dec else T.init_cache)(cfg, b, s))
        cplan = planner.cache_specs(cfg, cache, mesh, global_batch=b,
                                    long_context=long_ctx)
        cshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), cplan.specs,
                              is_leaf=lambda x: isinstance(x, P))
        meta["cache_report"] = cplan.report

        if cfg.enc_dec:
            frames = jax.ShapeDtypeStruct((b, cfg.enc_ctx, cfg.d_model),
                                          cfg.param_dtype)
            fr_shard = NamedSharding(mesh, planner.batch_spec(mesh, b, extra_dims=2))

            def prefill_step(params, cache, frames, tokens):
                with use_mesh(mesh):
                    enc = W.encode(params, frames, cfg, acfg)
                    logits, cache = W.decode(params, tokens, enc, cfg,
                                             acfg=acfg, cache=cache,
                                             cache_pos=0, last_only=True)
                return logits[:, -1], cache

            return StepBundle(
                fn=prefill_step, args=(params, cache, frames, toks),
                in_shardings=(pshard, cshard, fr_shard, tok_shard),
                out_shardings=(NamedSharding(mesh, planner.batch_spec(mesh, b)),
                               cshard),
                donate_argnums=(1,), meta=meta)

        def prefill_step(params, cache, tokens):
            with use_mesh(mesh):
                logits, cache = T.apply_model(params, tokens, cfg, acfg=acfg,
                                              cache=cache, cache_pos=0,
                                              last_only=True)
            return logits[:, -1], cache

        return StepBundle(
            fn=prefill_step, args=(params, cache, toks),
            in_shardings=(pshard, cshard, tok_shard),
            out_shardings=(NamedSharding(mesh, planner.batch_spec(mesh, b)), cshard),
            donate_argnums=(1,), meta=meta)

    # decode: one new token against a seq_len-deep cache
    toks = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    cache = jax.eval_shape(
        lambda: (W.init_cache if cfg.enc_dec else T.init_cache)(cfg, b, s))
    cplan = planner.cache_specs(cfg, cache, mesh, global_batch=b,
                                long_context=long_ctx)
    cshard = jax.tree.map(lambda sp: NamedSharding(mesh, sp), cplan.specs,
                          is_leaf=lambda x: isinstance(x, P))
    meta["cache_report"] = cplan.report
    rep = NamedSharding(mesh, P())

    if cfg.enc_dec:
        enc_out = jax.ShapeDtypeStruct((b, cfg.enc_ctx, cfg.d_model),
                                       cfg.param_dtype)
        enc_shard = NamedSharding(mesh, planner.batch_spec(mesh, b, extra_dims=2))

        def decode_step(params, cache, enc_out, tokens, pos):
            with use_mesh(mesh):
                logits, cache = W.decode(params, tokens, enc_out, cfg,
                                         acfg=acfg, cache=cache, cache_pos=pos)
            return logits[:, -1], cache

        return StepBundle(
            fn=decode_step, args=(params, cache, enc_out, toks, pos),
            in_shardings=(pshard, cshard, enc_shard, tok_shard, rep),
            out_shardings=(NamedSharding(mesh, planner.batch_spec(mesh, b)), cshard),
            donate_argnums=(1,), meta=meta)

    def decode_step(params, cache, tokens, pos):
        with use_mesh(mesh):
            logits, cache = T.apply_model(params, tokens, cfg, acfg=acfg,
                                          cache=cache, cache_pos=pos, decode=True)
        return logits[:, -1], cache

    return StepBundle(
        fn=decode_step, args=(params, cache, toks, pos),
        in_shardings=(pshard, cshard, tok_shard, rep),
        out_shardings=(NamedSharding(mesh, planner.batch_spec(mesh, b)), cshard),
        donate_argnums=(1,), meta=meta)
