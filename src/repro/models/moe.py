"""Mixture-of-Experts layer: top-k router + capacity-based scatter dispatch.

``cfg.moe_capacity`` is a capacity factor (assignments past it are
dropped), or None for dropless routing: every expert's buffer then holds a
whole dispatch block, E times the block's tokens in rows, k/E of them live
(docs/moe.md). ``cfg.moe_norm_topk`` renormalises the top-k weights to sum
to 1 (Granite) or keeps the softmax probabilities (OLMoE). The layer's
parts run under ``jax.named_scope``s ``route``, ``dispatch``, ``experts``
and ``combine`` inside ``moe``.

Dispatch is scatter/gather (not GShard one-hot einsum): a (T, E, C) one-hot
dispatch tensor is O(T^2)-ish at LM scale, while the scatter form moves
exactly T*k rows.

Two dispatch layouts (cfg.moe_shard_dispatch — §Perf hillclimb #1):

* ``False`` — *global* capacity buffers (E, C, D). Faithful to GShard
  semantics, but the buffer is unshardable when E doesn't divide the model
  axis and the combine-gather crosses shards: GSPMD replicates ~E*C*D bytes
  per layer (granite: 16 GB of all-gather per layer — the recorded baseline).
* ``True``  — *block-local* dispatch: tokens are grouped into ``data``-aligned
  blocks; each block routes into its own (E, C/nb) slice. Every dispatch
  gather/scatter is then shard-local; only the expert weights (TP) or the
  expert dim (EP) move across devices. Per-block capacity is the standard
  local-capacity relaxation of GShard.

Expert GEMMs under an ``ApproxConfig`` run as ONE grouped ragged fused
LUT-GEMM per projection (``approx_grouped_dense`` — docs/moe.md): all
``nb * E`` capacity buffers walk a single ``pallas_call`` whose groupinfo
lets it skip row-blocks past each group's live token count, instead of
launching E (or nb*E) separate kernels that all run ``cap`` rows. QAT
(``fake_quant_only``) keeps the per-expert vmapped path — fake-quant has no
LUT kernel to fuse.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.approx_ops import ApproxConfig, approx_dense, approx_grouped_dense
from repro.parallel.sharding import current_mesh_context, shard

Array = jnp.ndarray


def _route(xf: Array, router: Array, k: int, norm_topk: bool = True):
    """Router products: full softmax probs (T, E) plus top-k weights/indices
    (T, k), the weights renormalized to sum to 1 when ``norm_topk`` (else
    the softmax probabilities themselves). One softmax serves both dispatch
    and the load-balancing aux loss (``moe_block`` stats) — callers reuse
    these instead of re-running the router."""
    gate_logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)
    probs = jax.nn.softmax(gate_logits, axis=-1)               # (T, E)
    top_p, top_e = jax.lax.top_k(probs, k)                     # (T, k)
    if norm_topk:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    return probs, top_p, top_e


def _aux_loss(probs: Array, top_e: Array, n_experts: int) -> Array:
    """Switch-style load-balancing loss from routing products already in
    hand: E * sum(frac_tokens_per_expert * mean_router_prob_per_expert)."""
    frac_tokens = jax.nn.one_hot(top_e, n_experts).mean(
        axis=tuple(range(top_e.ndim)))
    frac_probs = probs.reshape(-1, n_experts).mean(0)
    return n_experts * jnp.sum(frac_tokens * frac_probs)


def _expert_ffn(xe: Array, p: dict, cfg, acfg, block_axes,
                counts: Optional[Array] = None):
    """xe: (..., E, C, D) -> (..., E, C, D) through the gated expert FFN.

    ``counts`` (matching xe's leading block/expert dims) gives the live row
    count of each capacity buffer; with an approx config the three
    projections run as grouped ragged fused LUT-GEMMs that skip row-blocks
    past the counts. Rows at or beyond a buffer's count come back exactly
    0.0 from the grouped path (dead-row contract, see docs/moe.md).
    """
    if acfg is None:
        gate = jnp.einsum("...ecd,edf->...ecf", xe, p["w_gate"])
        up = jnp.einsum("...ecd,edf->...ecf", xe, p["w_up"])
        h = jax.nn.silu(gate) * up
        h = shard(h, *block_axes, "experts", None, "expert_mlp")
        return jnp.einsum("...ecf,efd->...ecd", h, p["w_down"])

    if not acfg.fake_quant_only:
        # grouped ragged fused LUT-GEMM: one kernel per projection over all
        # nb*E capacity buffers, ragged-skipping past each live count
        lead = xe.shape[:-3]
        e_dim, cap, d = xe.shape[-3:]
        xg = xe.reshape(-1, cap, d)                      # (G, C, D)
        g = xg.shape[0]
        if counts is None:
            cnt = jnp.full((g,), cap, jnp.int32)
        else:
            cnt = jnp.asarray(counts, jnp.int32).reshape(g)
        gate = approx_grouped_dense(xg, p["w_gate"], acfg, cnt)
        up = approx_grouped_dense(xg, p["w_up"], acfg, cnt)
        h = jax.nn.silu(gate) * up
        y = approx_grouped_dense(h, p["w_down"], acfg, cnt)
        return y.reshape(*lead, e_dim, cap, d)

    def one(xe_e, wg, wu, wd):
        h = jax.nn.silu(approx_dense(xe_e, wg, None, acfg)) * \
            approx_dense(xe_e, wu, None, acfg)
        return approx_dense(h, wd, None, acfg)

    fn = jax.vmap(one, in_axes=(0, 0, 0, 0))
    if xe.ndim == 4:  # leading block dim
        fn = jax.vmap(fn, in_axes=(0, None, None, None))
    return fn(xe, p["w_gate"], p["w_up"], p["w_down"])


def _dispatch_blocks(cfg, t: int) -> int:
    """Number of data-aligned dispatch blocks (1 disables block-locality)."""
    if not cfg.moe_shard_dispatch:
        return 1
    ctx = current_mesh_context()
    nb = 1
    if ctx is not None:
        for a in ("pod", "data"):
            if a in ctx.mesh.axis_names:
                nb *= ctx.mesh.shape[a]
    else:
        nb = 16  # planner default when traced without a mesh (tests)
    while t % nb != 0 or nb > t:
        nb //= 2
    return max(nb, 1)


def _capacity(cfg, tb: int) -> int:
    """Rows of each expert's buffer in a block of ``tb`` tokens. Dropless
    (``moe_capacity`` None) holds the whole block: top-k picks distinct
    experts, so no expert gets more than ``tb`` assignments."""
    if cfg.moe_capacity is None:
        return tb
    return int(max(1, round(tb * cfg.moe_top_k / cfg.n_experts
                            * cfg.moe_capacity)))


def dispatch_geometry(cfg, t: int) -> dict:
    """Static dispatch geometry for ``t`` tokens under the active mesh
    context: resolved block count (after the divisibility fallback), tokens
    per block, and the per-block capacity. Pure shape arithmetic — safe to
    call at trace/lowering time (the dry-run surfaces it per MoE cell)."""
    e, k = cfg.n_experts, cfg.moe_top_k
    nb = _dispatch_blocks(cfg, t)
    tb = t // nb
    return {"n_blocks": nb, "tokens_per_block": tb,
            "capacity": _capacity(cfg, tb), "n_experts": e, "top_k": k,
            "capacity_factor": cfg.moe_capacity,
            "dropless": cfg.moe_capacity is None}


@jax.named_scope("moe")
def moe_block(x: Array, p: dict, cfg, acfg: Optional[ApproxConfig],
              *, return_stats: bool = False):
    """x: (B, S, D) -> (B, S, D), or ``(out, stats)`` with
    ``return_stats=True``.

    p: router (D, E); w_gate/w_up (E, D, F); w_down (E, F, D).

    stats (all computed from products the block already has in hand):
      ``aux_loss``      Switch-style load-balancing loss (reuses the routing
                        softmax — bitwise-identical to ``router_aux_loss``).
      ``dropped_frac``  fraction of the T*k routed assignments dropped by
                        the capacity limit (f32 scalar).
    """
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.moe_top_k
    t = b * s
    xf = x.reshape(t, d)
    with jax.named_scope("route"):
        probs, top_p, top_e = _route(xf, p["router"], k, cfg.moe_norm_topk)

    nb = _dispatch_blocks(cfg, t)
    tb = t // nb                 # tokens per block
    cap = _capacity(cfg, tb)

    with jax.named_scope("dispatch"):
        # ---- block-local slot assignment -------------------------------
        flat_e = top_e.reshape(nb, tb * k)                     # (nb, TBk)
        onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)    # (nb, TBk, E)
        onehot = shard(onehot, "expert_blocks", None, None)
        pos_in_e = jnp.cumsum(onehot, axis=1) - 1              # within block
        slot = jnp.take_along_axis(pos_in_e, flat_e[..., None], axis=2)[..., 0]
        keep = slot < cap                                      # (nb, TBk)
        dest = jnp.where(keep, flat_e * cap + slot, e * cap)   # (nb, TBk)

        # live rows per capacity buffer: slots 0..count-1 are occupied
        # (cumsum order packs kept tokens densely) — the grouped GEMM's
        # groupinfo
        counts = jnp.minimum(onehot.sum(axis=1), cap)          # (nb, E)

        # scatter token indices into per-block buffers (trash slot at end)
        tok_in_block = jnp.arange(tb * k, dtype=jnp.int32) // k
        idx_buf = jnp.zeros((nb, e * cap + 1), jnp.int32)
        idx_buf = idx_buf.at[jnp.arange(nb)[:, None], dest].set(
            tok_in_block[None] + 1)
        idx_buf = idx_buf[:, :-1]                              # (nb, E*cap)

        # gather rows (block-local): xfb (nb, TB, D) -> xe (nb, E, cap, D)
        xfb = xf.reshape(nb, tb, d)
        xfb = shard(xfb, "expert_blocks", None, None)
        xe = jnp.take_along_axis(
            xfb, jnp.maximum(idx_buf - 1, 0)[..., None], axis=1)
        xe = xe * (idx_buf > 0)[..., None].astype(x.dtype)
        xe = xe.reshape(nb, e, cap, d)
        xe = shard(xe, "expert_blocks", "experts", None, None)

    with jax.named_scope("experts"):
        ye = _expert_ffn(xe, p, cfg, acfg, ("expert_blocks",), counts=counts)
        ye = shard(ye, "expert_blocks", "experts", None, None)

    with jax.named_scope("combine"):
        # block-local gather + routed weights
        yeb = ye.reshape(nb, e * cap, d)
        src = jnp.where(keep, flat_e * cap + slot, 0)          # (nb, TBk)
        yk = jnp.take_along_axis(yeb, src[..., None], axis=1)  # (nb, TBk, D)
        yk = jnp.where(keep[..., None], yk, 0.0).reshape(t, k, d)
        out = (yk * top_p[:, :, None].astype(yk.dtype)).sum(axis=1)
    out = out.reshape(b, s, d)
    if not return_stats:
        return out
    stats = {
        "aux_loss": _aux_loss(probs, top_e, e),
        "dropped_frac": 1.0 - keep.mean(dtype=jnp.float32),
    }
    return out, stats


def router_aux_loss(x: Array, router: Array, n_experts: int, top_k: int) -> Array:
    """Switch-style load-balancing auxiliary loss (standalone API — shares
    ``_route``/``_aux_loss`` with ``moe_block``'s stats)."""
    xf = x.reshape(x.shape[0] * x.shape[1], -1)
    probs, _, top_e = _route(xf, router, top_k)
    return _aux_loss(probs, top_e, n_experts)
