"""Grouped ragged fused LUT-GEMM: kernel edge cases, plan routes, MoE wiring.

The bit-exactness oracle everywhere is the per-expert composition — either
``fused_lut_dense`` per group (kernel level) or ``approx_dense`` per expert
(approx level) — with the SAME pinned shared activation scale and the SAME
multiply-form (inline) weight scales the grouped path uses, masked to each
group's live rows. "Equal" is ``jnp.array_equal``, not allclose.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core import build_lut, get_multiplier, make_acu
from repro.core.acu import AcuMode, GroupedSpec, grouped_plan
from repro.core.approx_ops import ApproxConfig, approx_dense, approx_grouped_dense
from repro.core.multipliers import make_exact
from repro.core.quantization import (QParams, acu_operand,
                                     inline_symmetric_scale, quantize,
                                     symmetric_qparams)
from repro.kernels.fused_lut_dense.ops import fused_lut_dense
from repro.kernels.fused_lut_grouped.ops import fused_lut_grouped
from repro.models.moe import dispatch_geometry, moe_block, router_aux_loss
from repro.models.transformer import _init_moe

LUT = jnp.asarray(build_lut(get_multiplier("mul8s_1L2H")))
# biased multiplier: M[0, 0] = 7, so an all-zero row still accumulates
# K * LUT[0, w] != 0 — masking dead rows is observably different from
# never computing them
BIASED = dataclasses.replace(
    make_exact(8), name="mul8s_biased",
    fn=lambda a, w: a.astype(jnp.int32) * w.astype(jnp.int32) + 7)
BLUT = jnp.asarray(build_lut(BIASED))

ACU = make_acu("mul8s_1L2H", AcuMode.LUT, use_pallas=True, fused=True)
CFG_A = ApproxConfig(acu=ACU)
KEY = jax.random.PRNGKey(0)


def _grouped_operands(G, E, C, K, N, seed=0, counts=None):
    """Random operands with dispatch-style dead rows zeroed past counts."""
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(G, C, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(E, K, N)), jnp.float32)
    if counts is None:
        counts = rng.integers(0, C + 1, size=(G,))
    counts = jnp.asarray(counts, jnp.int32)
    mask = jnp.arange(C)[None, :] < counts[:, None]
    x = x * mask[..., None]
    return x, w, counts, mask


def _quantized(x, w):
    """Pinned shared activation qparams + per-expert weight codes/scales."""
    E = w.shape[0]
    xqp = symmetric_qparams(jnp.maximum(jnp.max(jnp.abs(x)), 1e-6), 8)
    qps = [symmetric_qparams(
        jnp.maximum(jnp.max(jnp.abs(w[e]), axis=0), 1e-9), 8, axis=1)
        for e in range(E)]
    wq = jnp.stack([acu_operand(quantize(w[e], qps[e]), qps[e])
                    for e in range(E)])
    ws = jnp.stack([qp.scale for qp in qps])
    return xqp, wq, ws


def _kernel_oracle(x, wq, lut, xqp, ws, mask):
    """Per-group fused_lut_dense with the shared scale, dead rows zeroed."""
    G, E = x.shape[0], wq.shape[0]
    refs = []
    for g in range(G):
        r = fused_lut_dense(x[g], wq[g % E], lut, 128, xqp.scale,
                            xqp.zero_point, ws[g % E], bits=8, interpret=True)
        refs.append(jnp.where(mask[g][:, None], r, 0.0))
    return jnp.stack(refs)


# ---------------------------------------------------------------------------
# kernel-level ragged edge cases
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("case", [
    # (G, E, C, K, N, biased, counts)
    (4, 4, 24, 33, 14, False, None),            # one block, ragged counts
    (8, 4, 24, 33, 14, False, None),            # nb=2 dispatch blocks
    (4, 4, 24, 33, 14, True, None),             # biased M00 + dead rows
    (4, 4, 24, 600, 14, False, None),           # K > 512 -> k-tiled grid
    (6, 3, 16, 40, 9, True, [0, 16, 3, 0, 16, 5]),  # empty experts
    (4, 4, 32, 40, 9, False, [32, 0, 0, 0]),    # all tokens to one expert
], ids=["ragged", "blocks", "biased_m00", "ktile", "empty_experts",
        "all_to_one"])
def test_grouped_kernel_bitwise_vs_per_expert(case):
    G, E, C, K, N, biased, counts = case
    lut = BLUT if biased else LUT
    x, w, counts, mask = _grouped_operands(G, E, C, K, N,
                                           seed=sum((G, C, K, N)),
                                           counts=counts)
    xqp, wq, ws = _quantized(x, w)
    out = fused_lut_grouped(x, wq, lut, 128, xqp.scale, xqp.zero_point, ws,
                            counts, bits=8, interpret=True)
    ref = _kernel_oracle(x, wq, lut, xqp, ws, mask)
    assert jnp.array_equal(out, ref)


def test_grouped_kernel_biased_dead_rows_exact_zero():
    """Rows past a group's count are never accumulated, not masked after
    the fact: under the biased multiplier a computed-then-masked zero row
    would carry sum(LUT[0, w]) != 0 before the mask, and the int32
    accumulator (emit_acc) shows the row really is zero in integer space."""
    x, w, counts, mask = _grouped_operands(4, 2, 16, 40, 9, seed=3,
                                           counts=[3, 16, 0, 7])
    xqp, wq, ws = _quantized(x, w)
    acc = fused_lut_grouped(x, wq, BLUT, 128, xqp.scale, xqp.zero_point, ws,
                            counts, bits=8, interpret=True, emit_acc=True)
    assert acc.dtype == jnp.int32
    assert bool(jnp.all(jnp.where(mask[..., None], 0, acc) == 0))
    # and the fused dequant output equals the one combined-scale multiply
    out = fused_lut_grouped(x, wq, BLUT, 128, xqp.scale, xqp.zero_point, ws,
                            counts, bits=8, interpret=True)
    dq = acc.astype(jnp.float32) * (xqp.scale * ws[:, None, :])[
        jnp.arange(4) % 2]
    assert jnp.array_equal(out, jnp.where(mask[..., None], dq, 0.0))


def test_grouped_kernel_jit_parity():
    x, w, counts, _ = _grouped_operands(4, 4, 24, 33, 14, seed=11)
    xqp, wq, ws = _quantized(x, w)

    def f(x, wq, ws, counts):
        return fused_lut_grouped(x, wq, LUT, 128, xqp.scale, xqp.zero_point,
                                 ws, counts, bits=8, interpret=True)

    assert jnp.array_equal(f(x, wq, ws, counts),
                           jax.jit(f)(x, wq, ws, counts))


# ---------------------------------------------------------------------------
# approx_grouped_dense: routes, oracle, STE
# ---------------------------------------------------------------------------

def _approx_operands(nb=2, E=4, C=24, K=33, N=14, seed=0):
    return _grouped_operands(nb * E, E, C, K, N, seed=seed)


def test_approx_grouped_routes_bitwise():
    """Fused grouped == pinned vmap fallback == per-expert approx_dense
    driven with the same pinned shared xqp + inline per-expert wqp."""
    x, w, counts, mask = _approx_operands()
    y_f = approx_grouped_dense(x, w, CFG_A, counts)
    y_v = approx_grouped_dense(x, w, CFG_A, counts, route="vmap")
    assert jnp.array_equal(y_f, y_v)

    E, N = w.shape[0], w.shape[2]
    xqp = QParams(scale=inline_symmetric_scale(
        jnp.maximum(jnp.max(jnp.abs(x)), 1e-6), 8),
        zero_point=jnp.zeros((), jnp.float32), bits=8)
    wscale = inline_symmetric_scale(
        jnp.maximum(jnp.max(jnp.abs(w), axis=1), 1e-9), 8)
    refs = []
    for g in range(x.shape[0]):
        e = g % E
        wqp = QParams(scale=wscale[e], zero_point=jnp.zeros((), jnp.float32),
                      bits=8, axis=1)
        r = approx_dense(x[g], w[e], None, CFG_A, xqp=xqp, wqp=wqp)
        refs.append(jnp.where(mask[g][:, None], r, 0.0))
    assert jnp.array_equal(y_f, jnp.stack(refs))


def test_approx_grouped_jit_eager_bitwise():
    """Default qparams are computed in multiply (inline) form, so the jitted
    layer equals the eager one bitwise — no reciprocal-multiply scale
    drift."""
    x, w, counts, _ = _approx_operands(seed=5)
    y = approx_grouped_dense(x, w, CFG_A, counts)
    y_j = jax.jit(lambda x, w, c: approx_grouped_dense(x, w, CFG_A, c))(
        x, w, counts)
    assert jnp.array_equal(y, y_j)


def test_approx_grouped_ste_grads():
    """STE grads agree between routes; dead rows carry no gradient."""
    x, w, counts, mask = _approx_operands(seed=7)
    N = w.shape[2]

    def loss(route):
        return lambda x, w: (approx_grouped_dense(
            x, w, CFG_A, counts, route=route) * jnp.arange(N)).sum()

    gfx, gfw = jax.grad(loss(None), argnums=(0, 1))(x, w)
    gvx, gvw = jax.grad(loss("vmap"), argnums=(0, 1))(x, w)
    assert jnp.array_equal(gfx, gvx) and jnp.array_equal(gfw, gvw)
    assert bool(jnp.all(jnp.isfinite(gfx)))
    assert float(jnp.abs(gfw).sum()) > 0
    assert bool(jnp.all(jnp.where(mask[..., None], 0.0, gfx) == 0))


def test_approx_grouped_fallback_and_pin():
    """Non-fusable ACU silently falls back (audited), a pinned route
    raises, and describe() reports the resolved geometry."""
    x, w, counts, _ = _approx_operands(seed=9)
    acu_np = make_acu("mul8s_1L2H", AcuMode.LUT)     # no pallas -> no fuse
    y_np = approx_grouped_dense(x, w, ApproxConfig(acu=acu_np), counts)
    assert jnp.array_equal(y_np, approx_grouped_dense(x, w, CFG_A, counts))

    spec = GroupedSpec(n_experts=4, cap=24, d_in=33, d_out=14, n_blocks=2)
    plan = grouped_plan(ACU, spec)
    d = plan.describe()
    assert d["route"] == "fused_grouped"
    assert (d["experts"], d["cap"], d["n_blocks"]) == (4, 24, 2)
    fb = grouped_plan(acu_np, spec)
    assert fb.route == "vmap" and fb.report
    with pytest.raises(ValueError, match="fused_grouped route unavailable"):
        grouped_plan(acu_np, spec, route="fused_grouped")


def test_approx_grouped_rejects_fake_quant_only():
    x, w, counts, _ = _approx_operands(seed=1)
    cfg = ApproxConfig(acu=ACU, fake_quant_only=True)
    with pytest.raises(ValueError, match="fake-quant"):
        approx_grouped_dense(x, w, cfg, counts)


# ---------------------------------------------------------------------------
# MoE layer wiring
# ---------------------------------------------------------------------------

CFG_MOE = ModelConfig(name="t", family="moe", n_layers=1, d_model=32,
                      n_heads=2, n_kv_heads=2, d_ff=16, vocab_size=64,
                      pattern=("attn_moe",), n_experts=4, moe_top_k=2,
                      moe_capacity=8.0, dtype="float32")


def _moe_params(cfg):
    return jax.tree.map(lambda a: a[0], _init_moe(KEY, cfg, 1))


def test_moe_block_grouped_vs_exact_lut():
    """With an exact-multiplier LUT ACU the grouped approx MoE matches the
    float MoE within quantization error — the full dispatch -> grouped
    GEMM -> combine path is wired correctly."""
    p = _moe_params(CFG_MOE)
    x = jax.random.normal(KEY, (2, 8, 32)) * 0.1
    acfg = ApproxConfig(
        acu=make_acu("mul8s_exact", AcuMode.LUT, use_pallas=True, fused=True))
    out = moe_block(x, p, CFG_MOE, acfg)
    ref = moe_block(x, p, CFG_MOE, None)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.1, atol=0.05)


def test_moe_block_grouped_grads():
    p = _moe_params(CFG_MOE)
    x = jax.random.normal(KEY, (2, 8, 32))

    def loss(p):
        return (moe_block(x, p, CFG_MOE, CFG_A) ** 2).sum()

    g = jax.grad(loss)(p)
    assert all(bool(jnp.isfinite(v).all()) for v in jax.tree.leaves(g))
    assert float(jnp.abs(g["w_gate"]).max()) > 0


def test_moe_nonpow2_tokens_block_fallback():
    """t=24 does not divide the default 16 dispatch blocks: the pow-2
    fallback resolves nb=8, the geometry helper reports it, and the plan's
    describe() carries the resolved block count end to end."""
    geo = dispatch_geometry(CFG_MOE, 24)
    assert geo["n_blocks"] == 8 and geo["tokens_per_block"] == 3
    spec = GroupedSpec(n_experts=CFG_MOE.n_experts, cap=geo["capacity"],
                       d_in=32, d_out=16, n_blocks=geo["n_blocks"])
    assert grouped_plan(ACU, spec).describe()["n_blocks"] == 8
    # and the layer actually runs at that shape through the grouped path
    p = _moe_params(CFG_MOE)
    x = jax.random.normal(KEY, (2, 12, 32))        # t = 24
    out = moe_block(x, p, CFG_MOE, CFG_A)
    assert out.shape == x.shape and bool(jnp.isfinite(out).all())


def test_aux_loss_reuses_routing_bitwise():
    """moe_block's aux_loss stat == the standalone router_aux_loss == the
    pre-refactor standalone formula, bitwise."""
    p = _moe_params(CFG_MOE)
    x = jax.random.normal(KEY, (2, 8, 32))
    _, stats = moe_block(x, p, CFG_MOE, None, return_stats=True)

    # the old standalone implementation, verbatim
    t = x.shape[0] * x.shape[1]
    logits = x.reshape(t, -1).astype(jnp.float32) @ \
        p["router"].astype(jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    _, top_e = jax.lax.top_k(probs, CFG_MOE.moe_top_k)
    frac_tokens = jax.nn.one_hot(top_e, CFG_MOE.n_experts).mean(axis=(0, 1))
    old = CFG_MOE.n_experts * jnp.sum(frac_tokens * probs.mean(0))

    new = router_aux_loss(x, p["router"], CFG_MOE.n_experts,
                          CFG_MOE.moe_top_k)
    assert jnp.array_equal(new, old)
    assert jnp.array_equal(stats["aux_loss"], old)


def test_dropped_frac_pinned_at_low_capacity():
    """moe_capacity=0.25 forces drops; dropped_frac matches an independent
    first-come-first-served replay of the routing decisions."""
    cfg = dataclasses.replace(CFG_MOE, moe_capacity=0.25)
    p = _moe_params(cfg)
    x = jax.random.normal(KEY, (2, 12, 32))    # t=24 -> nb=8, 3 tokens/block
    out, stats = moe_block(x, p, cfg, None, return_stats=True)
    assert bool(jnp.isfinite(out).all())

    # independent replay: greedy in-order slot grab per (block, expert)
    b, s, d = x.shape
    t = b * s
    geo = dispatch_geometry(cfg, t)
    nb, tb, cap = geo["n_blocks"], geo["tokens_per_block"], geo["capacity"]
    xf = np.asarray(x.reshape(t, d), np.float32)
    logits = xf @ np.asarray(p["router"], np.float32)
    top_e = np.asarray(jax.lax.top_k(jnp.asarray(logits),
                                     cfg.moe_top_k)[1])
    flat = top_e.reshape(nb, tb * cfg.moe_top_k)
    dropped = 0
    for blk in range(nb):
        used = np.zeros(cfg.n_experts, np.int64)
        for e in flat[blk]:
            if used[e] >= cap:
                dropped += 1
            used[e] += 1
    expect = dropped / (t * cfg.moe_top_k)
    assert dropped > 0                      # the capacity really binds
    assert float(stats["dropped_frac"]) == pytest.approx(expect, abs=1e-7)


def test_dropped_frac_zero_with_ample_capacity():
    p = _moe_params(CFG_MOE)                # moe_capacity = 8.0
    x = jax.random.normal(KEY, (2, 8, 32))
    _, stats = moe_block(x, p, CFG_MOE, None, return_stats=True)
    assert float(stats["dropped_frac"]) == 0.0


# ---------------------------------------------------------------------------
# partition resolver (no real mesh needed — planner unit tests)
# ---------------------------------------------------------------------------

class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)

    @property
    def size(self):
        return int(np.prod(list(self.shape.values())))


def _ctx(shape, rules=None):
    from repro.parallel.sharding import DEFAULT_RULES, MeshContext
    return MeshContext(mesh=FakeMesh(shape),
                       rules=dict(DEFAULT_RULES, **(rules or {})))


def test_grouped_partition_defaults():
    from repro.parallel import planner
    part, report = planner.acu_grouped_partition(
        _ctx({"data": 2, "model": 4}), n_experts=40, n_blocks=2)
    assert (part.rows, part.cols, part.k) == (("data",), ("model",), ())
    assert (part.n_rows, part.n_cols, part.n_k) == (2, 4, 1)
    assert not report


def test_grouped_partition_nondividing_experts_drop():
    from repro.parallel import planner
    part, report = planner.acu_grouped_partition(
        _ctx({"data": 2, "model": 4}), n_experts=6, n_blocks=2)
    assert part.cols == () and part.n_cols == 1
    assert any("whole experts" in r for r in report)
    assert part.report == tuple(report)


def test_grouped_partition_k_claims_axis():
    from repro.parallel import planner
    part, report = planner.acu_grouped_partition(
        _ctx({"data": 2, "model": 4},
             {"acu_grouped_k": ("model",)}),
        n_experts=4, n_blocks=2)
    assert part.k == ("model",) and part.cols == ()
    assert any("contraction" in r for r in report)


def test_grouped_partition_nondividing_blocks_drop():
    from repro.parallel import planner
    part, report = planner.acu_grouped_partition(
        _ctx({"data": 4, "model": 2}), n_experts=4, n_blocks=3)
    assert part.rows == () and part.n_rows == 1
    assert any("blocks" in r for r in report)


# ---------------------------------------------------------------------------
# 2x4 (data, model) mesh: expert parallelism — needs 8 host devices
# ---------------------------------------------------------------------------

needs_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


@pytest.fixture(scope="module")
def mesh():
    from repro.launch.mesh import make_host_multi_mesh
    return make_host_multi_mesh((2, 4))


@needs_mesh
def test_grouped_mesh_expert_parallel_bitwise(mesh):
    """Experts shard over model, dispatch blocks over data; the sharded
    grouped plan equals the single-device one bitwise, eager and jitted."""
    from repro.parallel.sharding import use_mesh
    x, w, counts, _ = _approx_operands(seed=13)
    ref = approx_grouped_dense(x, w, CFG_A, counts)
    with use_mesh(mesh):
        plan = grouped_plan(ACU, GroupedSpec(
            n_experts=4, cap=24, d_in=33, d_out=14, n_blocks=2))
        assert plan.partition is not None
        assert plan.describe()["partition"].startswith("blocks('data',)")
        out = approx_grouped_dense(x, w, CFG_A, counts)
        out_j = jax.jit(lambda x, w, c: approx_grouped_dense(
            x, w, CFG_A, c))(x, w, counts)
    assert jnp.array_equal(out, ref)
    assert jnp.array_equal(out_j, ref)


@needs_mesh
@pytest.mark.tier2
@pytest.mark.parametrize("case", [
    # (nb, E, C, K, N): divisible experts, nondividing experts,
    # nondividing blocks, K>bk tiling under the mesh
    (2, 4, 24, 33, 14),
    (2, 6, 24, 33, 14),
    (3, 4, 16, 40, 9),
    (2, 8, 16, 300, 9),
], ids=["div", "nondiv_experts", "nondiv_blocks", "ktile"])
def test_grouped_mesh_sweep_bitwise(mesh, case):
    from repro.parallel.sharding import use_mesh
    nb, E, C, K, N = case
    x, w, counts, _ = _grouped_operands(nb * E, E, C, K, N, seed=sum(case))
    ref = approx_grouped_dense(x, w, CFG_A, counts)
    with use_mesh(mesh):
        out = approx_grouped_dense(x, w, CFG_A, counts)
    assert jnp.array_equal(out, ref)


@needs_mesh
@pytest.mark.tier2
def test_grouped_mesh_k_sharded_biased_m00(mesh):
    """Opt-in contraction sharding: int32 partials psum, the global K-pad
    correction lands once (biased M00 would expose double counting), and
    dead rows stay exactly zero after the correction un-zeroes them."""
    from repro.parallel.sharding import use_mesh
    x, w, counts, mask = _grouped_operands(8, 4, 24, 33, 14, seed=17)
    acu_b = dataclasses.replace(
        make_acu("mul8s_exact", AcuMode.LUT, use_pallas=True, fused=True),
        multiplier=BIASED, lut=build_lut(BIASED))
    cfg_b = ApproxConfig(acu=acu_b)
    ref = approx_grouped_dense(x, w, cfg_b, counts)
    rules = {"acu_grouped_k": ("model",), "acu_grouped_experts": (),
             "acu_grouped_rows": ("data",)}
    with use_mesh(mesh, rules):
        plan = grouped_plan(acu_b, GroupedSpec(
            n_experts=4, cap=24, d_in=33, d_out=14, n_blocks=2))
        assert plan.partition.k == ("model",)
        out = approx_grouped_dense(x, w, cfg_b, counts)
    assert jnp.array_equal(out, ref)
    assert bool(jnp.all(jnp.where(mask[..., None], 0.0, out) == 0))


@needs_mesh
@pytest.mark.tier2
def test_grouped_mesh_ste_grads_bitwise(mesh):
    from repro.parallel.sharding import use_mesh
    x, w, counts, _ = _approx_operands(seed=19)
    N = w.shape[2]

    def loss(x, w):
        return (approx_grouped_dense(x, w, CFG_A, counts)
                * jnp.arange(N)).sum()

    gx_r, gw_r = jax.grad(loss, argnums=(0, 1))(x, w)
    from repro.parallel.sharding import use_mesh
    with use_mesh(mesh):
        gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    assert jnp.array_equal(gx, gx_r)
    assert jnp.array_equal(gw, gw_r)


def test_build_step_meta_surfaces_moe_dispatch():
    """build_step records the resolved dispatch geometry for MoE configs so
    the dry-run can report it per cell (non-MoE configs get no entry)."""
    from repro.configs.shapes import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.specs import build_step
    mesh = make_mesh((1,), ("data",))
    shape = ShapeSpec("tiny", 8, 2, "train")
    bundle = build_step(CFG_MOE, shape, mesh)
    geo = bundle.meta["moe_dispatch"]
    assert geo["n_experts"] == CFG_MOE.n_experts
    assert geo["n_blocks"] >= 1 and geo["capacity"] >= 1
    assert (geo["n_blocks"] * geo["tokens_per_block"]
            == shape.global_batch * shape.seq_len)

    dense = dataclasses.replace(CFG_MOE, name="d", family="llama",
                                pattern=("attn_mlp",), n_experts=0,
                                moe_top_k=0)
    assert "moe_dispatch" not in build_step(dense, shape, mesh).meta
