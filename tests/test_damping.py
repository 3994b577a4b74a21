"""Gradient-noise batch damping: estimator math, schedule dynamics, trainer
integration (microbatch accumulation + the data-parallel mesh path), and the
determinism contracts — damped sharded step == single-device oracle bitwise,
damped kill-and-resume == uninterrupted run bitwise.
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim import damping as D
from repro.optim.adamw import AdamW, SGD
from repro.train.trainer import Trainer, TrainerConfig

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# estimator math
# ---------------------------------------------------------------------------

def test_noise_scale_inverts_the_noise_model():
    """E[|G_B|^2] = |G|^2 + S/B is linear in 1/B; feeding the estimator the
    model's exact expectations must return (S, |G|^2) exactly."""
    s_true, g2_true = 48.0, 3.0
    for b_small, b_big in [(2, 4), (8, 64), (1, 7)]:
        gsq_small = g2_true + s_true / b_small
        gsq_big = g2_true + s_true / b_big
        s, g2 = D.noise_scale(gsq_small, gsq_big, b_small, b_big)
        assert abs(s - s_true) < 1e-9
        assert abs(g2 - g2_true) < 1e-9


def test_noise_scale_statistical_recovery():
    """Monte-Carlo: i.i.d. per-sample gradients with known mean/variance."""
    rng = np.random.default_rng(0)
    dim, g = 64, rng.normal(size=64)
    sigma2 = 4.0
    b_small, b_big, trials = 4, 32, 4000
    small_sq = big_sq = 0.0
    for _ in range(trials):
        noise = rng.normal(scale=np.sqrt(sigma2), size=(b_big, dim))
        per = g[None] + noise
        small_sq += float((np.mean(per[:b_small], 0) ** 2).sum())
        big_sq += float((np.mean(per, 0) ** 2).sum())
    s, g2 = D.noise_scale(small_sq / trials, big_sq / trials, b_small, b_big)
    s_true = sigma2 * dim          # trace of the per-sample covariance
    g2_true = float((g ** 2).sum())
    assert abs(s - s_true) / s_true < 0.1
    assert abs(g2 - g2_true) / g2_true < 0.1


def test_tree_sqnorm():
    t = {"a": jnp.array([3.0, 4.0]), "b": {"c": jnp.array([[2.0]])}}
    assert float(D.tree_sqnorm(t)) == pytest.approx(29.0)


def test_microbatch_noise_stats():
    grads = {"w": jnp.array([1.0, 2.0])}
    st = D.microbatch_noise_stats(jnp.float32(40.0), grads, b_small=4,
                                  b_big=16)
    assert float(st.gsq_small) == pytest.approx(10.0)   # sum over 4 micros
    assert float(st.gsq_big) == pytest.approx(5.0)
    assert (st.b_small, st.b_big) == (4, 16)


# ---------------------------------------------------------------------------
# schedule
# ---------------------------------------------------------------------------

def _stats(b_noise, b_small=4, b_big=8, g2=1.0):
    """Stats whose exact two-point inversion yields S = b_noise * g2."""
    s = b_noise * g2
    return D.NoiseStats(gsq_small=g2 + s / b_small, gsq_big=g2 + s / b_big,
                        b_small=b_small, b_big=b_big)


def test_schedule_growth_is_rate_limited():
    cfg = D.DampingConfig(accum_max=16, warmup_updates=2, ema=0.0,
                          max_growth=2)
    st = D.init_state(cfg)
    noisy = _stats(b_noise=1024.0)
    st = D.update_state(st, cfg, noisy, batch_size=8)
    assert st.accum == 1                       # warming up
    seen = []
    for _ in range(6):
        st = D.update_state(st, cfg, noisy, batch_size=8)
        seen.append(st.accum)
    assert seen == [2, 4, 8, 16, 16, 16]       # doubles, then caps


def test_schedule_grow_only_holds_under_quiet_gradients():
    cfg = D.DampingConfig(accum_max=8, warmup_updates=0, ema=0.0)
    st = D.DampingState(accum=4)
    st = D.update_state(st, cfg, _stats(b_noise=1.0), batch_size=8)
    assert st.accum == 4                       # grow_only: no shrink
    cfg2 = D.DampingConfig(accum_max=8, warmup_updates=0, ema=0.0,
                           grow_only=False)
    st2 = D.update_state(D.DampingState(accum=4), cfg2,
                         _stats(b_noise=1.0), batch_size=8)
    assert st2.accum == 2                      # shrink also rate-limited


def test_residual_energy_inflates_noise():
    cfg = D.DampingConfig(warmup_updates=0, ema=0.0, residual_weight=1.0)
    quiet = _stats(b_noise=4.0)
    st_plain = D.update_state(D.init_state(cfg), cfg, quiet, batch_size=1)
    loud = quiet._replace(resid_sq=jnp.float32(10.0))
    st_resid = D.update_state(D.init_state(cfg), cfg, loud, batch_size=1)
    assert st_resid.b_noise > st_plain.b_noise


def test_state_json_roundtrip():
    cfg = D.DampingConfig()
    st = D.update_state(D.init_state(cfg), cfg, _stats(64.0), batch_size=8)
    st2 = D.DampingState.from_dict(json.loads(json.dumps(st.to_dict())))
    assert st2 == st
    # and the schedule continues identically from the round-tripped state
    a = D.update_state(st, cfg, _stats(64.0), batch_size=8)
    b = D.update_state(st2, cfg, _stats(64.0), batch_size=8)
    assert a == b


# ---------------------------------------------------------------------------
# trainer integration (single device)
# ---------------------------------------------------------------------------

def _regression_problem(noise=2.0, dim=8, seed=0):
    """Noisy linear regression: per-sample gradient noise is controllable."""
    rng = np.random.default_rng(seed)
    w_true = rng.normal(size=(dim,)).astype(np.float32)

    def batches(batch, seed=1):
        r = np.random.default_rng(seed)
        while True:
            x = r.normal(size=(batch, dim)).astype(np.float32)
            y = (x @ w_true + noise * r.normal(size=batch)).astype(np.float32)
            yield {"x": x, "y": y}

    def loss_fn(params, b):
        pred = b["x"] @ params["w"] + params["b"]
        return jnp.mean((pred - b["y"]) ** 2)

    params = {"w": jnp.zeros(dim, jnp.float32), "b": jnp.zeros((), jnp.float32)}
    return params, loss_fn, batches


def test_microbatch_matches_full_batch():
    """cfg.microbatch=k accumulates to the same step as one full-batch pass
    (same mean loss/grads up to fp reassociation)."""
    params, loss_fn, batches = _regression_problem()
    outs = []
    for k in (0, 2, 4):
        tr = Trainer(loss_fn, SGD(lr=0.05),
                     TrainerConfig(microbatch=k, log_every=1), donate=False)
        p, _ = tr.fit(jax.tree.map(jnp.copy, params), SGD(lr=0.05).init(params),
                      batches(16, seed=3), n_steps=5)
        outs.append(p)
    for p in outs[1:]:
        for a, b in zip(jax.tree.leaves(outs[0]), jax.tree.leaves(p)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-5, atol=2e-6)


def test_microbatch_non_divisible_raises():
    params, loss_fn, batches = _regression_problem()
    tr = Trainer(loss_fn, SGD(lr=0.05), TrainerConfig(microbatch=3))
    with pytest.raises(ValueError, match="does not divide"):
        tr.fit(params, SGD(lr=0.05).init(params), batches(16), n_steps=1)


def test_microbatch_loss_accumulator_is_float32():
    """The scan carry pins fp32 even when the loss comes back half-precision
    (a weak-typed 0.0 used to inherit bf16 and quantize the accumulation)."""
    params, loss_fn, batches = _regression_problem()
    bf16_loss = lambda p, b: loss_fn(p, b).astype(jnp.bfloat16)
    tr = Trainer(bf16_loss, SGD(lr=0.05),
                 TrainerConfig(microbatch=4, log_every=1), donate=False)
    tr.fit(params, SGD(lr=0.05).init(params), batches(16, seed=3), n_steps=1)
    losses = [h["loss"] for h in tr.history if "loss" in h]
    assert losses and np.isfinite(losses[0])


def test_damping_forbids_fixed_microbatch():
    params, loss_fn, _ = _regression_problem()
    with pytest.raises(ValueError, match="damping"):
        Trainer(loss_fn, SGD(lr=0.05),
                TrainerConfig(microbatch=4, damping=D.DampingConfig()))


def test_damped_trainer_grows_effective_batch():
    """High per-sample noise + tiny batch => B_noise >> batch => the trainer
    must grow its accumulation factor and consume extra batches."""
    params, loss_fn, batches = _regression_problem(noise=8.0)
    cfg = TrainerConfig(log_every=1,
                        damping=D.DampingConfig(accum_max=8, warmup_updates=1,
                                                ema=0.5))
    tr = Trainer(loss_fn, SGD(lr=0.01), cfg, donate=False)
    tr.fit(params, SGD(lr=0.01).init(params), batches(4, seed=2), n_steps=12)
    assert tr.damp_state.accum > 1
    assert tr.consumed > 12                    # accum>1 steps drew extra
    accums = [h["accum"] for h in tr.history if "accum" in h]
    assert accums == sorted(accums)            # grow_only is monotone


def test_damped_resume_matches_uninterrupted(tmp_path):
    """Kill-and-resume of a DAMPED run reproduces the uninterrupted run
    exactly: params bitwise, consumed count, and schedule state."""
    params, loss_fn, batches = _regression_problem(noise=6.0)
    dcfg = D.DampingConfig(accum_max=4, warmup_updates=1, ema=0.5)
    opt = SGD(lr=0.01)

    def mk(ckpt):
        return Trainer(loss_fn, opt,
                       TrainerConfig(ckpt_dir=ckpt, ckpt_every=5,
                                     async_ckpt=False, log_every=1,
                                     damping=dcfg), donate=False)

    tr0 = mk(str(tmp_path / "clean"))
    p_clean, _ = tr0.fit(jax.tree.map(jnp.copy, params), opt.init(params),
                         batches(4, seed=2), n_steps=20)

    tr1 = mk(str(tmp_path / "killed"))
    tr1.fit(jax.tree.map(jnp.copy, params), opt.init(params),
            batches(4, seed=2), n_steps=10)
    tr2 = mk(str(tmp_path / "killed"))      # fresh process stand-in
    p_res, _ = tr2.fit(jax.tree.map(jnp.copy, params), opt.init(params),
                       batches(4, seed=2), n_steps=20)

    assert tr2.consumed == tr0.consumed
    assert tr2.damp_state == tr0.damp_state
    for a, b in zip(jax.tree.leaves(p_clean), jax.tree.leaves(p_res)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# mesh path
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def mesh():
    if len(jax.devices()) < 8:
        pytest.skip("needs XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from repro.launch.mesh import make_host_multi_mesh
    return make_host_multi_mesh((2, 4))


@needs_8_devices
def test_compressed_psum_stats_pair(mesh):
    """with_stats exports the free estimator pair: mean per-worker |g|^2,
    |mean|^2, residual energy — and the noisier the shards, the wider the
    small/large gap."""
    from jax.sharding import PartitionSpec as P

    from repro.optim.compression import EFState, compressed_psum

    W = 2
    rng = np.random.default_rng(0)
    g = rng.normal(size=(W, 16)).astype(np.float32)

    def worker(gs, rs):
        summed, ef, stats = compressed_psum(
            {"g": gs[0]}, EFState(residual={"g": rs[0]}), "data",
            with_stats=True)
        return (jax.tree.map(lambda x: x[None], summed),
                jax.tree.map(lambda x: x[None], ef.residual),
                jax.tree.map(lambda x: jnp.reshape(x, (1,)), stats))

    f = jax.shard_map(worker, mesh=mesh,
                      in_specs=(P("data"), P("data")),
                      out_specs=(P("data"), P("data"),
                                 jax.tree.map(lambda _: P("data"), {
                                     "gsq_small": 0, "gsq_big": 0,
                                     "resid_sq": 0})),
                      check_vma=False)
    summed, resid, stats = f(jnp.asarray(g), jnp.zeros_like(jnp.asarray(g)))
    mean = np.asarray(summed["g"])[0]
    small = float(np.asarray(stats["gsq_small"])[0])
    big = float(np.asarray(stats["gsq_big"])[0])
    assert small == pytest.approx(float((g ** 2).sum(1).mean()), rel=1e-5)
    assert big == pytest.approx(float((mean ** 2).sum()), rel=1e-5)
    assert small > big                         # disagreeing shards
    # residual energy (what int8 dropped) is reported and finite
    assert np.isfinite(np.asarray(stats["resid_sq"])[0])
    assert np.isfinite(np.asarray(resid["g"])).all()


@needs_8_devices
def test_dp_damped_step_bitwise_matches_single_device_oracle(mesh):
    """The acceptance pin: one damped data-parallel step on the 2x4 mesh is
    BITWISE the single-device oracle that replays its semantics — per-shard
    grads, shared-amax int8 codes, int32 sum x scale/W, same AdamW update.
    The int-space psum in compressed_psum is what makes this exact."""
    from repro.optim.compression import compress, decompress

    params, loss_fn, batches = _regression_problem(noise=4.0)
    opt = AdamW(lr=1e-2)
    W = 2                                      # dp_axes=("data",) on 2x4
    batch = next(batches(8, seed=5))

    tr = Trainer(loss_fn, opt, TrainerConfig(mesh=mesh), donate=False)
    p_mesh, o_mesh, loss_mesh, _ = tr._run_step(
        jax.tree.map(jnp.copy, params), opt.init(params),
        {k: jnp.asarray(v) for k, v in batch.items()}, n_micro=1)

    # ---- single-device oracle ----
    grad_fn = jax.jit(jax.value_and_grad(loss_fn))
    shards = [{k: jnp.asarray(v[i * 4:(i + 1) * 4]) for k, v in batch.items()}
              for i in range(W)]
    per = [grad_fn(params, s)[1] for s in shards]
    leaves = [jax.tree.leaves(g) for g in per]
    mean_leaves = []
    for li in range(len(leaves[0])):
        gs = [leaves[w][li].astype(jnp.float32) for w in range(W)]
        amax = jnp.max(jnp.stack([jnp.max(jnp.abs(g)) for g in gs]))
        qs = [compress(g, amax) for g in gs]
        scale = qs[0][1]
        q_sum = sum(q[0].astype(jnp.int32) for q in qs)
        mean_leaves.append(q_sum.astype(jnp.float32) * (scale / W))
    mean = jax.tree.unflatten(jax.tree.structure(per[0]), mean_leaves)
    p_one, o_one = jax.jit(opt.update)(mean, opt.init(params), params)

    for a, b in zip(jax.tree.leaves(p_mesh), jax.tree.leaves(p_one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree.leaves(o_mesh), jax.tree.leaves(o_one)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@needs_8_devices
def test_dp_damped_trainer_runs_and_grows(mesh):
    """End-to-end damped data-parallel fit: schedule grows off the mesh's
    per-worker noise pair and the loss still falls."""
    params, loss_fn, batches = _regression_problem(noise=8.0)
    cfg = TrainerConfig(mesh=mesh, log_every=1,
                        damping=D.DampingConfig(accum_max=4, warmup_updates=1,
                                                ema=0.5))
    opt = SGD(lr=0.01)
    tr = Trainer(loss_fn, opt, cfg, donate=False)
    tr.fit(params, opt.init(params), batches(8, seed=2), n_steps=10)
    assert tr.damp_state.updates > 0
    assert tr.damp_state.b_noise > 0
    losses = [h["loss"] for h in tr.history if "loss" in h]
    assert losses[-1] < losses[0]


@needs_8_devices
@pytest.mark.tier2
def test_mesh_wide_damped_qat_recovery(mesh):
    """Long tier-2 run: mesh-wide QAT recovery through the approximate
    forward/backward with damping on reaches the fixed-batch run's recovered
    loss using no more samples (the BENCH_PR9 sample-efficiency claim,
    in miniature)."""
    from repro.core import make_acu
    from repro.core.acu import AcuMode
    from repro.core.approx_ops import ApproxConfig
    from repro.data.pipeline import image_task
    from repro.models.vision import cnn_forward, init_cnn

    acfg = ApproxConfig(acu=make_acu("mul8s_1L2H", AcuMode.LUT),
                        approx_bwd=True)

    def loss_fn(p, b):
        logits = cnn_forward(p, b["image"], acfg)
        logz = jax.nn.logsumexp(logits, -1)
        gold = jnp.take_along_axis(logits, b["label"][:, None], -1)[:, 0]
        return (logz - gold).mean()

    task = image_task(n_classes=4, size=8)
    params = init_cnn(jax.random.PRNGKey(0), n_classes=4, width=8, in_ch=3,
                      img=8)
    opt = SGD(lr=1e-2)

    def run(damping):
        tr = Trainer(loss_fn, opt,
                     TrainerConfig(mesh=mesh, log_every=1, damping=damping),
                     donate=False)
        p0 = jax.tree.map(jnp.copy, params)
        tr.fit(p0, opt.init(p0),
               ({k: jnp.asarray(v) for k, v in b.items()}
                for b in task(16, noise=0.5, seed=2)), n_steps=15)
        losses = [h["loss"] for h in tr.history if "loss" in h]
        return losses, tr.consumed * 16

    fixed_losses, fixed_samples = run(None)
    damped_losses, damped_samples = run(
        D.DampingConfig(accum_max=4, warmup_updates=2, ema=0.5))
    assert damped_losses[-1] <= fixed_losses[0]     # it recovered
    assert np.isfinite(damped_losses).all()
