"""The program names its layers and the trainer's phases for a profiler.

Device ops carry ``jax.named_scope`` names in their ``op_name`` metadata
(``embed``, ``attn``, ``mlp``, ``moe``, ``final_norm``, ``lm_head``,
``loss``, ``optimizer``), through autodiff and rematerialisation, and every
approximate-kernel op lies under one of them. ``Trainer.fit`` runs each
step in a ``repro.train.step`` span holding ``repro.train.draw`` and
``repro.train.wait`` spans, with ``repro.train.checkpoint`` and
``repro.train.restore`` where those run.
"""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.launch.specs import make_acfg
from repro.models.transformer import apply_model, init_params, loss_fn
from repro.optim.adamw import AdamW
from repro.train.trainer import Trainer, TrainerConfig

SCOPES = ("embed", "attn", "mlp", "moe", "final_norm", "lm_head", "loss",
          "optimizer")
KERNELS = ("fused_lut_dense_kernel", "fused_lut_bwd_kernel")
_WRAPPED = re.compile(r"(?:jvp|transpose)\((.*)\)")


def op_names(compiled_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', compiled_text))


def scope_of(op_name: str):
    """The innermost scope name of an ``op_name`` path, autodiff's
    ``jvp(...)`` / ``transpose(...)`` wrappers unwrapped."""
    for part in reversed(re.split(r"[/;]", op_name)):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        if part in SCOPES:
            return part
    return None


def _tiny():
    # rematerialised like the full-size configuration
    cfg = dataclasses.replace(reduced_config("smollm-135m"), remat=True)
    acfg = make_acfg("mul8s_1L2H:lut", approx_bwd=True)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    return cfg, acfg, params, tokens


@pytest.fixture(scope="module")
def step_ops():
    cfg, acfg, params, tokens = _tiny()
    opt = AdamW(lr=1e-3)
    tr = Trainer(lambda p, b: loss_fn(p, b["tokens"], b["labels"], cfg, acfg),
                 opt)
    state = jax.eval_shape(opt.init, params)
    batch = {"tokens": tokens, "labels": tokens}
    return op_names(tr._get_step(1).lower(params, state, batch)
                    .compile().as_text())


@pytest.fixture(scope="module")
def score_ops():
    cfg, acfg, params, tokens = _tiny()
    fn = jax.jit(lambda p, t: apply_model(p, t, cfg, acfg=acfg)[0])
    return op_names(fn.lower(params, tokens).compile().as_text())


@pytest.mark.parametrize("scope", ["embed", "attn", "mlp", "final_norm",
                                   "lm_head", "loss", "optimizer"])
def test_step_names_scope(step_ops, scope):
    assert any(scope_of(n) == scope for n in step_ops)


@pytest.mark.parametrize("path", ["transpose(jvp(lm_head))",
                                  "transpose(jvp())/while/body/closed_call/"
                                  "checkpoint/mlp",
                                  "checkpoint/rematted_computation/attn"])
def test_step_scopes_survive_autodiff_and_remat(step_ops, path):
    assert any(path in n for n in step_ops)


@pytest.mark.parametrize("scope", ["embed", "attn", "mlp", "final_norm",
                                   "lm_head"])
def test_score_names_scope(score_ops, scope):
    assert any(scope_of(n) == scope for n in score_ops)


@pytest.mark.parametrize("which", ["step", "score"])
def test_every_kernel_op_is_scoped(step_ops, score_ops, which):
    ops = step_ops if which == "step" else score_ops
    kernel = [n for n in ops if any(f"jit({k})" in n for k in KERNELS)]
    assert kernel
    assert all(scope_of(n) in ("attn", "mlp", "lm_head") for n in kernel), \
        sorted(n for n in kernel if scope_of(n) is None)[:3]


def test_moe_block_is_scoped():
    cfg = reduced_config("olmoe-1b-7b")
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((1, 16), jnp.int32)
    fn = jax.jit(lambda p, t: apply_model(p, t, cfg)[0])
    ops = op_names(fn.lower(params, tokens).compile().as_text())
    assert any(scope_of(n) == "moe" for n in ops)


# -- host spans -------------------------------------------------------------

def _problem():
    params = {"w": jnp.ones((4, 4), jnp.float32)}

    def loss(p, b):
        return jnp.mean((b["x"] @ p["w"]) ** 2)

    def batches():
        rng = np.random.default_rng(0)
        while True:
            yield {"x": rng.standard_normal((2, 4)).astype(np.float32)}

    return params, loss, batches()


def _host_spans(trace_dir) -> list:
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    pd = jax.profiler.ProfileData.from_file(path)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for plane in pd.planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith("repro.")]


@pytest.fixture(scope="module")
def fit_spans(tmp_path_factory):
    """Two plain steps, then (checkpointing) a step that fails once and is
    rolled back."""
    params, loss, feed = _problem()
    opt = AdamW(lr=1e-2)
    tr = Trainer(loss, opt, TrainerConfig(log_every=1))
    # the first step compiles outside the trace; fit donates its inputs
    p1, s1 = tr.fit(params, opt.init(params), feed, 1)
    plain = tmp_path_factory.mktemp("plain")
    with jax.profiler.trace(str(plain)):
        tr.fit(p1, s1, feed, 2)

    ckpt = tmp_path_factory.mktemp("ckpt")
    tr2 = Trainer(loss, opt, TrainerConfig(ckpt_dir=str(ckpt), ckpt_every=1,
                                           async_ckpt=False, log_every=1))
    failed = []

    def fail_once(step):
        if step == 1 and not failed:
            failed.append(step)
            raise RuntimeError("simulated node failure")

    recovery = tmp_path_factory.mktemp("recovery")
    fresh = _problem()[0]                       # the first fit donated params
    with jax.profiler.trace(str(recovery)):
        tr2.fit(fresh, opt.init(fresh), feed, 2, fail_hook=fail_once)
    return {"plain": _host_spans(plain), "recovery": _host_spans(recovery)}


def _inside(spans, outer, name):
    return [s for s in spans if s[0] == name
            and outer[1] <= s[1] and s[2] <= outer[2]]


def test_two_steps_are_two_step_spans(fit_spans):
    assert sum(s[0] == "repro.train.step" for s in fit_spans["plain"]) == 2


@pytest.mark.parametrize("inner", ["repro.train.draw", "repro.train.wait"])
def test_each_step_holds_one(fit_spans, inner):
    spans = fit_spans["plain"]
    steps = [s for s in spans if s[0] == "repro.train.step"]
    assert [len(_inside(spans, s, inner)) for s in steps] == [1, 1]


@pytest.mark.parametrize("name,count", [("repro.train.step", 3),
                                        ("repro.train.restore", 1),
                                        ("repro.train.checkpoint", 2),
                                        ("repro.train.wait", 2)])
def test_recovery_spans(fit_spans, name, count):
    # step 0 saves, step 1 fails and rolls back to it, step 1 runs again
    assert sum(s[0] == name for s in fit_spans["recovery"]) == count
