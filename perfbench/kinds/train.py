"""Approximation-aware retraining: the program's ``Trainer.fit`` with the
approximate forward and STE backward, built as ``launch/train.train``
builds it, fed packed batches from the mix.

Set-up builds the trainer once, takes the first ``check_steps`` steps
through ``fit`` (the first compiles) and reads the observables the
reference checks; the window then goes on with the same trainer, one
``fit`` call per step, until ``seconds`` have passed.
"""
from __future__ import annotations

import itertools
import time

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from perfbench import checks, model, reference, traffic


class TrainCell:
    def __init__(self, spec: dict, seed: int, *, fault=None):
        from repro.launch.specs import make_acfg
        from repro.models.transformer import loss_fn
        from repro.optim.adamw import AdamW, cosine_schedule
        from repro.train.trainer import Trainer, TrainerConfig

        self.cfg, self.mix, self.seed = spec["config"], spec["mix"], seed
        mix, o = self.mix, self.mix["optimizer"]
        self.mcfg = model.program_config(self.cfg, mix["dtype"])
        acfg = make_acfg(model.acu_spec(self.cfg), approx_bwd=True)
        mcfg = self.mcfg
        self.opt = AdamW(lr=cosine_schedule(o["lr"], o["warmup"], o["total"]),
                         b1=o["b1"], b2=o["b2"], eps=o["eps"],
                         weight_decay=o["weight_decay"],
                         clip_norm=o["clip_norm"])
        self.trainer = Trainer(
            lambda p, b: loss_fn(p, b["tokens"], b["labels"], mcfg, acfg),
            self.opt, TrainerConfig(log_every=1))
        if fault is not None:
            fault(self.trainer)
        self.tokens_per_step = mix["batch"] * mix["seq_len"]
        self.feed = traffic.train_batches(mix, self.cfg["vocab_size"], seed)

    def setup(self) -> None:
        """The first ``check_steps`` steps, with the readings the reference
        checks: each step's loss, the first step's gradient as AdamW got it
        (its first moment over 1 - b1) and the parameters' change after
        the last of them."""
        n = self.mix["check_steps"]
        with TraceAnnotation("perfbench.train.init"):
            params = model.init_weights(self.cfg, self.seed, self.mcfg.param_dtype)
            p0 = jax.tree.map(jnp.copy, params)
        h0 = len(self.trainer.history)
        with TraceAnnotation("perfbench.train.step"):
            params, state = self.trainer.fit(params, self.opt.init(params),
                                             self.feed, 1)
        first_grad = reference.leaf_norms(
            jax.tree.map(lambda m: m / (1 - self.opt.b1), state.mu))
        with TraceAnnotation("perfbench.train.step"):
            params, state = self.trainer.fit(params, state, self.feed, n - 1)
        self.readings = {
            "losses": [h["loss"] for h in self.trainer.history[h0:]],
            "first_grad": first_grad,
            "change": reference.diff_norms(params, p0)}
        del p0
        self.params, self.state = params, state

    def window(self, seconds: float) -> dict:
        """Whole steps until ``seconds`` have passed; the rate is their
        tokens over the wall time from the window's start to the end of
        the last step (each step ends in the host reading its loss)."""
        steps, t0 = 0, time.monotonic()
        while time.monotonic() - t0 < seconds:
            with TraceAnnotation("perfbench.train.step"):
                self.params, self.state = self.trainer.fit(
                    self.params, self.state, self.feed, 1)
            steps += 1
        wall = time.monotonic() - t0
        return {"steps": steps, "wall_s": wall,
                "tokens": steps * self.tokens_per_step,
                "e2e": {"qat_tokens_per_s": steps * self.tokens_per_step / wall},
                "attempted": steps, "failed": 0}

    def free(self) -> None:
        """Drop the program's state before the reference runs."""
        del self.params, self.state, self.trainer, self.feed

    def check(self) -> dict:
        n = self.mix["check_steps"]
        batches = list(itertools.islice(
            traffic.train_batches(self.mix, self.cfg["vocab_size"], self.seed), n))
        params0 = model.init_weights(self.cfg, self.seed,
                                     self.mcfg.param_dtype)
        ref = reference.train_readings(self.cfg, self.mix["optimizer"], params0,
                                       batches, n)
        return checks.train_numbers(self.readings, ref)


def make(spec: dict, seed: int, **kw) -> TrainCell:
    return TrainCell(spec, seed, **kw)


def readings(spec: dict, seed: int, who: list) -> dict:
    """The compared numbers of one seed, for each of ``who``: the
    program's (``"program"``), or the float32 reference's against a copy
    of itself in the program's place, in the mix's lower ``control``
    precision (``"control"``) or in float32 with half of each batch left
    out (``"half_batch"``)."""
    out = {}
    cfg, mix = spec["config"], spec["mix"]
    n = mix["check_steps"]
    if "program" in who:
        cell = TrainCell(spec, seed)
        cell.setup()
        cell.free()
        out["program"] = cell.check()
    others = [w for w in who if w != "program"]
    if not others:
        return out
    batches = list(itertools.islice(
        traffic.train_batches(mix, cfg["vocab_size"], seed), n))
    p0 = model.init_weights(
        cfg, seed, model.program_config(cfg, mix["dtype"]).param_dtype)
    ref = reference.train_readings(cfg, mix["optimizer"], p0, batches, n)
    for w in others:
        kw = ({"dtype": jnp.dtype(mix["control"])} if w == "control"
              else {"fault": w})
        other = reference.train_readings(cfg, mix["optimizer"], p0, batches,
                                         n, **kw)
        out[w] = checks.train_numbers(other, ref)
    return out
