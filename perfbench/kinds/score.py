"""Approximate scoring (perplexity): the program's ``apply_model`` with the
approximate forward, jitted once, over packed batches of the mix; each
sequence's answer is its mean next-token NLL, read back by the host.

Set-up compiles the scoring program on the first batch; the window scores
one batch after another until ``seconds`` have passed.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import model, reference, traffic


class ScoreCell:
    def __init__(self, spec: dict, seed: int, *, fault=None):
        from repro.launch.specs import make_acfg
        from repro.models.transformer import apply_model
        self.cfg, self.mix, self.seed = spec["config"], spec["mix"], seed
        mcfg = model.program_config(self.cfg, self.mix["dtype"])
        acfg = make_acfg(model.acu_spec(self.cfg))

        def score(params, tokens, labels):
            logits, _ = apply_model(params, tokens, mcfg, acfg=acfg)
            return reference.token_nll(logits.astype(jnp.float32), labels)

        self.score = jax.jit(score)
        if fault is not None:
            fault(self)
        with TraceAnnotation("perfbench.score.init"):
            self.params = model.init_weights(self.cfg, seed, mcfg.param_dtype)
        self.feed = traffic.train_batches(self.mix, self.cfg["vocab_size"], seed)
        self.tokens_per_batch = self.mix["batch"] * self.mix["seq_len"]
        self.answers: list = []

    def _one(self):
        b = next(self.feed)
        with TraceAnnotation("perfbench.score.batch"):
            nll = np.asarray(self.score(self.params, b["tokens"], b["labels"]))
        return b, nll

    def setup(self) -> None:
        self._one()

    def window(self, seconds: float) -> dict:
        t0 = time.monotonic()
        while time.monotonic() - t0 < seconds:
            self.answers.append(self._one())
        wall = time.monotonic() - t0
        n = len(self.answers) * self.tokens_per_batch
        return {"wall_s": wall, "batches": len(self.answers), "tokens": n,
                "e2e": {"eval_tokens_per_s": n / wall},
                "attempted": len(self.answers) * self.mix["batch"], "failed": 0}

    def free(self) -> None:
        del self.feed

    def check(self) -> dict:
        """Gaps of the program's token NLLs from the reference's over a
        sample of the window's batches drawn from the seed."""
        rng = np.random.default_rng([self.seed, 4])
        k = min(len(self.answers), self.mix["check_batches"])
        pick = sorted(rng.choice(len(self.answers), k, replace=False))
        got = [self.answers[i] for i in pick]
        want = reference.token_nlls(self.cfg, self.params, [b for b, _ in got])
        return _gaps([n for _, n in got], want)


def _gaps(got, want) -> dict:
    """The widest relative gap of a sequence's mean NLL, and the mean
    absolute gap of a token's NLL."""
    g, w = np.stack(got), np.stack(want)
    seq = np.abs(g.mean(-1) - w.mean(-1)) / np.abs(w.mean(-1))
    return {"nll_gap": float(seq.max()),
            "token_nll_gap": float(np.abs(g - w).mean())}


def make(spec: dict, seed: int, **kw) -> ScoreCell:
    return ScoreCell(spec, seed, **kw)


def readings(spec: dict, seed: int, who: list) -> dict:
    """A short window at the cell's load, then for each of ``who``: the
    program's gaps (``"program"``), or those of the reference computed in
    the mix's lower ``control`` precision in the program's place
    (``"control"``), on the window's first batches."""
    cell = ScoreCell(spec, seed)
    cell.setup()
    cell.window(spec["mix"]["readings_seconds"])
    out = {}
    if "program" in who:
        out["program"] = cell.check()
    if "control" in who:
        batches = [b for b, _ in cell.answers[: spec["mix"]["check_batches"]]]
        want = reference.token_nlls(cell.cfg, cell.params, batches)
        low = reference.token_nlls(cell.cfg, cell.params, batches,
                                   spec["mix"]["control"])
        out["control"] = _gaps(low, want)
    return out
