"""Approximate scoring of a mixture-of-experts decoder (OLMoE): the scoring
cell of :mod:`perfbench.kinds.score` with the program's config and weights
from :mod:`perfbench.olmoe` and the plain reference of
:mod:`perfbench.reference_olmoe`.

Besides the gaps it compares, the check reads ``expert_load_max``: the most
rows any expert is routed in a layer of the checked batches over the mean
(T k / E), by the reference's routing.
"""
from __future__ import annotations

import itertools

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import model, olmoe, reference, reference_olmoe, traffic
from perfbench.kinds.score import ScoreCell, _gaps


class ScoreMoeCell(ScoreCell):
    def __init__(self, spec: dict, seed: int, *, fault=None):
        from repro.launch.specs import make_acfg
        from repro.models.transformer import apply_model
        self.cfg, self.mix, self.seed = spec["config"], spec["mix"], seed
        mcfg = olmoe.program_config(self.cfg, self.mix["dtype"])
        acfg = make_acfg(model.acu_spec(self.cfg))

        def score(params, tokens, labels):
            logits, _ = apply_model(params, tokens, mcfg, acfg=acfg)
            return reference.token_nll(logits.astype(jnp.float32), labels)

        self.score = jax.jit(score)
        if fault is not None:
            fault(self)
        with TraceAnnotation("perfbench.score.init"):
            self.params = olmoe.init_weights(self.cfg, seed, mcfg.param_dtype)
        self.feed = traffic.train_batches(self.mix, self.cfg["vocab_size"], seed)
        self.tokens_per_batch = self.mix["batch"] * self.mix["seq_len"]
        self.answers: list = []

    def check(self) -> dict:
        """Gaps of the program's token NLLs from the reference's over a
        sample of the window's batches drawn from the seed, and the
        reference's largest expert load there."""
        rng = np.random.default_rng([self.seed, 4])
        k = min(len(self.answers), self.mix["check_batches"])
        pick = sorted(rng.choice(len(self.answers), k, replace=False))
        got = [self.answers[i] for i in pick]
        want, loads = reference_olmoe.score(self.cfg, self.params,
                                            [b for b, _ in got])
        return {**_gaps([n for _, n in got], want),
                "expert_load_max": expert_load_max(self.cfg, loads)}


def expert_load_max(cfg: dict, loads) -> float:
    """The most rows an expert is routed in one layer over the mean."""
    mean = np.sum(loads[0][0]) / cfg["num_experts"]
    return float(max(np.max(c) for c in loads) / mean)


def make(spec: dict, seed: int, **kw) -> ScoreMoeCell:
    return ScoreMoeCell(spec, seed, **kw)


def readings(spec: dict, seed: int, who: list) -> dict:
    """For each of ``who``: the program's gaps after a short window at the
    cell's load (``"program"``), or those of the reference computed in the
    mix's lower ``control`` precision in the program's place
    (``"control"``), on the seed's first batches; the program does not run
    for the control."""
    cell = ScoreMoeCell(spec, seed)
    out = {}
    if "program" in who:
        cell.setup()
        cell.window(spec["mix"]["readings_seconds"])
        out["program"] = cell.check()
    if "control" in who:
        batches = list(itertools.islice(
            traffic.train_batches(cell.mix, cell.cfg["vocab_size"], seed),
            cell.mix["check_batches"]))
        want, _ = reference_olmoe.score(cell.cfg, cell.params, batches)
        low, _ = reference_olmoe.score(cell.cfg, cell.params, batches,
                                       cell.mix["control"])
        out["control"] = _gaps(low, want)
    return out
