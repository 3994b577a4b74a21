"""The general traffic generator: reads a mix file's parameters and makes
the cell's inputs from the seed.

Token content comes from a seeded Markov language model (each token has a
few successors with Zipf-like weights), so sequences have structure the
model can learn. Lengths are the quantiles of the mix's distribution, the
same set for every seed; the seed draws their order and the tokens, so
every seed asks for the same amount of work.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


class MarkovTokens:
    def __init__(self, vocab: int, seed: int, branching: int = 8):
        rng = np.random.default_rng([seed, 0])
        self.vocab = vocab
        self.succ = rng.integers(0, vocab, (vocab, branching))
        w = 1.0 / np.arange(1, branching + 1)
        self.cdf = np.cumsum(w / w.sum())

    def sample(self, rng: np.random.Generator, rows: int, length: int,
               first=None) -> np.ndarray:
        """``rows`` sequences of ``length`` tokens; ``first`` seeds row 0
        positions when given."""
        out = np.empty((rows, length), np.int32)
        out[:, 0] = rng.integers(0, self.vocab, rows) if first is None else first
        pick = np.searchsorted(self.cdf, rng.random((rows, length - 1)))
        pick = np.minimum(pick, self.succ.shape[1] - 1)
        for t in range(1, length):
            out[:, t] = self.succ[out[:, t - 1], pick[:, t - 1]]
        return out


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the mid-quantiles of ``dist``: ``loguniform`` over
    [lo, hi], or ``lognormal`` with ``median`` and ``sigma`` clipped to
    [lo, hi]; ``fixed`` gives ``value``."""
    u = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "fixed":
        x = np.full(n, float(dist["value"]))
    elif kind == "loguniform":
        x = np.exp(math.log(dist["lo"]) + u * (math.log(dist["hi"])
                                               - math.log(dist["lo"])))
    elif kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = dist["median"] * np.exp(dist["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), dist.get("lo", 1), dist.get("hi", 1 << 30)
                   ).astype(np.int64)


def train_batches(mix: dict, vocab: int, seed: int):
    """Endless packed training batches ``{"tokens", "labels"}`` of
    ``batch`` x ``seq_len``; batch ``i`` depends on (seed, i) alone."""
    lm = MarkovTokens(vocab, seed, mix["tokens"]["branching"])
    b, s = mix["batch"], mix["seq_len"]
    i = 0
    while True:
        rng = np.random.default_rng([seed, 1, i])
        seq = lm.sample(rng, b, s + 1)
        yield {"tokens": seq[:, :-1], "labels": seq[:, 1:]}
        i += 1


def zipf_shares(n: int, count: int, s: float) -> np.ndarray:
    """How many of ``n`` requests go to each of ``count`` items whose
    popularity falls as 1 / rank^s (largest remainders, so the counts add
    up to ``n``)."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    out[np.argsort(out - exact)[: n - out.sum()]] += 1
    return out


def serve_requests(mix: dict, vocab: int, seed: int):
    """The offline batch of a serving mix: a list of (prompt, max_new)
    pairs, all queued at the start. Prompts are an optional shared prefix
    (one of ``prefix.count`` task prefixes, chosen by Zipf) followed by a
    private part."""
    lm = MarkovTokens(vocab, seed, mix["tokens"]["branching"])
    rng = np.random.default_rng([seed, 2])
    n = mix["requests"]
    own = rng.permutation(quantile_lengths(mix["prompt"], n))
    out = rng.permutation(quantile_lengths(mix["output"], n))
    pre = mix.get("prefix")
    prefixes, which = [], np.zeros(n, np.int64)
    if pre:
        prefixes = [lm.sample(rng, 1, pre["length"])[0]
                    for _ in range(pre["count"])]
        which = rng.permutation(np.repeat(
            np.arange(pre["count"]), zipf_shares(n, pre["count"], pre["zipf_s"])))
    reqs = []
    for j in range(n):
        body = lm.sample(rng, 1, int(own[j]))[0]
        prompt = (np.concatenate([prefixes[which[j]], body]) if pre
                  else body)
        reqs.append((prompt.astype(np.int32), int(out[j])))
    return reqs
