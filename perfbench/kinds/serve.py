"""Approximate generation: the program's ``PagedContinuousServeEngine.run``
(chunked prefill through the paged KV cache, prefix reuse, batched greedy
decode through the approximate paged attention), one call per window over
an offline batch queued at the start.

The window closes at the first token emitted after ``seconds``; tokens are
timed by the engine's ``on_token`` callback.
"""
from __future__ import annotations

import time

import numpy as np
from jax.profiler import TraceAnnotation

from perfbench import model, reference, traffic


class WindowClosed(Exception):
    pass


class ServeCell:
    def __init__(self, spec: dict, seed: int, *, fault=None):
        from repro.launch.specs import make_acfg
        from repro.serve.engine import (PagedContinuousServeEngine,
                                        kv_block_bytes)
        self.cfg, self.mix, self.seed = spec["config"], spec["mix"], seed
        mix = self.mix
        self.mcfg = model.program_config(self.cfg, mix["dtype"])
        bs = mix["block_size"]
        with TraceAnnotation("perfbench.serve.init"):
            self.params = model.init_weights(self.cfg, seed,
                                             self.mcfg.param_dtype)
        self.eng = PagedContinuousServeEngine(
            self.params, self.mcfg, slots=mix["slots"], max_seq=mix["max_seq"],
            block_size=bs, acfg=make_acfg(model.acu_spec(self.cfg)),
            hbm_budget=mix["pool_tokens"] // bs * kv_block_bytes(self.mcfg, bs))
        if fault is not None:
            fault(self.eng)
        self.requests = traffic.serve_requests(mix, self.cfg["vocab_size"], seed)

    def _run(self, reqs, on_token=None):
        from repro.serve.engine import Request
        batch = [Request(prompt=p, max_new_tokens=n) for p, n in reqs]
        self.eng.run(batch, on_token=on_token)
        return batch

    def setup(self) -> None:
        """Compile every program the window's traffic uses: the chunked
        prefill, each tail bucket its prompts need, decode at the full slot
        count and the prefix cache's block copy: one short request per tail
        bucket."""
        bs = self.mix["block_size"]
        buckets = sorted({_bucket(_tail(len(p), bs)) for p, _ in self.requests})
        warm = [(np.arange(i * 64 + 1, i * 64 + 1 + bs + b, dtype=np.int32), 2)
                for i, b in enumerate(buckets)]
        with TraceAnnotation("perfbench.serve.warmup"):
            self._run(warm)

    def window(self, seconds: float) -> dict:
        stamps: dict[int, list[float]] = {}
        toks: dict[int, list[int]] = {}
        t0 = time.monotonic()
        deadline = t0 + seconds

        def on_token(rid, tok):
            now = time.monotonic()
            if now >= deadline:
                raise WindowClosed
            stamps.setdefault(rid, []).append(now)
            toks.setdefault(rid, []).append(tok)

        with TraceAnnotation("perfbench.serve.run"):
            try:
                self._run(self.requests, on_token)
                t_end = time.monotonic()
            except WindowClosed:
                t_end = deadline
        wall = t_end - t0
        gaps = np.concatenate([np.diff(s) for s in stamps.values()] or [[]])
        n_tok = sum(len(t) for t in toks.values())
        st = dict(self.eng.stats)
        self.served = toks
        done = [r for r, t in toks.items() if len(t) == self.requests[r][1]]
        return {"wall_s": wall, "tokens": n_tok, "finished": len(done),
                "stats": st,
                "e2e": {"gen_tokens_per_s": n_tok / wall,
                        "gen_itl_p95_ms": 1e3 * float(np.percentile(gaps, 95))
                        if gaps.size else float("nan")},
                "attempted": len(toks), "failed": 0}

    def free(self) -> None:
        del self.eng

    def check(self) -> dict:
        """The widest gap of a served token's reference logit below the
        reference's best, over a sample of finished requests drawn from the
        seed, the longest among them."""
        seqs = self._sample()
        if not seqs:
            return _gap_numbers(np.array([np.nan]))
        return _gap_numbers(reference.served_gaps(
            self.cfg, self.params, self.mix["block_size"], seqs,
            self._padded(seqs)))

    def _sample(self):
        done = sorted(r for r, t in self.served.items()
                      if len(t) == self.requests[r][1])
        if not done:
            return []
        rng = np.random.default_rng([self.seed, 3])
        longest = max(done, key=lambda r: len(self.served[r]))
        rest = [r for r in done if r != longest]
        k = min(len(rest), self.mix["check_requests"] - 1)
        pick = [longest] + list(rng.choice(rest, k, replace=False)) if k else [longest]
        return [(self.requests[r][0], np.asarray(self.served[r], np.int32))
                for r in pick]

    def _padded(self, seqs) -> int:
        n = max(len(p) + len(s) for p, s in seqs)
        return -(-n // 128) * 128


def _gap_numbers(gaps: np.ndarray) -> dict:
    """The widest gap, the mean gap and the share of tokens not the
    reference's first choice."""
    return {"served_gap": float(gaps.max()),
            "served_gap_mean": float(gaps.mean()),
            "served_mismatch": float((gaps > 0).mean())}


def _tail(plen: int, bs: int) -> int:
    """Tokens of a prompt's last, privately replayed prefill chunk."""
    n_full, t_real = divmod(plen, bs)
    n_shared = n_full - (1 if t_real == 0 and n_full > 0 else 0)
    return plen - n_shared * bs


def _bucket(n: int, lo: int = 8) -> int:
    """The engine's tail-chunk bucket: the power of two >= n, at least lo."""
    b = lo
    while b < n:
        b *= 2
    return b


def make(spec: dict, seed: int, **kw) -> ServeCell:
    return ServeCell(spec, seed, **kw)


def readings(spec: dict, seed: int, who: list) -> dict:
    """One window at the cell's load, then for each of ``who``: the gaps
    of the program's served tokens (``"program"``), or at each position of
    the same prompts and served tokens the gap of the token that the
    reference a precision lower (the mix's ``control``) puts first
    (``"control"``)."""
    cell = ServeCell(spec, seed)
    cell.setup()
    cell.window(spec["mix"]["readings_seconds"])
    cell.free()
    seqs = cell._sample()
    out = {}
    for w in who:
        if not seqs:
            out[w] = _gap_numbers(np.array([np.nan]))
        elif w == "program":
            out[w] = cell.check()
        else:
            out[w] = _gap_numbers(reference.served_gaps(
                cell.cfg, cell.params, cell.mix["block_size"], seqs,
                cell._padded(seqs), other=spec["mix"]["control"]))
    return out
