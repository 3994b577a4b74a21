"""Serving launcher: batched greedy decoding through the serving engines.

    PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m --reduced \
        [--approx mul8s_1L2H:lut] [--requests 8] [--new-tokens 16] \
        [--continuous | --paged] [--arrival-rate 0.5] \
        [--block-size 16] [--hbm-budget BYTES]

``--continuous`` swaps the wave engine for slot-level continuous batching;
``--paged`` selects the paged-KV continuous engine (block pool + prefix
reuse, docs/serving.md "Paged KV") and prints the resolved attention plan
report plus the pool geometry. ``--block-size`` and ``--hbm-budget``
(bytes; default = the contiguous engine's footprint for the same slots)
shape the pool. ``--arrival-rate`` (arrivals per decode step,
continuous/paged only) replays a Poisson trace instead of firing every
request at t=0.

:func:`build_engine` and :func:`serve` are the callable halves of
:func:`main`; ``chip_smoke.py`` drives them directly.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np


def build_engine(arch: str = "smollm-135m", *, reduced: bool = False,
                 approx: str | None = None, slots: int = 4,
                 continuous: bool = False, paged: bool = False,
                 block_size: int = 16, hbm_budget: int | None = None,
                 max_seq: int = 256, seed: int = 0):
    """Random-weight model (from ``seed``) behind the selected engine."""
    from repro.configs import get_config, reduced_config
    from repro.launch.specs import make_acfg
    from repro.models.transformer import init_params
    from repro.serve.engine import (ContinuousServeEngine,
                                    PagedContinuousServeEngine, ServeEngine)

    cfg = reduced_config(arch) if reduced else get_config(arch)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    acfg = make_acfg(approx)
    if paged:
        return PagedContinuousServeEngine(
            params, cfg, slots=slots, max_seq=max_seq, block_size=block_size,
            acfg=acfg, hbm_budget=hbm_budget)
    cls = ContinuousServeEngine if continuous else ServeEngine
    return cls(params, cfg, slots=slots, max_seq=max_seq, acfg=acfg)


def make_requests(vocab_size: int, n: int, new_tokens: int, seed: int = 0):
    """``n`` random prompts of 4-11 tokens, ``new_tokens`` each."""
    from repro.serve.engine import Request
    rng = np.random.default_rng(seed)
    return [Request(prompt=rng.integers(1, vocab_size, rng.integers(4, 12)
                                        ).astype(np.int32),
                    max_new_tokens=new_tokens)
            for _ in range(n)]


def serve(eng, requests, *, arrival_rate: float | None = None):
    """Run ``requests`` through ``eng``; returns (done, seconds)."""
    from repro.serve.engine import ServeEngine, poisson_arrivals
    slotted = not isinstance(eng, ServeEngine)
    if arrival_rate is not None and not slotted:
        raise ValueError("arrival_rate needs a continuous or paged engine")
    t0 = time.monotonic()
    if slotted:
        arrivals = (None if arrival_rate is None else
                    poisson_arrivals(len(requests), arrival_rate, seed=0))
        done = eng.run(requests, arrivals)
    else:
        done = eng.run(requests)
    return done, time.monotonic() - t0


def attn_plan_report(eng) -> dict:
    """``describe()`` of the paged engine's attention plan."""
    from repro.core.acu import AttnSpec, attn_plan
    cfg, acfg = eng.cfg, eng.acfg
    spec = AttnSpec(hq=cfg.n_heads, hkv=cfg.n_kv_heads, bk=eng.block_size,
                    kv_layout="paged")
    return attn_plan(acfg.acu, spec, a_bits=acfg.a_bits, mesh=False).describe()


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--approx", default=None)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--continuous", action="store_true")
    ap.add_argument("--paged", action="store_true",
                    help="paged-KV continuous engine (implies slot-level "
                         "scheduling; see docs/serving.md)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="KV block size in tokens (paged only; pow2 >= 8)")
    ap.add_argument("--hbm-budget", type=int, default=None,
                    help="KV pool budget in bytes (paged only; default = "
                         "slots * max_seq contiguous footprint)")
    ap.add_argument("--arrival-rate", type=float, default=None,
                    help="Poisson arrivals per decode step "
                         "(continuous/paged only)")
    args = ap.parse_args()
    if args.arrival_rate is not None and not (args.continuous or args.paged):
        ap.error("--arrival-rate needs --continuous or --paged")

    from repro.kernels.runtime import enable_compile_cache
    from repro.serve.engine import kv_block_bytes
    enable_compile_cache()

    eng = build_engine(args.arch, reduced=args.reduced, approx=args.approx,
                       slots=args.slots, continuous=args.continuous,
                       paged=args.paged, block_size=args.block_size,
                       hbm_budget=args.hbm_budget)
    if args.paged:
        bbytes = kv_block_bytes(eng.cfg, args.block_size)
        print(f"paged pool: {eng.n_blocks} blocks x {args.block_size} tok "
              f"({bbytes} B/block, budget {eng.hbm_budget} B, "
              f"{eng.n_logical} logical blocks/slot)")
        if eng.acfg is not None:
            for k, v in attn_plan_report(eng).items():
                print(f"attn_plan.{k}: {v}")
    reqs = make_requests(eng.cfg.vocab_size, args.requests, args.new_tokens)
    done, dt = serve(eng, reqs, arrival_rate=args.arrival_rate)
    n_tok = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {n_tok} tokens in {dt:.2f}s "
          f"({n_tok/dt:.1f} tok/s)")
    if args.continuous or args.paged:
        print(f"stats: {eng.stats}")
    for i, r in enumerate(done[:4]):
        print(f"req{i}: {list(r.prompt)[:6]}... -> {list(r.out)[:8]}...")


if __name__ == "__main__":
    main()
