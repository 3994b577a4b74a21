"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch smollm-135m \
        [--steps N] [--batch 8] [--seq 256] [--approx mul8s_1L2H:lut] \
        [--ckpt DIR] [--reduced]

On real hardware this process runs per-host under `jax.distributed`
(initialize() is called when the standard cluster env vars are present);
in this container it runs single-process. The step function, planner
shardings, checkpointing and recovery paths are identical either way —
that's the point of the dry-run-first design.

With ``--approx`` the run is QAT: approximate forward GEMMs and the
approximate STE backward (``approx_bwd``), both on the fused kernels in LUT
mode. :func:`train` is the callable half of :func:`main`;
``chip_smoke.py`` drives it directly.
"""
from __future__ import annotations

import argparse
import os

import jax


def train(arch: str = "smollm-135m", *, steps: int = 200, batch: int = 8,
          seq: int = 256, approx: str | None = None,
          ckpt: str | None = None, reduced: bool = False,
          log_every: int = 20, seed: int = 0):
    """Train random-init weights (from ``seed``) on the synthetic Markov LM
    at the config's full vocabulary; returns the trainer (its ``history``
    holds the logged losses). ``ckpt=None`` runs without checkpoints, so a
    failing step raises instead of rolling back."""
    from repro.configs import get_config, reduced_config
    from repro.data.pipeline import MarkovLM, Prefetcher
    from repro.launch.specs import make_acfg
    from repro.models.transformer import init_params, loss_fn
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.train.trainer import Trainer, TrainerConfig

    cfg = reduced_config(arch) if reduced else get_config(arch)
    acfg = make_acfg(approx, approx_bwd=True)

    lm = MarkovLM(vocab=cfg.vocab_size, seed=seed)
    params = init_params(jax.random.PRNGKey(seed), cfg)
    opt = AdamW(lr=cosine_schedule(3e-4, 100, steps), weight_decay=0.01)

    trainer = Trainer(
        lambda p, b: loss_fn(p, b["tokens"], b["labels"], cfg, acfg), opt,
        TrainerConfig(ckpt_dir=ckpt, ckpt_every=100, log_every=log_every))
    data = Prefetcher(lm.batches(batch, seq), depth=2)
    try:
        trainer.fit(params, opt.init(params), data, steps)
    finally:
        data.close()
    return trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--approx", default=None, help="mult:mode[:rank]")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint directory (default: no checkpoints)")
    ap.add_argument("--reduced", action="store_true",
                    help="width-reduced config (CPU-sized)")
    args = ap.parse_args()

    if "JAX_COORDINATOR_ADDRESS" in os.environ:  # multi-host cluster
        jax.distributed.initialize()

    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    trainer = train(args.arch, steps=args.steps, batch=args.batch,
                    seq=args.seq, approx=args.approx, ckpt=args.ckpt,
                    reduced=args.reduced)
    for h in trainer.history[-10:]:
        print(h)


if __name__ == "__main__":
    main()
