"""What the per-layer metric files share: a device share, a kernel's
roofline share and the model step's share of the int8 peak, each read from
the trace summary and the counts of the window's work.

``ctx`` holds ``trace`` (a :class:`perfbench.trace.TraceSummary`),
``spec`` (the cell's files), ``window`` (what the window did) and
``device``. A reader that finds nothing to read returns None.
"""
from __future__ import annotations

import numpy as np

from perfbench import counts, model


def _peaks(ctx) -> dict:
    return counts.peaks(ctx["device"]["kind"])


def idle_share(ctx) -> float:
    return 100.0 * ctx["trace"].idle_share


def kernel_roofline(ctx, family: str, work) -> float | None:
    """Least time of the emulated work over the device time of every op
    whose family name contains ``family``; ``work`` lists (ops, bytes)."""
    t = sum(s for k, s in ctx["trace"].op_s.items() if family in k)
    t /= ctx["trace"].n_devices
    if not t or not work:
        return None
    return 100.0 * counts.total_least_s(work, _peaks(ctx)) / t


def step_mfu(ctx, ops_per_token: float, tokens: float) -> float | None:
    """Model operations of the window's tokens over the window at the
    int8 peak (every chip)."""
    if not tokens:
        return None
    peak = _peaks(ctx)["int8_ops"] * ctx["trace"].n_devices
    return 100.0 * ops_per_token * tokens / (ctx["trace"].window_s * peak)


def act_bytes(ctx) -> int:
    return np.dtype(ctx["spec"]["mix"]["dtype"]).itemsize


def batch_fwd_work(ctx, n_batches: int) -> list:
    """Forward GEMMs of ``n_batches`` packed batches of the mix."""
    cfg, mix = ctx["spec"]["config"], ctx["spec"]["mix"]
    one = [counts.gemm_fwd(m, k, n, act_bytes(ctx))
           for m, k, n in model.gemm_shapes(cfg, mix["batch"] * mix["seq_len"])]
    return one * n_batches


def train_fwd_work(ctx) -> list:
    return batch_fwd_work(ctx, ctx["window"]["steps"])


def train_bwd_work(ctx) -> list:
    cfg, win = ctx["spec"]["config"], ctx["window"]
    rows = ctx["spec"]["mix"]["batch"] * ctx["spec"]["mix"]["seq_len"]
    one = [w for m, k, n in model.gemm_shapes(cfg, rows)
           for w in counts.gemm_bwd(m, k, n, act_bytes(ctx))]
    return one * win["steps"]
