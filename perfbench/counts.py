"""Operations and bytes of the emulated ops, counted from their shapes, and
the chip's peaks.

An approximate GEMM of (M, K) activations by (K, N) weights emulates an
int8 GEMM: 2 M K N integer operations, reading the activations at their
dtype and the weights as 1-byte codes, writing the output at its dtype. A
backward GEMM reads both operands as float residuals. The count is of the
emulated op, so it reads the same whether the program computes it by a
table gather, a one-hot matrix product or anything else; recomputation
(rematerialised forwards) is not counted.
"""
from __future__ import annotations

import functools

from perfbench.common import BENCH_DIR, BenchError, load_json


@functools.cache
def _table() -> dict:
    return load_json(BENCH_DIR / "peaks.json")


def peaks(device_kind: str) -> dict:
    if device_kind not in _table():
        raise BenchError(f"no peaks for device kind {device_kind!r}")
    return _table()[device_kind]


def least_s(ops: float, nbytes: float, pk: dict) -> float:
    """The least time the chip could take: compute- or bandwidth-bound."""
    return max(ops / pk["int8_ops"], nbytes / pk["hbm_bytes_per_s"])


def gemm_fwd(m: int, k: int, n: int, act_bytes: int) -> tuple[float, float]:
    return 2.0 * m * k * n, m * k * act_bytes + k * n + m * n * 4


def gemm_bwd(m: int, k: int, n: int, act_bytes: int) -> list[tuple[float, float]]:
    """The two STE gradient GEMMs of a forward (M, K) x (K, N): dX = dY W^T
    and dW = X^T dY, float operands, float32 outputs."""
    b = act_bytes
    return [(2.0 * m * k * n, m * n * 4 + n * k * b + m * k * 4),
            (2.0 * m * k * n, k * m * b + m * n * 4 + k * n * 4)]


def total_least_s(items, pk: dict) -> float:
    return sum(least_s(o, b, pk) for o, b in items)
