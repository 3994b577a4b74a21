"""The numbers that decide ``correct``, each against its limit."""
from __future__ import annotations

import statistics


def rel_gap_loss(prog: list[float], ref: list[float]) -> float:
    """Widest relative gap of a step's loss."""
    return max(abs(p - r) / abs(r) for p, r in zip(prog, ref))


def norm_gap(prog: dict, ref: dict, keep=None) -> float:
    """Worst leaf of | |prog| - |ref| | over max(|ref|, median leaf |ref|)."""
    keys = [k for k in ref if keep is None or k in keep]
    med = statistics.median(ref[k] for k in keys)
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in keys)


def moving_leaves(first_grad_ref: dict) -> set:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's."""
    med = statistics.median(first_grad_ref.values())
    return {k for k, v in first_grad_ref.items() if v >= 1e-3 * med}


def train_numbers(prog: dict, ref: dict) -> dict:
    return {
        "loss_gap": rel_gap_loss(prog["losses"], ref["losses"]),
        "grad_gap": norm_gap(prog["first_grad"], ref["first_grad"]),
        "change_gap": norm_gap(prog["change"], ref["change"],
                               moving_leaves(ref["first_grad"])),
    }


def judge(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and ``{name: {"value", "limit"}}``; a missing or
    non-finite number fails."""
    out, ok = {}, True
    for name, limit in limits.items():
        v = numbers.get(name)
        good = v is not None and v == v and v <= limit
        ok &= good
        out[name] = {"value": v, "limit": limit}
    return ok, out
