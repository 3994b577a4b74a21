"""Attribute one cell's traced window to the program's scopes and spans,
and read what the attribution gives:

    python3 perfbench/scope_report.py --workload <name> --seed <n> --seconds <s>

Set-up and the window are as ``run.py --trace 1`` takes them; the trace is
reduced by :mod:`perfbench.trace` and by :mod:`perfbench.scopes` from the
same file. Prints one JSON line: ``scopes`` (device seconds by scope and
phase, idle gaps by ``repro.*`` span, each step's host time), the accepted
per-layer metrics of the cell, and the readings below; the reference is not
run, so the line carries no ``correct``.

* ``<prefix>.remat_share`` (%, training): device time of phase
  ``recompute`` over busy time;
* ``<prefix>.lm_head_roofline`` (%): least time of the window's output-head
  GEMMs (the forward, and in training both STE gradient GEMMs) over the
  device time of every op in scope ``lm_head``;
* ``<prefix>.step_host_ms`` (ms, training): mean over the window's steps of
  ``repro.train.step`` minus its ``repro.train.wait``.

A program that sets no scope or span reads as nothing there, and the
readings that need it are left out.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from perfbench import counts, model, readers  # noqa: E402
from perfbench.common import find_cell, metric_reader  # noqa: E402

PREFIX = {"train": "qat", "score": "eval"}


def scope_roofline(ctx, scope: str, work) -> float | None:
    """Least time of the emulated work over the device time of every op in
    ``scope``, all phases; ``work`` lists (ops, bytes)."""
    sc = ctx.get("scopes")
    t = sc.scope_s(scope) if sc else 0.0
    if not t or not work:
        return None
    pk = counts.peaks(ctx["device"]["kind"])
    return 100.0 * counts.total_least_s(work, pk) / t


def head_work(ctx, n_passes: int, backward: bool) -> list:
    """The output head's GEMM (the last of a forward's shapes) over
    ``n_passes`` batches of the mix, with its two STE gradient GEMMs when
    ``backward``."""
    cfg, mix = ctx["spec"]["config"], ctx["spec"]["mix"]
    m, k, n = model.gemm_shapes(cfg, mix["batch"] * mix["seq_len"])[-1]
    b = readers.act_bytes(ctx)
    one = [counts.gemm_fwd(m, k, n, b)]
    if backward:
        one += counts.gemm_bwd(m, k, n, b)
    return one * n_passes


def readings(ctx) -> dict:
    """The readings of the module docstring that the window gives."""
    sc, win = ctx.get("scopes"), ctx["window"]
    kind = ctx["spec"]["mix"]["kind"]
    train = kind == "train"
    out = {}
    if train and sc and sc.busy_s:
        out["remat_share"] = 100.0 * sc.phase_s("recompute") / sc.busy_s
    passes = win["steps"] if train else win.get("batches", 0)
    out["lm_head_roofline"] = scope_roofline(
        ctx, "lm_head", head_work(ctx, passes, backward=train))
    if train and sc and sc.step_host_s:
        out["step_host_ms"] = 1e3 * sum(sc.step_host_s) / len(sc.step_host_s)
    return {f"{PREFIX.get(kind, kind)}.{k}": v for k, v in out.items()
            if v is not None}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()
    spec = find_cell(args.workload)
    import jax

    from perfbench import scopes, trace
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    d = jax.devices()
    dev = {"platform": d[0].platform, "kind": d[0].device_kind,
           "count": len(d)}
    kind = importlib.import_module(f"perfbench.kinds.{spec['mix']['kind']}")
    cell = kind.make(spec, args.seed)
    cell.setup()
    trace_dir = tempfile.mkdtemp(prefix="perfbench-scopes-")
    try:
        jax.profiler.start_trace(trace_dir)
        try:
            win = cell.window(args.seconds)
        finally:
            jax.profiler.stop_trace()
        summary = trace.reduce_dir(trace_dir, host_prefix="perfbench.")
        sc = scopes.reduce_dir(trace_dir, host_prefix="perfbench.")
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    ctx = {"trace": summary, "scopes": sc, "spec": spec, "window": win,
           "device": dev}
    accepted = {m["name"]: metric_reader(m["name"])(ctx)
                for m in spec["per_layer"]}
    print(json.dumps({"workload": args.workload, "seed": args.seed,
                      "device": dev, "window_s": summary.window_s,
                      "busy_s": summary.busy_s, "e2e": win["e2e"],
                      "readings": readings(ctx), "metrics": accepted,
                      "scopes": sc.as_dict()}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
