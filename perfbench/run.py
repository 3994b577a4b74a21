"""Run one benchmark cell once and print its result line.

    python3 perfbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Exits non-zero and prints no result when JAX finds no TPU or fewer chips
than the cell asks for. With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics read from a
profiler trace of the window. Either way the run ends by comparing what
the timed path produced with the plain reference; the numbers compared
and their limits are the last lines on standard error and the last key
of the result.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

from perfbench import checks  # noqa: E402
from perfbench.common import (ROOT, BenchError, CompileLog, find_cell,  # noqa: E402
                              log, metric_reader)

CACHE_DIR = ROOT / ".jax_cache"


def device_info(jax) -> dict:
    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_bytes(jax) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.local_devices())


def run(args) -> int:
    spec = find_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"no program under {ROOT / 'src'}")
    # the persistent compile cache lives at a fixed path in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    dev = device_info(jax)
    if dev["platform"] != "tpu" or dev["count"] < spec["cell"]["chips"]:
        log(f"needs {spec['cell']['chips']} TPU chip(s); JAX found "
            f"{dev['count']} {dev['platform']} device(s)")
        return 3
    from repro.kernels.runtime import enable_compile_cache
    enable_compile_cache()
    result = execute(spec, args.seed, args.seconds, bool(args.trace), dev)
    for name, v in result.pop("readings").items():
        log(f"reading {name}: {v!r} (not compared)")
    for name, c in result["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


def execute(spec: dict, seed: int, seconds: float, trace: bool, dev: dict,
            fault=None) -> dict:
    """Set-up, the measured window, the metrics and the comparison with the
    reference, on whatever devices JAX has; ``fault`` breaks the program
    underneath (for the harness's own tests)."""
    import jax
    clog = CompileLog()
    kind = importlib.import_module(f"perfbench.kinds.{spec['mix']['kind']}")
    cell = kind.make(spec, seed, fault=fault)
    cell.setup()
    setup_s = time.monotonic() - T_START
    t_win = time.monotonic()
    trace_dir = None
    if trace:
        trace_dir = tempfile.mkdtemp(prefix="perfbench-trace-")
        jax.profiler.start_trace(trace_dir)
    try:
        win = cell.window(seconds)
    finally:
        if trace_dir:
            jax.profiler.stop_trace()
    compiles = clog.traces_since(t_win)
    log(f"setup_s {setup_s!r} (compile {clog.seconds(0.0)!r} s, cache hits "
        f"{clog.hits}, misses {clog.misses}); window {win['wall_s']!r} s; "
        f"compiles inside the window: {compiles}")
    dev = {**dev, "memory_peak_bytes": peak_bytes(jax)}

    result = {"attempted": win["attempted"], "failed": win["failed"]}
    if trace_dir:
        from perfbench import trace as tr
        try:
            summary = tr.reduce_dir(trace_dir, host_prefix="perfbench.")
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        ctx = {"trace": summary, "spec": spec, "window": win, "device": dev}
        metrics = {}
        for m in spec["per_layer"]:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        dev["busy_s"] = summary.busy_s
        dev["window_s"] = summary.window_s
        result["breakdown"] = summary.breakdown()
    else:
        e2e = {"setup_s": setup_s, **win["e2e"]}
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in spec["end_to_end"]}
    result["metrics"] = metrics
    result["device"] = dev

    cell.free()
    gc.collect()
    numbers = cell.check()
    limits = spec["limits"]["limits"]
    correct, compared = checks.judge(numbers, limits)
    return {"correct": correct, **result, "checks": compared,
            "readings": {k: v for k, v in numbers.items() if k not in limits}}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    try:
        return run(args)
    except BenchError as e:
        log(f"error: {e}")
        return 2


if __name__ == "__main__":
    sys.exit(main())
