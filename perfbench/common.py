"""Shared plumbing: locating the cell's files, seeds, compile accounting and
the result line."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import sys
import time

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


class BenchError(RuntimeError):
    """A cell that cannot run as specified (unknown name, no chip)."""


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def find_cell(workload: str, bench_path: pathlib.Path | None = None) -> dict:
    """The workload entry of ``BENCHMARK.json`` with its configuration, mix,
    limits and the metric entries that apply to it."""
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise BenchError(f"unknown workload {workload!r}; have {sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    config = configs[cell["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return {
        "cell": cell,
        "config": load_json(ROOT / config["file"]),
        "mix": load_json(BENCH_DIR / "mixes" / f"{cell['traffic']}.json"),
        "limits": load_json(BENCH_DIR / "limits" / f"{workload}.json"),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


def metric_reader(name: str):
    """``read(ctx)`` of ``metrics/<name>.py``."""
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"perfbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def rng_key(seed: int):
    """A JAX key from any non-negative seed (wider than 32 bits too)."""
    import jax
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0xFFFFFFFF)


class CompileLog:
    """Tracing, lowering and compile time, and persistent-cache hits and
    misses, from JAX's monitoring events. Nested events overlap, so compile
    time is the length of the union of their intervals."""

    def __init__(self):
        import jax
        self.spans: list[tuple[float, float]] = []
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event in COMPILE_EVENTS:
            end = time.monotonic()
            self.spans.append((end - secs, end))

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def seconds(self, since: float = 0.0) -> float:
        total, reach = 0.0, since
        for lo, hi in sorted(self.spans):
            lo = max(lo, reach)
            if hi > lo:
                total += hi - lo
                reach = hi
        return total

    def traces_since(self, since: float) -> int:
        """Trace/lower/compile events that ended after ``since``."""
        return sum(1 for _, hi in self.spans if hi > since)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)
