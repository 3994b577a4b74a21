"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

* the window: from the start of the first host span whose name starts
  with the benchmark's prefix to the end of the last one;
* busy time: the union of the device's ``XLA Ops`` intervals inside the
  window, averaged over the TPU devices; idle share is 1 - busy/window;
* self time of each device op, grouped by its HLO instruction name with
  the numeric suffix dropped (``%fused_lut_dense_kernel.57`` ->
  ``fused_lut_dense_kernel``); a ``while`` loop's own time excludes the ops
  it contains;
* each idle gap inside the window, named by the innermost host event of
  the benchmark's thread that covers its midpoint.

Host and device events share one clock in the trace.
"""
from __future__ import annotations

import dataclasses
import glob
import re

_SUFFIX = re.compile(r"\.\d+$")


def op_family(event_name: str) -> str:
    """``%fused_lut_dense_kernel.57 = f32[...] custom-call(...)`` ->
    ``fused_lut_dense_kernel``."""
    instr = event_name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", instr)


def union_length(intervals, lo: float, hi: float) -> float:
    total, reach = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, reach), min(b, hi)
        if b > a:
            total += b - a
            reach = b
    return total


def merged(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def self_times(events, lo: float, hi: float) -> dict:
    """Self time per op family of possibly nested ``(name, start, end)``
    events, clipped to [lo, hi]."""
    out: dict[str, float] = {}
    stack: list[list] = []          # [family, start, end, child_time]

    def close(entry):
        fam, a, b, child = entry
        own = max(0.0, min(b, hi) - max(a, lo)) - child
        out[fam] = out.get(fam, 0.0) + max(own, 0.0)
        if stack:
            stack[-1][3] += max(0.0, min(b, hi) - max(a, lo))

    for name, a, b in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][2] <= a:
            close(stack.pop())
        stack.append([op_family(name), a, b, 0.0])
    while stack:
        close(stack.pop())
    return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    op_s: dict            # op family -> self seconds (summed over devices)
    gaps: list            # [(host event name, seconds)], longest first
    n_devices: int
    module_s: dict        # program (XLA module) name -> seconds, per device

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, family: str) -> float | None:
        """Device seconds of one op family per device, or None when the
        window ran none of it."""
        s = self.op_s.get(family)
        return None if not s else s / self.n_devices

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:n]
        by_host: dict[str, float] = {}
        for name, s in self.gaps:
            by_host[name] = by_host.get(name, 0.0) + s
        gaps = sorted(by_host.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v / self.n_devices] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def reduce_file(path: str, host_prefix: str) -> TraceSummary:
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    devices, modules, host_lines = [], [], []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if line.name == "XLA Ops":
                    devices.append(evs)
                elif line.name == "XLA Modules":
                    modules.extend(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n.startswith(host_prefix) for n, _, _ in evs):
                    host_lines.append(evs)
    spans = [e for line in host_lines for e in line if e[0].startswith(host_prefix)]
    if not devices or not spans:
        raise ValueError(f"{path}: no TPU ops or no {host_prefix}* host spans")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    busy = [union_length([(a, b) for _, a, b in d], lo, hi) for d in devices]
    op_s: dict[str, float] = {}
    for d in devices:
        for k, v in self_times(d, lo, hi).items():
            op_s[k] = op_s.get(k, 0.0) + v / 1e9
    # idle gaps of the first device, named by the innermost host event
    busy_iv = merged([(max(a, lo), min(b, hi)) for _, a, b in devices[0]
                      if b > lo and a < hi])
    host = sorted((e for line in host_lines for e in line),
                  key=lambda e: (e[1], -e[2]))
    gaps, prev = [], lo
    for a, b in busy_iv + [[hi, hi]]:
        if a > prev:
            mid = (prev + a) / 2
            inner = [e for e in host if e[1] <= mid <= e[2]]
            name = min(inner, key=lambda e: e[2] - e[1])[0] if inner else "(none)"
            gaps.append((name, (a - prev) / 1e9))
        prev = max(prev, b)
    gaps.sort(key=lambda g: -g[1])
    module_s: dict[str, float] = {}
    for name, a, b in modules:
        fam = name.split("(", 1)[0]
        module_s[fam] = module_s.get(fam, 0.0) + max(
            0.0, min(b, hi) - max(a, lo)) / 1e9 / len(devices)
    return TraceSummary(module_s=module_s, window_s=(hi - lo) / 1e9,
                        busy_s=sum(busy) / len(busy) / 1e9, op_s=op_s,
                        gaps=gaps, n_devices=len(devices))


def reduce_dir(trace_dir: str, host_prefix: str) -> TraceSummary:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, found {paths}")
    return reduce_file(paths[0], host_prefix)
