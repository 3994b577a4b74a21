"""Attribution of a profiler trace (``.xplane.pb``) to the program's own
names, beside the op-family reduction of :mod:`perfbench.trace`:

* each device op in the window to a ``(scope, phase)``. Its path is the
  ``tf_op`` stat of its event metadata, read from the file by a small
  protobuf wire-format reader (``jax.profiler.ProfileData`` exposes event
  stats only). The scope is the innermost of the program's
  ``jax.named_scope`` names (:data:`SCOPES`) in the path, ``jvp(...)`` and
  ``transpose(...)`` unwrapped, else ``(unscoped)``; the phase is
  ``recompute`` inside ``rematted_computation``, else ``backward`` under a
  ``transpose(``, else ``forward``. Time is self time, as
  :func:`perfbench.trace.self_times` computes it, so the buckets partition
  the busy time;
* each idle gap (the gaps :mod:`perfbench.trace` finds) to the innermost
  ``repro.*`` host span of the program over its midpoint;
* each training step's host time: its ``repro.train.step`` span minus the
  ``repro.train.wait`` span inside it (the host reading the loss).
"""
from __future__ import annotations

import dataclasses
import glob
import re

from perfbench import trace

SCOPES = frozenset({"embed", "attn", "mlp", "moe", "final_norm", "lm_head",
                    "loss", "optimizer"})
UNSCOPED = "(unscoped)"
PHASES = ("forward", "recompute", "backward")
PROGRAM_PREFIX = "repro."
STEP, WAIT = "repro.train.step", "repro.train.wait"
_DEVICE = re.compile(r"/device:TPU:\d+")
_WRAPPED = re.compile(r"(?:jvp|transpose)\((.*)\)")


# -- protobuf wire format ----------------------------------------------------

def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, wire type, value) of one message; a length-delimited
    value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(buf, i)
        elif wt == 1:
            v, i = buf[i:i + 8], i + 8
        elif wt == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wt == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield num, wt, v


def _map_value(buf):
    """The value (field 2) of a protobuf map entry."""
    for num, _, v in _fields(buf):
        if num == 2:
            return v
    return memoryview(b"")


def _str(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def _plane_tf_ops(plane) -> tuple[str, dict]:
    """XPlane: name (2), event_metadata (4), stat_metadata (5) ->
    (plane name, {event metadata name: tf_op})."""
    name, events, stat_names = "", [], {}
    for num, _, v in _fields(plane):
        if num == 2:
            name = _str(v)
        elif num == 4:
            events.append(_map_value(v))
        elif num == 5:
            sid, sname = 0, ""
            for n2, _, v2 in _fields(_map_value(v)):   # XStatMetadata
                if n2 == 1:
                    sid = v2
                elif n2 == 2:
                    sname = _str(v2)
            stat_names[sid] = sname
    tf_op = {i for i, s in stat_names.items() if s == "tf_op"}
    out = {}
    if not tf_op:
        return name, out
    for ev in events:                                   # XEventMetadata
        ename, path = "", None
        for n2, _, v2 in _fields(ev):
            if n2 == 2:
                ename = _str(v2)
            elif n2 == 5:                               # XStat
                sid, val = None, None
                for n3, _, v3 in _fields(v2):
                    if n3 == 1:
                        sid = v3
                    elif n3 == 5:                       # str_value
                        val = _str(v3)
                    elif n3 == 7:                       # ref_value
                        val = stat_names.get(v3)
                if sid in tf_op and val is not None:
                    path = val
        if path is not None:
            out.setdefault(ename, path)
    return name, out


def tf_ops(path: str) -> dict:
    """{TPU plane name: {event name as ``ProfileData`` reports it: tf_op}}
    from an ``.xplane.pb`` (XSpace: planes are field 1)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for num, _, v in _fields(buf):
        if num == 1:
            name, ops = _plane_tf_ops(v)
            if _DEVICE.fullmatch(name):
                out[name] = ops
    return out


# -- labels ------------------------------------------------------------------

def scope_of(tf_op: str) -> str:
    """The innermost program scope of a ``tf_op`` path."""
    for part in reversed(re.split(r"[/;]", tf_op)):
        m = _WRAPPED.fullmatch(part)
        while m:
            part = m.group(1)
            m = _WRAPPED.fullmatch(part)
        if part in SCOPES:
            return part
    return UNSCOPED


def phase_of(tf_op: str) -> str:
    if "rematted_computation" in tf_op:
        return "recompute"
    if "transpose(" in tf_op:
        return "backward"
    return "forward"


def label(tf_op: str | None) -> str:
    """``scope/phase``: the key a device op's self time is summed under."""
    tf_op = tf_op or ""
    return f"{scope_of(tf_op)}/{phase_of(tf_op)}"


# -- reduction ---------------------------------------------------------------

@dataclasses.dataclass
class ScopeSummary:
    window_s: float
    busy_s: float         # per device, as trace.TraceSummary.busy_s
    device_s: dict        # "scope/phase" -> self seconds per device
    gaps: dict            # innermost repro.* span (or "(none)") -> seconds
    step_host_s: list     # per window step: step span minus its wait span

    def scope_s(self, scope: str) -> float:
        return sum(s for k, s in self.device_s.items()
                   if k.split("/")[0] == scope)

    def phase_s(self, phase: str) -> float:
        return sum(s for k, s in self.device_s.items()
                   if k.split("/")[1] == phase)

    def as_dict(self) -> dict:
        """The result line's ``scopes`` key."""
        by_scope: dict[str, dict] = {}
        for k, s in sorted(self.device_s.items(), key=lambda kv: -kv[1]):
            scope, phase = k.split("/")
            by_scope.setdefault(scope, {})[phase] = s
        return {"device_s": by_scope,
                "idle_gaps": dict(sorted(self.gaps.items(),
                                         key=lambda kv: -kv[1])),
                "step_host_s": self.step_host_s}


def _inside(e, a, b) -> bool:
    return e[1] >= a and e[2] <= b


def reduce_file(path: str, host_prefix: str) -> ScopeSummary:
    """The window is the benchmark's, as :func:`perfbench.trace.reduce_file`
    takes it: from the first ``host_prefix`` span to the end of the last."""
    import jax
    paths = tf_ops(path)
    pd = jax.profiler.ProfileData.from_file(path)
    devices, spans, program = [], [], []   # device events carry labels
    for plane in pd.planes:
        if _DEVICE.fullmatch(plane.name):
            ops = paths.get(plane.name, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices.append([(label(ops.get(e.name)), e.start_ns,
                                     e.start_ns + e.duration_ns)
                                    for e in line.events])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(host_prefix):
                        spans.append(ev)
                    elif e.name.startswith(PROGRAM_PREFIX):
                        program.append(ev)
    if not devices or not spans:
        raise ValueError(f"{path}: no TPU ops or no {host_prefix}* host spans")
    lo = min(a for _, a, _ in spans)
    hi = max(b for _, _, b in spans)
    n = len(devices)
    device_s: dict[str, float] = {}
    for d in devices:
        for k, v in trace.self_times(d, lo, hi).items():
            device_s[k] = device_s.get(k, 0.0) + v / 1e9 / n
    busy = sum(trace.union_length([(a, b) for _, a, b in d], lo, hi)
               for d in devices) / n / 1e9
    # idle gaps of the first device, named by the innermost program span
    busy_iv = trace.merged([(max(a, lo), min(b, hi))
                            for _, a, b in devices[0] if b > lo and a < hi])
    gaps: dict[str, float] = {}
    prev = lo
    for a, b in busy_iv + [[hi, hi]]:
        if a > prev:
            mid = (prev + a) / 2
            inner = [e for e in program if e[1] <= mid <= e[2]]
            name = (min(inner, key=lambda e: e[2] - e[1])[0] if inner
                    else "(none)")
            gaps[name] = gaps.get(name, 0.0) + (a - prev) / 1e9
        prev = max(prev, b)
    step_host_s = []
    for s in sorted(e for e in program if e[0] == STEP and _inside(e, lo, hi)):
        wait = sum(w[2] - w[1] for w in program
                   if w[0] == WAIT and _inside(w, s[1], s[2]))
        step_host_s.append((s[2] - s[1] - wait) / 1e9)
    return ScopeSummary(window_s=(hi - lo) / 1e9, busy_s=busy,
                        device_s=device_s, gaps=gaps, step_host_s=step_host_s)


def reduce_dir(trace_dir: str, host_prefix: str) -> ScopeSummary:
    paths = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: expected one .xplane.pb, "
                         f"found {paths}")
    return reduce_file(paths[0], host_prefix)
