"""Roofline extraction from compiled SPMD artifacts (DESIGN.md §7).

Terms per (arch x shape x mesh), all **per device**:
  T_compute    = HLO_FLOPs / peak_FLOP/s
  T_memory     = HLO_bytes / HBM_bw
  T_collective = collective_bytes / ICI_link_bw

`cost_analysis()` counts `lax.scan` bodies ONCE (measured), so each model is
compiled twice — scan_unroll=1 and =2 — and the per-layer-group delta is
scaled by the group count (`two_point`). Collective bytes are absent from
cost_analysis and are parsed from the compiled HLO text instead.

Analytic correction: time-recurrences that live inside nested scans (the
RWKV WKV loop) are under-counted even by the two-point method; their FLOPs
are added analytically (`recurrence_correction`) — they are <2% of any cell.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Optional

# TPU v5e (assignment constants)
PEAK_BF16 = 197e12          # FLOP/s per chip
HBM_BW = 819e9              # B/s per chip
ICI_BW = 50e9               # B/s per link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLL_RE = re.compile(
    r"^\s*(?:%?\S+\s*=\s*)?\(?([a-z0-9\[\],\s{}()]*?)\)?\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", re.IGNORECASE)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Sum result-shape bytes of every collective op (per-device traffic
    proxy: ring all-reduce moves ~2x, all-gather ~(n-1)/n x result bytes —
    within 2x of the true per-link bytes; we report result bytes and note
    the convention)."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.match(line)
        if not m:
            continue
        shape_part, kind = m.group(1), m.group(2).lower()
        b = _shape_bytes(shape_part)
        if b:
            out[kind] = out.get(kind, 0) + b
    return out


@dataclasses.dataclass
class CellCost:
    flops: float                 # per device
    bytes_accessed: float        # per device
    coll_bytes: float            # per device (result-bytes convention)
    coll_breakdown: dict
    peak_memory: float           # per device bytes (args + temps)
    arg_bytes: float

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_BF16

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        """Lower-bound step time = max of the three terms (perfect overlap)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    def as_dict(self) -> dict:
        return {
            "flops": self.flops, "bytes": self.bytes_accessed,
            "coll_bytes": self.coll_bytes, "coll_breakdown": self.coll_breakdown,
            "peak_memory": self.peak_memory, "arg_bytes": self.arg_bytes,
            "t_compute": self.t_compute, "t_memory": self.t_memory,
            "t_collective": self.t_collective, "bottleneck": self.bottleneck,
            "step_time_lb": self.step_time,
        }


def extract(compiled, hlo_text: Optional[str] = None) -> CellCost:
    ca = compiled.cost_analysis()
    ma = compiled.memory_analysis()
    text = hlo_text if hlo_text is not None else compiled.as_text()
    coll = collective_bytes(text)
    return CellCost(
        flops=float(ca.get("flops", 0.0)),
        bytes_accessed=float(ca.get("bytes accessed", 0.0)),
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        peak_memory=float(ma.argument_size_in_bytes + ma.temp_size_in_bytes +
                          ma.output_size_in_bytes - ma.alias_size_in_bytes),
        arg_bytes=float(ma.argument_size_in_bytes),
    )


def two_point(cost_u1: CellCost, cost_u2: CellCost, n_groups: int) -> CellCost:
    """total = outside + n_groups * (group delta); memory stats from u1."""
    def comb(a, b):
        delta = max(b - a, 0.0)
        return a + (n_groups - 1) * delta

    coll = {}
    keys = set(cost_u1.coll_breakdown) | set(cost_u2.coll_breakdown)
    for k in keys:
        coll[k] = comb(cost_u1.coll_breakdown.get(k, 0),
                       cost_u2.coll_breakdown.get(k, 0))
    return CellCost(
        flops=comb(cost_u1.flops, cost_u2.flops),
        bytes_accessed=comb(cost_u1.bytes_accessed, cost_u2.bytes_accessed),
        coll_bytes=float(sum(coll.values())),
        coll_breakdown=coll,
        peak_memory=cost_u1.peak_memory,
        arg_bytes=cost_u1.arg_bytes,
    )


def model_flops(cfg, shape, n_devices: int) -> float:
    """MODEL_FLOPS per device: 6*N*D train, 2*N*D forward-only (D = tokens
    processed; decode D = global_batch tokens). MoE uses active params."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        toks = shape.global_batch * shape.seq_len
        total = 6.0 * n * toks
    elif shape.kind == "prefill":
        toks = shape.global_batch * shape.seq_len
        total = 2.0 * n * toks
    else:  # decode: one token per sequence
        total = 2.0 * n * shape.global_batch
    return total / n_devices


def recurrence_correction(cfg, shape, n_devices: int) -> tuple[float, float]:
    """Analytic FLOPs/bytes for nested-scan recurrences (RWKV WKV): counted
    once by cost_analysis even with the two-point method."""
    if not cfg.pattern or cfg.pattern[0] != "rwkv":
        return 0.0, 0.0
    if shape.kind == "decode":
        toks = shape.global_batch
    else:
        toks = shape.global_batch * shape.seq_len
    h, hd = cfg.rwkv_n_heads, cfg.rwkv_head_dim
    # per token per layer: kv outer (h*hd*hd) + state update (2x) + readout (2x)
    fl = 5.0 * h * hd * hd * toks * cfg.n_layers
    by = 2.0 * 4.0 * h * hd * hd * toks * cfg.n_layers  # state r/w fp32
    return fl / n_devices, by / n_devices
