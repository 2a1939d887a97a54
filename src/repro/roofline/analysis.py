"""Roofline-term extraction from compiled XLA artifacts.

Methodology (documented in EXPERIMENTS.md §Roofline):

  * `compiled.cost_analysis()` on the host backend reports PER-DEVICE flops
    and 'bytes accessed', and counts each while-loop body exactly ONCE. The
    roofline lowerings therefore UNROLL the block stack (model.UNROLL_BLOCKS)
    and the flash KV scan (flash ref UNROLL_SCANS) at two reduced depths;
    per-depth-unit cost is the difference, extrapolated to the full depth.
  * collective bytes are parsed from `compiled.as_text()`: the sum of
    result-shape bytes of all-gather / all-reduce / reduce-scatter /
    all-to-all / collective-permute ops (per device, matching cost_analysis;
    loop multiplicity handled by the same unroll+extrapolate scheme).
  * residual in-loop work that cannot be unrolled (xLSTM chunk/time scans)
    gets an explicit analytic correction (functions below), flagged in the
    output record.

Terms (seconds, per step, on the target chip counts):
  compute    = flops_per_device / PEAK_FLOPS_BF16
  memory     = bytes_per_device / HBM_BW
  collective = collective_bytes_per_device / ICI_BW
"""
from __future__ import annotations

import dataclasses
import re

from repro.roofline import hw

_COLL_RE = re.compile(
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\("
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
}


def _shape_bytes(shape_str: str) -> int:
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, int]:
    """Per-collective-kind result bytes (per device). Only counts ops in the
    entry/unrolled computations once each — callers ensure loop bodies are
    unrolled or corrected."""
    out: dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if not m or "-done" in line.split("=")[0]:
            continue
        kind = m.group(1)
        lhs = line.split("=", 1)[0]
        # result shape appears right after '=' : "%x = bf16[..] op(...)"
        rhs = line.split("=", 1)[1] if "=" in line else line
        shape_part = rhs.split(m.group(0))[0]
        out[kind] = out.get(kind, 0) + _shape_bytes(shape_part)
        del lhs
    return out


@dataclasses.dataclass
class CellCost:
    """Per-device, per-step costs at full depth."""
    flops: float
    bytes_hbm: float
    coll_bytes: float
    coll_breakdown: dict
    corrected: bool = False

    def terms(self):
        return {
            "compute_s": self.flops / hw.PEAK_FLOPS_BF16,
            "memory_s": self.bytes_hbm / hw.HBM_BW,
            "collective_s": self.coll_bytes / hw.ICI_BW,
        }

    def dominant(self):
        t = self.terms()
        return max(t, key=t.get)


def extract_costs(compiled) -> tuple[float, float, dict]:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    bytes_ = float(ca.get("bytes accessed", 0.0))
    colls = collective_bytes(compiled.as_text())
    return flops, bytes_, colls


def extrapolate(depth_costs: dict[int, tuple], full_units: float) -> CellCost:
    """depth_costs: {units: (flops, bytes, colls)} at two unrolled depths.
    Linear model cost(u) = base + u * per_unit, evaluated at full_units."""
    (u1, c1), (u2, c2) = sorted(depth_costs.items())
    assert u2 > u1

    def lin(v1, v2):
        per = (v2 - v1) / (u2 - u1)
        base = v1 - u1 * per
        return max(base + full_units * per, 0.0)

    flops = lin(c1[0], c2[0])
    bytes_ = lin(c1[1], c2[1])
    kinds = set(c1[2]) | set(c2[2])
    breakdown = {
        k: lin(c1[2].get(k, 0), c2[2].get(k, 0)) for k in kinds
    }
    return CellCost(flops, bytes_, sum(breakdown.values()), breakdown)


# ---------------------------------------------------------------------------
# analytic in-loop corrections (xLSTM cells only — see module docstring)
# ---------------------------------------------------------------------------


def mlstm_chunk_scan_correction(*, batch_per_dev, seq, heads, head_dim,
                                chunk, n_layers):
    """Per-device extra (flops, bytes) for the (nc-1) uncounted chunkwise
    mLSTM scan bodies per layer."""
    b, q, h, p = batch_per_dev, chunk, heads, head_dim
    nc = seq // chunk
    body_flops = 6 * b * q * q * h * p + 4 * b * q * h * p * p \
        + 6 * b * q * q * h
    body_bytes = 4 * (4 * b * q * h * p + 2 * b * h * p * p + b * q * q * h)
    extra = max(nc - 1, 0) * n_layers
    return body_flops * extra, body_bytes * extra


def slstm_time_scan_correction(*, batch_per_dev, seq, d_model, num_heads,
                               n_layers):
    """Per-device extra (flops, bytes) for the (S-1) uncounted sLSTM steps."""
    b, d = batch_per_dev, d_model
    body_flops = 8 * b * d * d // num_heads + 24 * b * d
    body_bytes = 4 * (8 * b * d)
    extra = max(seq - 1, 0) * n_layers
    return body_flops * extra, body_bytes * extra
