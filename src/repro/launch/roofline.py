"""Hardware model for roofline rows: published chip peaks and HLO
collective bytes.

:data:`PEAKS` holds the published peaks of each chip, keyed by
``jax.Device.device_kind``; a device that is not in the table has no
peaks, never a default. :func:`collective_bytes` parses optimized HLO
text for the operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute. Both price the engine
entry points in ``benchmarks/roofline_table.py``.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops: float                # bf16 FLOP/s per chip
    hbm_bw: float               # HBM bytes/s per chip
    ici_bw: float               # interconnect bytes/s per link
    source: str


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM,
# 1,600 Gbit/s interchip interconnect (200 GB/s over four links).
V5E = "TPU v5 lite"
PEAKS = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9,
                   source='Google Cloud documentation, "TPU v5e"'),
}


_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Bytes of one HLO shape string like 'f32[128,1024]' or a tuple."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str, default_trip: int = 1) -> Dict[str, int]:
    """Sum collective operand bytes from optimized HLO text.

    Instructions inside computations whose name suggests a while body are
    multiplied by ``default_trip`` (the loop's trip count, when the caller
    knows it).
    """
    per_op: Dict[str, int] = {c: 0 for c in _COLLECTIVES}
    current_mult = 1
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # computation headers look like: %name (args) -> type {  /  ENTRY..
        if (stripped.startswith("%") or stripped.startswith("ENTRY")) \
                and stripped.endswith("{"):
            lname = stripped.lower()
            current_mult = default_trip if (
                "while" in lname or "body" in lname
                or "scan" in lname) else 1
            continue
        for c in _COLLECTIVES:
            if f" {c}(" in stripped or f"= {c}" in stripped \
                    or stripped.startswith(c) or f"{c}-start" in stripped:
                # output shape is on the lhs: %x = TYPE collective(...)
                lhs = stripped.split("=", 1)
                shape_part = lhs[1] if len(lhs) == 2 else stripped
                b = _shape_bytes(shape_part.split("(", 1)[0])
                if b == 0:
                    b = _shape_bytes(shape_part)
                per_op[c] += b * current_mult
                break
    return per_op
