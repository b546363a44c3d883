"""Roofline-term derivation from compiled dry-run artifacts.

Hardware model: the published per-chip peaks in :data:`PEAKS`, keyed by
``jax.Device.device_kind``. A device that is not in the table is an error
(:func:`chip_peaks`), never a default.

Terms (seconds, per step, per chip):
    compute    = HLO_FLOPs / (chips * PEAK)
    memory     = HLO_bytes / (chips * HBM_BW)
    collective = collective_bytes / (chips * ICI_BW)

``cost_analysis`` reports whole-program FLOPs/bytes (already partitioned —
the SPMD module is per-device, so no division by chips is applied to those
numbers; they ARE per-device). Collective bytes are parsed from the
optimized HLO text: operand bytes of every all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute, with while-loop bodies
(scanned layer groups) multiplied by their trip count.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional


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


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Published peaks of one chip; raises for a device not in PEAKS."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(PEAKS)})") from None


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


def _trip_count(body_text: str) -> Optional[int]:
    """Best-effort trip count from a while-loop condition constant."""
    m = re.search(r"compare\([^)]*\)[^\n]*direction=LT", body_text)
    return None


def collective_bytes(hlo_text: str, default_trip: int = 1) -> Dict[str, int]:
    """Sum collective operand bytes from optimized HLO text.

    Instructions inside computations whose name suggests a while body are
    multiplied by ``default_trip`` (callers pass the scanned layer count —
    the dominant loop in every model here).
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


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops: float                 # per-device HLO flops
    bytes_accessed: float        # per-device HLO bytes
    coll_bytes: float            # per-device collective bytes
    coll_breakdown: Dict[str, int]
    model_flops: float           # 6*N*D useful flops (global)
    device_kind: str             # chip the terms are priced on (PEAKS)

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def t_compute(self) -> float:
        return self.flops / self.peaks.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_accessed / self.peaks.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def t_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / (chips * HLO_FLOPs) — remat/redundancy waste."""
        total = self.flops * self.chips
        return self.model_flops / total if total else 0.0

    @property
    def mfu_bound(self) -> float:
        """Upper bound on achievable MFU given the dominant term."""
        if self.t_bound == 0:
            return 0.0
        return (self.model_flops / self.chips / self.peaks.flops) \
            / self.t_bound

    def row(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute,
            "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_flops_per_chip": self.flops,
            "hlo_bytes_per_chip": self.bytes_accessed,
            "coll_bytes_per_chip": self.coll_bytes,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "useful_ratio": self.useful_ratio,
            "mfu_bound": self.mfu_bound,
        }


def analytic_hbm_bytes(cfg, shape, chips: int, param_bytes: int,
                       opt_bytes: int = 0,
                       param_shards: int | None = None) -> float:
    """Per-chip HBM traffic of the schedule we actually lower (bytes/step).

    XLA's ``cost_analysis()['bytes accessed']`` cannot express our chunked
    attention/CE (loop bodies count once) and the dense probes overcount
    score traffic by the S/blk factor flash-style execution avoids, so the
    memory term is derived from the schedule itself:

    train:  3x param reads (fwd + remat recompute + bwd) + grad write/read
            + optimizer state read/write + param write
            + activation traffic (residual stream + block io, ~10 tensor
              passes per layer with remat)
            + flash KV re-reads (K,V once per query block)
            + chunked-CE logits write/read (fwd+bwd, chunk-local)
    prefill: 1x param read + activation writes + cache write
    decode:  1x param read + full cache read + one-position cache write
    """
    act = 2                                   # bf16 activations
    d = cfg.d_model
    L = cfg.n_layers
    tokens = shape.global_batch * shape.seq_len / chips
    # params fully sharded when training (FSDP); TP-only when serving
    shards = param_shards or chips
    pb = param_bytes / shards
    ob = opt_bytes / shards

    def attn_layers():
        return sum(reps * sum(1 for k, _ in unit
                              if k in ("global", "local", "mla"))
                   for unit, reps in cfg.layout)

    if shape.step == "train":
        traffic = 3 * pb + 2 * pb + 2 * ob + pb
        traffic += tokens * d * act * L * 10
        # flash KV re-reads: K/V row per query block
        nb = max(shape.seq_len // max(cfg.attn_chunk or shape.seq_len, 1),
                 1)
        kv_row = (cfg.kv_lora_rank + cfg.qk_rope_dim) if cfg.kv_lora_rank \
            else 2 * cfg.n_kv_heads * cfg.hd
        traffic += (shape.global_batch / chips) * shape.seq_len * kv_row \
            * act * attn_layers() * nb * 2          # fwd + bwd repass
        # chunked CE: logits written+read fwd, recomputed in bwd
        traffic += tokens * cfg.padded_vocab * act * 3
        return traffic
    if shape.step == "prefill":
        traffic = pb + tokens * d * act * L * 4
        traffic += tokens * cfg.padded_vocab * act / shape.seq_len  # last
        return traffic
    # decode: params once + cache read
    import jax as _jax
    import jax.numpy as _jnp
    import numpy as _np
    from repro.models.transformer import lm_cache_shapes
    cache = lm_cache_shapes(cfg, shape.global_batch, shape.seq_len,
                            _jnp.dtype(cfg.kv_dtype))
    cache_bytes = sum(int(_np.prod(leaf.shape)) * leaf.dtype.itemsize
                      for leaf in _jax.tree.leaves(cache))
    return pb + cache_bytes / chips * 1.02    # read all + write 1 position


def model_flops_estimate(cfg, shape) -> float:
    """6*N*D for training; 2*N_active*D for a forward; decode counts one
    token per sequence."""
    n_active = cfg.active_param_count()
    if shape.step == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.step == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention over the cache
    tokens = shape.global_batch
    flops = 2.0 * n_active * tokens
    # attention reads: 2 * cache_len * d per kv head pair ~ folded into
    # bytes, not FLOPs-dominant; keep parameter term.
    return flops
