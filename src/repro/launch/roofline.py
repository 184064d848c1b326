"""Roofline-term extraction from AOT-compiled artifacts.

Three terms per (arch x shape x mesh), per the assignment:

    compute    = HLO_FLOPs        / (chips * peak_FLOP/s)
    memory     = HLO_bytes        / (chips * HBM_bw)
    collective = collective_bytes / (chips * link_bw)

``compiled.cost_analysis()`` supplies FLOPs/bytes (per-device module —
multiplied back to global); collective bytes are parsed out of the
optimized HLO text (GSPMD-inserted all-gather / all-reduce /
reduce-scatter / all-to-all / collective-permute operand sizes).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.core.autotune import (  # noqa: F401  (re-exported table)
    HARDWARE_TABLE,
    TPU_V5E,
    HardwareModel,
    calibrate_from_bench,
    hardware_model,
)

# The dry-run's deployment chip is a TPU v5e: its row of the
# device_kind-keyed HARDWARE_TABLE (core/autotune, with the figures'
# source). The module-level aliases stay for existing callers (dryrun.py
# reads HBM_BYTES) and remain the default when a Roofline is built without
# an explicit hardware model.
_TPU = hardware_model(TPU_V5E)
PEAK_FLOPS_BF16 = _TPU.peak_flops_bf16  # FLOP/s
PEAK_FLOPS_INT8 = _TPU.peak_flops_int8
HBM_BW = _TPU.hbm_bw  # B/s
LINK_BW = _TPU.link_bw  # B/s per ICI link
HBM_BYTES = _TPU.hbm_bytes

_DTYPE_BYTES = {
    "pred": 1,
    "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16,
}

_COLLECTIVES = (
    "all-gather",
    "all-reduce",
    "reduce-scatter",
    "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"=\s*(\([^)]*\)|[\w\[\],{}\s]*?)\s*"
    r"(all-gather-start|all-gather|all-reduce-start|all-reduce|"
    r"reduce-scatter|all-to-all|collective-permute-start|collective-permute)\("
)


def _shape_bytes(type_str: str) -> int:
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> Dict[str, dict]:
    """Per-collective-kind {count, bytes} from optimized HLO. Bytes are the
    op *output* payload per device (all-reduce in == out; all-gather output
    is the gathered tensor; reduce-scatter output is the scattered shard)."""
    out: Dict[str, dict] = {}
    for m in _OP_RE.finditer(hlo_text):
        type_str, op = m.group(1), m.group(2)
        kind = op.replace("-start", "")
        b = _shape_bytes(type_str)
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def collective_wire_bytes(colls: Dict[str, dict], n_shards: int = 16) -> float:
    """Approximate per-device wire bytes using ring-algorithm factors:
    all-reduce moves ~2x payload, all-gather/reduce-scatter ~1x the full
    tensor, permute/all-to-all ~1x."""
    f = (n_shards - 1) / max(n_shards, 1)
    total = 0.0
    for kind, rec in colls.items():
        if kind == "all-reduce":
            total += 2 * f * rec["bytes"]
        elif kind == "all-gather":
            total += f * rec["bytes"]
        elif kind == "reduce-scatter":
            total += f * rec["bytes"] * n_shards
        else:
            total += rec["bytes"]
    return total


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float  # global
    hlo_bytes: float  # global HBM traffic
    collective_bytes: float  # global wire bytes
    model_flops: float
    compute_s: float = 0.0
    memory_s: float = 0.0
    collective_s: float = 0.0
    # Which hardware-table row (possibly bench-calibrated) the terms are
    # normalized against; None keeps the historical TPU-v5e defaults.
    hw: Optional[HardwareModel] = None

    def __post_init__(self):
        hw = self.hw or _TPU
        self.compute_s = self.hlo_flops / (self.chips * hw.peak_flops_bf16)
        self.memory_s = self.hlo_bytes / (self.chips * hw.hbm_bw)
        self.collective_s = self.collective_bytes / (self.chips * hw.link_bw)

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.compute_s,
            "memory": self.memory_s,
            "collective": self.collective_s,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step time: max of the three terms (perfect overlap)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flop_fraction(self) -> float:
        return self.model_flops / max(self.hlo_flops, 1.0)

    @property
    def mfu(self) -> float:
        """Model-FLOPs utilization at the roofline step time."""
        peak = (self.hw or _TPU).peak_flops_bf16
        return self.model_flops / (self.step_time_s * self.chips * peak + 1e-30)

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.chips,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "hlo_flops": self.hlo_flops,
            "useful_flop_fraction": self.useful_flop_fraction,
            "mfu_at_roofline": self.mfu,
        }


def model_flops(cfg, shape) -> float:
    """MODEL_FLOPS = 6*N*D (train) / 2*N*D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def extract_cost(compiled, chips: int) -> tuple[float, float]:
    """(global_flops, global_bytes) from compiled.cost_analysis().

    XLA reports the per-device (SPMD) module cost; scale by chip count.
    WARNING: while-loop bodies (lax.scan) are counted ONCE — prefer
    :func:`extract_cost_scan_aware` (launch/hlo_cost.py), which multiplies
    by the compiler-proven trip counts.
    """
    ca = compiled.cost_analysis()
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    flops = float(ca.get("flops", 0.0)) * chips
    bytes_ = float(ca.get("bytes accessed", 0.0)) * chips
    return flops, bytes_


def extract_cost_scan_aware(hlo_text: str, chips: int):
    """(global_flops, global_bytes, per_device_collectives) via the
    scan-aware HLO walker. Collectives are per-device {kind: {count,bytes}}
    with bytes = operand payload, matching parse_collectives()."""
    from repro.launch import hlo_cost

    cost = hlo_cost.analyze(hlo_text)
    colls = {
        k: {"count": int(v["count"]), "bytes": int(v["bytes"])}
        for k, v in sorted(cost.collectives.items())
    }
    return cost.flops * chips, cost.bytes * chips, colls
