"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Three terms per (arch x shape x mesh), all in seconds:

    compute    = HLO_FLOPs_per_device / peak_FLOP/s          (197e12 bf16)
    memory     = HLO_bytes_per_device / HBM_bw               (819e9 B/s)
    collective = wire_bytes_per_device / ICI_link_bw         (50e9 B/s)

``compiled.cost_analysis()`` is **per-device** for SPMD modules (verified
in-repo); collective bytes are parsed from the HLO text with a ring model:

    all-gather      out_bytes * (g-1)/g     (out = full gathered buffer)
    all-reduce      2 * bytes * (g-1)/g
    reduce-scatter  shard_bytes * (g-1)
    all-to-all      bytes * (g-1)/g
    collective-permute  bytes

XLA counts a while-loop body ONCE — scans would corrupt the terms.  Models
unroll their layer/chunk loops below a threshold; the remaining scans
(sLSTM time loop, long-sequence SSM chunk loops) are corrected via
*supplements*: the scan body is compiled standalone and its costs added
(trips-1) times (x3 for train cells: fwd+bwd ~ 3x fwd — documented
approximation, only affects scan-bound archs).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.configs.base import ModelConfig, ShapeCell
from repro.core.resource_model import TPU_V5E, HardwareSpec

__all__ = [
    "CollectiveOp", "parse_collectives", "wire_bytes_per_device",
    "roofline_terms", "model_flops", "RooflineRecord", "analyze_compiled",
]

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLLECTIVE_RE = re.compile(
    r"=\s+(?:\(([^)]*)\)|(\S+))\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(",
)
_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_GROUPS_BRACE_RE = re.compile(r"replica_groups=\{\{([^}]*)\}")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    result_bytes: int          # per-device result buffer bytes
    group_size: int
    wire_bytes: float          # modeled per-device wire traffic


def _shape_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def _group_size(line: str, default: int) -> int:
    m = _GROUPS_RE.search(line)
    if m:
        return int(m.group(2))           # [num_groups, group_size]
    m = _GROUPS_BRACE_RE.search(line)
    if m:
        return len(m.group(1).split(","))
    return default


def _wire(kind: str, nbytes: int, g: int) -> float:
    if g <= 1:
        return 0.0
    if kind == "all-gather":
        return nbytes * (g - 1) / g
    if kind == "all-reduce":
        return 2.0 * nbytes * (g - 1) / g
    if kind == "reduce-scatter":
        return float(nbytes) * (g - 1)
    if kind == "all-to-all":
        return nbytes * (g - 1) / g
    if kind == "collective-permute":
        return float(nbytes)
    return 0.0


def parse_collectives(hlo_text: str, default_group: int) -> List[CollectiveOp]:
    ops: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if not m:
            continue
        if "-done" in line:
            continue
        type_str = m.group(1) if m.group(1) is not None else m.group(2)
        kind = m.group(3)
        nbytes = _shape_bytes(type_str)
        g = _group_size(line, default_group)
        ops.append(CollectiveOp(kind, nbytes, g, _wire(kind, nbytes, g)))
    return ops


def wire_bytes_per_device(ops: List[CollectiveOp]) -> float:
    return float(sum(o.wire_bytes for o in ops))


def roofline_terms(
    flops_per_dev: float,
    bytes_per_dev: float,
    wire_per_dev: float,
    hw: HardwareSpec = TPU_V5E,
) -> Dict[str, float]:
    return {
        "compute_s": flops_per_dev / hw.peak_flops_bf16,
        "memory_s": bytes_per_dev / hw.hbm_bw,
        "collective_s": wire_per_dev / hw.ici_bw,
    }


def model_flops(cfg: ModelConfig, cell: ShapeCell) -> float:
    """Useful-model-FLOPs for the cell: 6·N·D train, 2·N·D prefill,
    2·N_active·B + KV-read flops for decode (N = active params for MoE)."""
    n_active = cfg.active_param_count()
    tokens = cell.global_batch * cell.seq_len
    if cell.kind == "train":
        return 6.0 * n_active * tokens
    if cell.kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence; attention reads the cache
    from repro.models.transformer import layer_specs

    attn_layers = sum(1 for s in layer_specs(cfg) if s.mixer == "attn")
    kv_len = min(cell.seq_len, cfg.window) if cfg.window else cell.seq_len
    attn_flops = (
        4.0 * cell.global_batch * cfg.n_heads * cfg.head_dim_() * kv_len * attn_layers
    )
    return 2.0 * n_active * cell.global_batch + attn_flops


@dataclasses.dataclass
class RooflineRecord:
    arch: str
    cell: str
    mesh: str
    chips: int
    flops_per_dev: float
    bytes_per_dev: float
    wire_per_dev: float
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops_total: float
    useful_ratio: float          # MODEL_FLOPS / (HLO_FLOPs * chips)
    collectives: Dict[str, int]
    memory_stats: Dict[str, float]
    supplements: Dict[str, float]

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


def analyze_compiled(
    compiled,
    cfg: ModelConfig,
    cell: ShapeCell,
    *,
    mesh_name: str,
    chips: int,
    default_group: int,
    supplements: Optional[Dict[str, float]] = None,
    hw: HardwareSpec = TPU_V5E,
) -> RooflineRecord:
    ca = compiled.cost_analysis()
    flops = float(ca.get("flops", 0.0))
    byts = float(ca.get("bytes accessed", 0.0))
    hlo = compiled.as_text()
    ops = parse_collectives(hlo, default_group)
    wire = wire_bytes_per_device(ops)

    supplements = supplements or {}
    flops += supplements.get("flops", 0.0)
    byts += supplements.get("bytes", 0.0)

    terms = roofline_terms(flops, byts, wire, hw)
    dominant = max(terms, key=terms.get).replace("_s", "")
    mf = model_flops(cfg, cell)
    ma = compiled.memory_analysis()
    mem = {
        "argument_gb": ma.argument_size_in_bytes / 1e9,
        "output_gb": ma.output_size_in_bytes / 1e9,
        "temp_gb": ma.temp_size_in_bytes / 1e9,
        "alias_gb": ma.alias_size_in_bytes / 1e9,
        "peak_gb": (ma.argument_size_in_bytes + ma.temp_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes) / 1e9,
    }
    counts: Dict[str, int] = {}
    for o in ops:
        counts[o.kind] = counts.get(o.kind, 0) + 1
    return RooflineRecord(
        arch=cfg.name,
        cell=cell.name,
        mesh=mesh_name,
        chips=chips,
        flops_per_dev=flops,
        bytes_per_dev=byts,
        wire_per_dev=wire,
        compute_s=terms["compute_s"],
        memory_s=terms["memory_s"],
        collective_s=terms["collective_s"],
        dominant=dominant,
        model_flops_total=mf,
        useful_ratio=mf / max(flops * chips, 1e-30),
        collectives=counts,
        memory_stats=mem,
        supplements=dict(supplements),
    )
