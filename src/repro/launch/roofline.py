"""Roofline analysis from compiled dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell, three terms in seconds:

    compute    = HLO_FLOPs          / peak_FLOP/s          (per chip)
    memory     = HLO_bytes_accessed / HBM_bw               (per chip)
    collective = collective_bytes   / link_bw              (per chip)

``compiled.cost_analysis()`` is per-device (the SPMD-partitioned module),
so no further division by chip count is needed. Collective bytes come
from a textual parse of the post-partitioning HLO: the summed result
sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute (all-reduce counted 2× — ring = reduce-scatter +
all-gather). Hardware model: TPU v5e.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, Optional

# --- TPU v5e hardware model -----------------------------------------------
PEAK_FLOPS = 197e12          # bf16 FLOP/s per chip
HBM_BW = 819e9               # bytes/s per chip
ICI_BW = 50e9                # bytes/s per link (~ per-chip collective bw)
HBM_PER_CHIP = 16e9          # bytes

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
                "collective-permute")
# an HLO op line: "%name = <shape-or-tuple> opcode(...)"
_OP_RE = re.compile(
    r"=\s*((?:\([^)]*\))|(?:[\w\[\],{}/#\s]*?))\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(shape_text: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(shape_text):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_stats(hlo_text: str) -> Dict[str, Any]:
    """Sum result bytes per collective kind from post-SPMD HLO text."""
    by_kind: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    counts: Dict[str, int] = {k: 0 for k in _COLLECTIVES}
    for m in _OP_RE.finditer(hlo_text):
        shape_text, kind = m.group(1), m.group(2)
        b = _shape_bytes(shape_text)
        by_kind[kind] += b
        counts[kind] += 1
    # '-done' ops repeat the '-start' shape; halve pairs
    for kind in _COLLECTIVES:
        starts = len(re.findall(kind + r"-start\(", hlo_text))
        if starts:
            by_kind[kind] = by_kind[kind] * starts // max(counts[kind], 1)
            counts[kind] = starts
    total = sum(by_kind.values()) + by_kind["all-reduce"]  # AR counts 2×
    return {"bytes_by_kind": by_kind, "counts": counts,
            "effective_bytes": total}


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    kind: str
    chips: int
    flops: float               # per-chip HLO FLOPs
    hbm_bytes: float           # per-chip bytes accessed
    coll_bytes: float          # per-chip effective collective bytes
    coll_detail: Dict[str, Any]
    model_flops: float         # 6·N·D (train) or 2·N_active·tokens (decode)
    peak_mem_bytes: Optional[float] = None

    @property
    def t_compute(self) -> float:
        return self.flops / PEAK_FLOPS

    @property
    def t_memory(self) -> float:
        return self.hbm_bytes / HBM_BW

    @property
    def t_collective(self) -> float:
        return self.coll_bytes / ICI_BW

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_frac(self) -> float:
        """MODEL_FLOPS / (chips × HLO_FLOPs) — remat/redundancy waste."""
        denom = self.chips * self.flops
        return self.model_flops / denom if denom else 0.0

    @property
    def roofline_frac(self) -> float:
        """Useful-FLOPs time over the bound step time (≈ achievable MFU)."""
        bound = max(self.t_compute, self.t_memory, self.t_collective)
        if bound <= 0:
            return 0.0
        t_useful = self.model_flops / (self.chips * PEAK_FLOPS)
        return t_useful / bound

    def to_dict(self) -> Dict[str, Any]:
        return {
            **{f.name: getattr(self, f.name)
               for f in dataclasses.fields(self) if f.name != "coll_detail"},
            "coll_detail": self.coll_detail,
            "t_compute": self.t_compute,
            "t_memory": self.t_memory,
            "t_collective": self.t_collective,
            "bottleneck": self.bottleneck,
            "useful_flops_frac": self.useful_flops_frac,
            "roofline_frac": self.roofline_frac,
        }


def model_flops_for(cfg, shape) -> float:
    """Useful-work FLOPs for one step of this cell."""
    n_active = cfg.n_active_params()
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * tokens
    # decode: one token per sequence + attention reads over the cache
    return 2.0 * n_active * shape.global_batch


def analyze(compiled, cfg, shape, mesh_name: str, chips: int,
            arch: str) -> Roofline:
    from repro.launch.hlo_cost import analyze_text
    cost = compiled.cost_analysis()
    text = compiled.as_text()
    # while-aware accounting (XLA's cost_analysis counts loop bodies once;
    # see launch.hlo_cost) — the XLA numbers ride along for reference
    hc = analyze_text(text)
    flops = float(hc["flops"])
    hbm = float(hc["bytes"])
    coll = {"bytes_by_kind": hc["coll_by_kind"],
            "effective_bytes": hc["collective_bytes"],
            "xla_flops_once": float(cost.get("flops", 0.0)),
            "xla_bytes_once": float(cost.get("bytes accessed", 0.0))}
    mem = None
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            mem = float(
                getattr(ma, "temp_size_in_bytes", 0)
                + getattr(ma, "argument_size_in_bytes", 0)
                + getattr(ma, "output_size_in_bytes", 0)
                - getattr(ma, "alias_size_in_bytes", 0))
    except Exception:
        pass
    return Roofline(
        arch=arch, shape=shape.name, mesh=mesh_name, kind=shape.kind,
        chips=chips, flops=flops, hbm_bytes=hbm,
        coll_bytes=float(coll["effective_bytes"]), coll_detail=coll,
        model_flops=model_flops_for(cfg, shape), peak_mem_bytes=mem)


def save(r: Roofline, path: str) -> None:
    with open(path, "w") as f:
        json.dump(r.to_dict(), f, indent=1)
