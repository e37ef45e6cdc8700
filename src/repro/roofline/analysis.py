"""Three-term roofline from a compiled (dry-run) artifact.

    compute term    = HLO_FLOPs / peak_FLOP/s            (per chip)
    memory term     = HLO_bytes / HBM_bw                 (per chip)
    collective term = collective_bytes / ICI link bw     (per chip)

FLOPs/bytes come from ``compiled.cost_analysis()`` on the SPMD-partitioned
module (already per-device). collective_bytes is NOT in cost_analysis: we
parse ``compiled.as_text()`` (post-partitioner HLO, real collectives with
per-device shapes) and sum operand sizes per collective op, weighted by the
ring-algorithm transfer factor:

    all-gather          : output bytes       (each chip receives the gather)
    reduce-scatter      : operand bytes
    all-reduce          : 2 x operand        (ring = RS + AG)
    all-to-all          : operand bytes
    collective-permute  : operand bytes
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.roofline.hw import HW, TPU_V5E

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_SHAPE_RE = re.compile(r"\b(" + "|".join(_DTYPE_BYTES) + r")\[([0-9,]*)\]")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")
# "%all-reduce.17 = f32[...] all-reduce(" -> opcode after " = type "
_OP_RE = re.compile(
    r"=\s+(?:\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start|-done)?\(")


def _shape_bytes(m: re.Match) -> int:
    dt, dims = m.group(1), m.group(2)
    n = _DTYPE_BYTES[dt]
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-op-type byte totals + the weighted per-chip transfer estimate."""
    out = {op: 0.0 for op in _COLLECTIVES}
    counts = {op: 0 for op in _COLLECTIVES}
    weighted = 0.0
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        op = m.group(1)
        if "-done(" in line:        # async pair: count the -start only
            continue
        shapes = list(_SHAPE_RE.finditer(line))
        if not shapes:
            continue
        split = m.start(1)          # opcode position: before = output types
        out_shapes = [s for s in shapes if s.start() < split]
        operand_shapes = [s for s in shapes if s.start() >= split]
        out_b = sum(_shape_bytes(s) for s in out_shapes)
        opr_b = sum(_shape_bytes(s) for s in operand_shapes)
        counts[op] += 1
        out[op] += opr_b
        if op == "all-gather":
            weighted += out_b
        elif op == "all-reduce":
            weighted += 2 * opr_b
        else:
            weighted += opr_b
    return {"per_op_operand_bytes": out, "counts": counts,
            "collective_bytes": weighted}


@dataclasses.dataclass
class RooflineReport:
    name: str
    flops: float                    # per device
    bytes_accessed: float           # per device
    collective_bytes: float         # per device (weighted)
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    model_flops: float = 0.0        # 6ND / 2ND useful-work estimate
    useful_frac: float = 0.0        # model_flops / (flops * chips)
    collective_counts: Optional[Dict[str, int]] = None
    peak_memory_bytes: Optional[float] = None

    def as_dict(self) -> Dict:
        return dataclasses.asdict(self)


def roofline_from_compiled(name: str, compiled, *, chips: int,
                           model_flops: float = 0.0,
                           analytic_bytes: float = 0.0,
                           hw: HW = TPU_V5E) -> RooflineReport:
    """Three-term roofline.

    flops + collective bytes come from the trip-count-aware HLO walk
    (hlo_cost.py) -- ``cost_analysis()`` counts scan bodies once and
    under-reports 61--96-layer models by ~2 orders of magnitude. The
    memory term uses max(cost_analysis bytes, analytic steady-state
    traffic / chips): fusion-level traffic is not recoverable from HLO
    text, and the analytic term (weights + cache + optimizer) is the
    dependable lower bound at scale.
    """
    from repro.roofline.hlo_cost import walk_costs
    cost = compiled.cost_analysis()
    walk = walk_costs(compiled.as_text())
    flops = float(walk["flops"])
    byts = max(float(cost.get("bytes accessed", 0.0)),
               analytic_bytes / max(chips, 1))
    compute_s = flops / hw.peak_flops_bf16
    memory_s = byts / hw.hbm_bw
    collective_s = float(walk["collective_bytes"]) / hw.ici_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    peak = None
    try:
        ma = compiled.memory_analysis()
        peak = float(ma.temp_size_in_bytes + ma.argument_size_in_bytes
                     + ma.output_size_in_bytes - ma.alias_size_in_bytes)
    except Exception:
        pass
    return RooflineReport(
        name=name, flops=flops, bytes_accessed=byts,
        collective_bytes=float(walk["collective_bytes"]),
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        dominant=dominant, model_flops=model_flops,
        useful_frac=(model_flops / (flops * chips)) if flops else 0.0,
        collective_counts=walk["collective_counts"],
        peak_memory_bytes=peak)


def model_flops_estimate(cfg, shape_cfg) -> float:
    """6·N·D (train) or 2·N·D (inference), N = active params."""
    n = cfg.active_param_count()
    if shape_cfg.kind == "train":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 6.0 * n * tokens
    if shape_cfg.kind == "prefill":
        tokens = shape_cfg.global_batch * shape_cfg.seq_len
        return 2.0 * n * tokens
    # decode: one token per request
    return 2.0 * n * shape_cfg.global_batch
