"""Roofline-term extraction from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), all in seconds-per-step on the
TPU v5e target (spec §Roofline):

  compute    = per-device HLO FLOPs / 197 TFLOP/s
  memory     = per-device HLO bytes accessed / 819 GB/s
  collective = per-device collective operand bytes / 50 GB/s link

``cost_analysis()`` supplies FLOPs + bytes of the (already SPMD-
partitioned, per-device) module.  Collective bytes are NOT in
cost_analysis — we parse the optimized HLO: build a name → byte-size map
from every instruction definition, then sum the *operand* sizes of each
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

MODEL_FLOPS uses the 6·N·D (train) / 2·N·D (inference) convention with
N = active parameters (MoE-aware); the ratio MODEL_FLOPS/HLO_FLOPs shows
how much compiled compute is "useful" (catches remat/redundancy waste).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.launch import hw

__all__ = ["collective_bytes", "RooflineReport", "analyze"]

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16,
}

# "bf16[128,4096]{1,0}" or "f32[]" — one typed buffer
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
# instruction definition: "  %name = <type> op(...)" or "  name = ..."
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")


def _shape_bytes(type_str: str) -> int:
    """Bytes of one (possibly tuple) HLO type string."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue                     # token/opaque types
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Per-collective-kind operand bytes summed over the module."""
    # pass 1: instruction name -> result byte size
    sizes: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        m = _DEF_RE.match(line)
        if not m:
            continue
        name, rhs = m.groups()
        # the type annotation is the prefix of rhs up to the opcode
        tm = _SHAPE_RE.match(rhs) or _SHAPE_RE.search(rhs.split(" ")[0])
        if tm is None:
            continue
        # result type may be a tuple "(f32[..], f32[..])"
        head = rhs.split(")")[0] + ")" if rhs.startswith("(") else \
            rhs.split(" ")[0]
        sizes[name] = _shape_bytes(head)

    # pass 2: for each collective, sum operand sizes
    out = {k: 0 for k in _COLLECTIVES}
    op_re = re.compile(
        r"=\s*(?:\([^=]*\)|\S+)\s+(" + "|".join(_COLLECTIVES)
        + r")(?:-start|-done)?\(([^)]*)\)")
    for line in hlo_text.splitlines():
        m = op_re.search(line)
        if not m:
            continue
        kind, operand_str = m.groups()
        if "-done(" in line:
            continue                     # avoid double counting async pairs
        n = 0
        for tok in operand_str.split(","):
            tok = tok.strip().lstrip("%")
            if tok in sizes:
                n += sizes[tok]
            else:
                n += _shape_bytes(tok)   # inline-typed operand
        out[kind] += n
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: Dict[str, int]
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float               # MODEL_FLOPS / (HLO_FLOPs * chips)
    memory_analysis: dict
    tokens: int
    meta: dict

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops(cfg, kind: str, tokens: int) -> float:
    """6·N_active·tokens (train) / 2·N_active·tokens (inference)."""
    n = cfg.active_param_count()
    return (6.0 if kind == "train" else 2.0) * n * tokens


def analyze(*, arch: str, shape: str, mesh_name: str, chips: int,
            cost: dict, hlo_text: str, mem: dict, cfg, kind: str,
            tokens: int, meta: Optional[dict] = None) -> RooflineReport:
    meta = meta or {}
    # Loop-cost corrections (EXPERIMENTS.md §Dry-run): HLO cost analysis
    # counts while bodies once.  Stage scans are lowered fully unrolled;
    # the grad-accumulation loop multiplies everything but the optimizer
    # update; time-step scans (mamba/sLSTM) get analytic add-ons.
    mult = float(meta.get("loop_multiplier", 1))
    deduct = float(meta.get("loop_flops_deduct", 0.0))
    scan_fix = float(meta.get("scan_flops_correction", 0.0))
    fscale = float(meta.get("flops_scale", 1.0))
    flops = (float(cost.get("flops", 0.0)) * mult - deduct) * fscale \
        + scan_fix
    byts = float(cost.get("bytes accessed", 0.0)) * mult
    coll = collective_bytes(hlo_text)
    coll = {k: int(v * mult) for k, v in coll.items()}
    coll_total = float(sum(coll.values()))
    t_c = flops / hw.PEAK_FLOPS_BF16
    t_m = byts / hw.HBM_BW
    t_x = coll_total / hw.ICI_BW
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    mf = model_flops(cfg, kind, tokens)
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        flops_per_device=flops, bytes_per_device=byts,
        coll_bytes_per_device=coll_total, coll_breakdown=coll,
        t_compute=t_c, t_memory=t_m, t_collective=t_x,
        bottleneck=max(terms, key=terms.get),
        model_flops=mf,
        useful_ratio=mf / max(flops * chips, 1.0),
        memory_analysis=mem, tokens=tokens, meta=meta or {},
    )
