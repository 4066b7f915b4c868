"""Roofline analysis from compiled dry-run artifacts.

Three terms per (arch × shape × mesh), in seconds:

  compute    = HLO_FLOPs_per_device / peak_FLOP/s            (197e12 bf16)
  memory     = HLO_bytes_per_device / HBM_bw                  (819e9)
  collective = collective_bytes_per_device / ICI_bw           (50e9/link)

HLO_FLOPs / bytes come from compiled.cost_analysis() (the module is already
SPMD-partitioned, so the numbers are per device). collective_bytes is not in
cost_analysis — we parse the compiled HLO text, build a symbol table of
instruction result shapes, and sum *operand* bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.

MODEL_FLOPS is the classic 6·N·D (N = params, D = tokens; N_active for MoE)
— the "useful compute" yardstick; the ratio MODEL_FLOPS / HLO_FLOPs exposes
remat/redundancy waste.
"""
from __future__ import annotations

import re
from typing import Dict, Optional

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
    "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "u1": 1, "s1": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z]+[0-9]+(?:e[0-9a-z]+)?)\[([0-9,]*)\]")
_INSTR_RE = re.compile(r"^\s*(?:ROOT\s+)?(%?[\w\.\-]+)\s*=\s*(.*?)\s*"
                       r"([a-z][\w\-]*)\((.*)$")
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")


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


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum of operand bytes per collective kind, from compiled HLO text."""
    sizes: Dict[str, int] = {}
    pending = []
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if not m:
            continue
        name, type_str, op, rest = m.groups()
        sizes[name.lstrip("%")] = _shape_bytes(type_str)
        base_op = op.rstrip(".0123456789")
        if base_op.endswith("-start"):
            base_op = base_op[:-6]
        if base_op in _COLLECTIVES:
            operands = re.findall(r"%?([\w\.\-]+)", rest.split(")")[0])
            pending.append((base_op, operands))
    out = {k: 0 for k in _COLLECTIVES}
    for op, operands in pending:
        out[op] += sum(sizes.get(o, 0) for o in operands)
    return out


def model_flops(cfg, shape, n_params: int, n_active_params: Optional[int] = None
                ) -> float:
    """6·N·D for training, 2·N·D for inference forward-only."""
    n = n_active_params if n_active_params else n_params
    tokens = shape.global_batch * (shape.seq_len if shape.kind != "decode"
                                   else 1)
    mult = 6.0 if shape.kind == "train" else 2.0
    return mult * n * tokens


def active_params(cfg, n_params: int) -> int:
    """Rough active-parameter count for MoE archs (top-k of routed, at the
    nominal share of the held experts)."""
    if not cfg.moe:
        return n_params
    m = cfg.moe
    held = cfg.resolved_experts_held
    routed = ((cfg.n_layers - cfg.first_k_dense) * 3 * cfg.d_model
              * m.d_ff_expert * held)
    active_routed = routed * m.top_k / m.n_experts
    return int(n_params - routed + active_routed)


def roofline_terms(cost: dict, coll_bytes: int, n_chips: int) -> dict:
    """cost: compiled.cost_analysis() dict (per-device numbers)."""
    flops = float(cost.get("flops", 0.0))
    byts = float(cost.get("bytes accessed", 0.0))
    return {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": byts / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": byts,
        "collective_bytes_per_device": coll_bytes,
    }


def dominant_term(terms: dict) -> str:
    vals = {"compute": terms["compute_s"], "memory": terms["memory_s"],
            "collective": terms["collective_s"]}
    return max(vals, key=vals.get)


# ---------------------------------------------------------------------------
# Per-tile kernel arithmetic intensity (static, from the kernels' own block
# shapes — no compile needed). One grid step of each Pallas kernel moves
# `bytes` through VMEM and does `flops` MXU work; intensity = flops/byte
# against the machine ridge point PEAK/HBM_BW says which side of the
# roofline the kernel's inner loop sits on.
# ---------------------------------------------------------------------------

def _entry(name: str, flops: float, byts: float, note: str) -> dict:
    intensity = flops / byts
    ridge = PEAK_FLOPS_BF16 / HBM_BW
    return {"kernel": name, "tile_flops": flops, "tile_bytes": byts,
            "intensity": intensity, "ridge": ridge,
            "bound": "compute" if intensity >= ridge else "memory",
            "note": note}


def gemm_intensity(bm: int = 128, bk: int = 128, bn: int = 128,
                   itemsize: int = 4) -> dict:
    """One (bm, bk)×(bk, bn) tile of `local_step.matmul_blocked` (the
    im2col+GEMM local step): 2·bm·bk·bn FLOPs over A, B and the output
    accumulator tile."""
    flops = 2.0 * bm * bk * bn
    byts = float(bm * bk + bk * bn + bm * bn) * itemsize
    return _entry("gemm", flops, byts, f"bm={bm},bk={bk},bn={bn}")


def bgmv_intensity(block_n: int = 256, d_in: int = 2048, d_out: int = 2048,
                   r: int = 8, itemsize: int = 4) -> dict:
    """One (member, N-block) step of `bgmv.bgmv_pallas` (factored-serving
    correction): x(bn,d_in)@u(d_in,r) then @v(d_out,r)ᵀ —
    2·bn·r·(d_in+d_out) FLOPs over the x tile, both factor panels, and the
    (bn, d_out) output. At serving ranks (r ≪ d) the x/out tiles dominate
    bytes while FLOPs scale with r, so the kernel is memory-bound by
    design — it exists to cut the S× *weight* traffic of the dense
    vmapped ensemble, not to raise MXU utilization."""
    flops = 2.0 * block_n * r * (d_in + d_out)
    byts = float(block_n * d_in + d_in * r + d_out * r
                 + block_n * d_out) * itemsize
    return _entry("bgmv", flops, byts,
                  f"block_n={block_n},d_in={d_in},d_out={d_out},r={r}")


def kernel_intensities() -> list:
    """The repo's Pallas kernels at their default tile shapes — the
    EXPERIMENTS.md §Roofline kernel table (benchmarks/roofline_report.py
    prints and persists these rows)."""
    return [gemm_intensity(), bgmv_intensity()]
