"""Roofline-term derivation from a compiled dry-run artifact.

Three terms, in seconds (v5e constants from launch.mesh):

    compute    = HLO_FLOPs_per_chip / PEAK_FLOPS_BF16
    memory     = HLO_bytes_per_chip / HBM_BW
    collective = sum_ops factor(op) * output_bytes(op) / ICI_BW

``cost_analysis()`` on the SPMD-partitioned executable reports the per-chip
program, so no further division by chip count is applied (verified against
the analytic 6*N*D/chips for yi-34b in EXPERIMENTS.md §Roofline).

Collective bytes are not in cost_analysis: we parse the compiled (post-SPMD)
HLO and sum output bytes of all-gather / all-reduce / reduce-scatter /
all-to-all / collective-permute ops. Ring-model factors: all-reduce counts
2x (reduce-scatter + all-gather phases); everything else 1x; the (n-1)/n
ring correction (~0.94-0.99 on 16-256 participants) is folded into 1.
"""
from __future__ import annotations

import re
from typing import Dict

from repro.launch.mesh import HBM_BW, ICI_BW, PEAK_FLOPS_BF16

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")
_FACTORS = {"all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
            "all-to-all": 1.0, "collective-permute": 1.0}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective op type from post-SPMD HLO."""
    out: Dict[str, int] = {op: 0 for op in _COLL_OPS}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if not stripped or "=" not in stripped:
            continue
        for op in _COLL_OPS:
            tok = f" {op}("
            tok_start = f" {op}-start("
            pos = stripped.find(tok)
            if pos < 0:
                pos = stripped.find(tok_start)
            if pos < 0:
                continue
            lhs = stripped[:pos]
            rhs_eq = lhs.find("=")
            shapes = _SHAPE_RE.findall(lhs[rhs_eq:])
            out[op] += sum(_shape_bytes(d, s) for d, s in shapes)
            break
    return out


def roofline_terms(flops: float, hbm_bytes: float,
                   coll: Dict[str, int]) -> Dict[str, float]:
    coll_s = sum(_FACTORS[op] * b for op, b in coll.items()) / ICI_BW
    return {
        "compute_s": flops / PEAK_FLOPS_BF16,
        "memory_s": hbm_bytes / HBM_BW,
        "collective_s": coll_s,
    }


def dominant(terms: Dict[str, float]) -> str:
    return max(("compute_s", "memory_s", "collective_s"),
               key=lambda k: terms[k])


def model_flops(cfg, tokens: int, train: bool) -> float:
    """6*N*D (training) or 2*N*D (inference fwd) with N = active non-embedding
    params (MoE counts top_k + shared experts only)."""
    n = cfg.param_count(active_only=True) - cfg.vocab_size * cfg.d_model
    mult = 6.0 if train else 2.0
    return mult * n * tokens
