"""Roofline terms of a compiled program's cost record.

Per (arch x shape x mesh) record:
  compute term    = HLO_FLOPs / peak_FLOP/s            (per-chip module)
  memory term     = HLO_bytes / HBM_bw                 (unfused upper bound)
  collective term = ring-weighted collective bytes / ICI_bw

plus MODEL_FLOPS = 6*N*D (training; 2*N_active*D_dec for decode) and the
useful-compute ratio MODEL_FLOPS / (HLO_FLOPs * chips).
"""
from __future__ import annotations

from typing import Dict

from repro.configs import ARCHS, INPUT_SHAPES
from repro.launch.mesh import HBM_BW, ICI_BW_PER_LINK, PEAK_FLOPS_BF16


def model_flops(arch: str, shape_name: str) -> float:
    """Analytic useful FLOPs for the whole step (all chips)."""
    cfg = ARCHS[arch]
    shape = INPUT_SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch


def analyze_record(rec: Dict, chips: int) -> Dict:
    cost = rec.get("cost", {})
    coll = rec.get("collectives", {})
    flops_dev = cost.get("flops", 0.0)
    bytes_dev = cost.get("bytes_accessed", 0.0)
    coll_dev = coll.get("traffic_weighted", 0.0)
    t_compute = flops_dev / PEAK_FLOPS_BF16
    t_memory = bytes_dev / HBM_BW
    t_coll = coll_dev / ICI_BW_PER_LINK
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(rec["arch"], rec["shape"])
    ratio = mf / max(flops_dev * chips, 1.0)
    return {
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf,
        "useful_ratio": round(ratio, 4),
        "bound_step_s": round(max(terms.values()), 6),
    }
