"""Three-term roofline analysis from compiled dry-run artifacts.

    compute term    = HLO_FLOPs    / (chips x peak_FLOP/s)
    memory term     = HLO_bytes    / (chips x HBM_bw)
    collective term = coll_bytes   / (chips x link_bw)

``cost_analysis()`` of a GSPMD-partitioned module reports *per-device*
numbers; we rescale to global (x chips) so the formulas above apply as
written.  Collective bytes are not in cost_analysis — we parse the
optimized HLO and sum the result-shape bytes of every all-gather /
all-reduce / reduce-scatter / all-to-all / collective-permute.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, Optional

from repro.core.hardware import TPU_ICI_BW, TPU_V5E

# the dry-run targets the v5e production meshes, whatever device it runs on
PEAK_FLOPS = TPU_V5E.peak_flops     # bf16 FLOP/s per chip
HBM_BW = TPU_V5E.hbm_bandwidth      # bytes/s per chip
LINK_BW = TPU_ICI_BW                # bytes/s per ICI link (HW has multiple
                                    # links; we charge one — conservative)

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "s32": 4, "u32": 4, "s64": 8, "u64": 8,
    "f8e4m3fn": 1, "f8e5m2": 1, "bf16": 2, "f16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "token": 0,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# e.g.:  %all-gather.3 = bf16[16,512,128]{...} all-gather(...)
_SHAPE_RE = re.compile(
    r"=\s*(?:\()?\s*([a-z0-9]+)\[([0-9,]*)\][^=]*?\b(" +
    "|".join(_COLLECTIVES) + r")(?:-start|-done)?\(")


def _shape_bytes(dtype: str, dims: str) -> float:
    b = _DTYPE_BYTES.get(dtype)
    if b is None:
        return 0.0
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n * b)


def collective_bytes_from_hlo(hlo_text: str) -> Dict[str, float]:
    """Per-opcode result bytes of collectives in the (per-device) module."""
    out: Dict[str, float] = {c: 0.0 for c in _COLLECTIVES}
    for m in _SHAPE_RE.finditer(hlo_text):
        dtype, dims, op = m.group(1), m.group(2), m.group(3)
        # "-done" ops repeat the "-start" shape; count each pair once
        if "-done(" in m.group(0):
            continue
        out[op] += _shape_bytes(dtype, dims)
    return out


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # global quantities
    hlo_flops: float
    hlo_bytes: float
    collective_bytes: float
    per_op_collectives: Dict[str, float]
    model_flops: float
    # terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    def row(self) -> Dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "hlo_flops": self.hlo_flops, "hlo_bytes": self.hlo_bytes,
            "collective_bytes": self.collective_bytes,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "per_op_collectives": self.per_op_collectives,
        }


def model_flops(param_count: float, tokens: float, *, active_params:
                Optional[float] = None, train: bool = True) -> float:
    """6*N*D (dense) / 6*N_active*D (MoE); 2*N*D for inference."""
    n = active_params if active_params is not None else param_count
    return (6.0 if train else 2.0) * n * tokens


def roofline_report(*, arch: str, shape: str, mesh_name: str, chips: int,
                    cost_analysis: Dict[str, float], hlo_text: str,
                    model_flops_global: float) -> RooflineReport:
    per_dev_flops = float(cost_analysis.get("flops", 0.0))
    per_dev_bytes = float(cost_analysis.get("bytes accessed", 0.0))
    colls = collective_bytes_from_hlo(hlo_text)
    per_dev_coll = sum(colls.values())

    g_flops = per_dev_flops * chips
    g_bytes = per_dev_bytes * chips
    g_coll = per_dev_coll * chips
    return RooflineReport(
        arch=arch, shape=shape, mesh=mesh_name, chips=chips,
        hlo_flops=g_flops, hlo_bytes=g_bytes, collective_bytes=g_coll,
        per_op_collectives=colls, model_flops=model_flops_global,
        t_compute=g_flops / (chips * PEAK_FLOPS),
        t_memory=g_bytes / (chips * HBM_BW),
        t_collective=g_coll / (chips * LINK_BW),
    )


# ---------------------------------------------------------------------------
# modeled HBM traffic + residency (TPU-fused estimate)
# ---------------------------------------------------------------------------
# XLA:CPU's "bytes accessed" counts every unfused op's operands, a gross
# upper bound on TPU HBM traffic after fusion.  The dry-run therefore also
# reports a MODELED memory term from the same analytic layer workloads the
# paper's estimator uses: weights touched per pass, optimizer state traffic,
# and activation stash/reload.  Both numbers appear in EXPERIMENTS.md; the
# bottleneck verdict uses the modeled one.

@dataclasses.dataclass
class MemoryModel:
    traffic_bytes_per_device: float     # HBM bytes moved per step per chip
    resident_bytes_per_device: float    # persistent + peak stash per chip
    fits: bool

    def t_memory(self) -> float:
        return self.traffic_bytes_per_device / HBM_BW


def modeled_memory(specs, *, mode: str, chips: int, tp: int,
                   data_shards: int, remat: bool,
                   batch: int, cache_bytes_total: float = 0.0,
                   hbm_capacity: float = 16e9,
                   seq_shard: int = 1) -> MemoryModel:
    """specs: LayerSpec list (full model).  batch: global batch (sequences);
    cache_bytes_total: global KV/SSM cache bytes (decode modes);
    seq_shard: sequence-parallel factor on the stashed activations
    (Megatron-style; 1 = paper-faithful baseline)."""
    n_params = sum(s.param_count for s in specs)
    n_active = sum(s.active_param_count() for s in specs)
    b_dev = batch / data_shards
    act_dev = sum((s.bnd_bytes_per_sample + s.int_bytes_per_sample)
                  for s in specs) * b_dev / seq_shard
    bnd_dev = sum(s.bnd_bytes_per_sample for s in specs) * b_dev / seq_shard

    w_pass = 2.0 * n_params / tp          # bf16 weights touched, TP-sharded
    opt_dev = 16.0 * n_params / chips     # mixed-precision Adam states
    cache_dev = cache_bytes_total / chips

    if mode == "train":
        # fwd read + bwd (dx, dw) reads + recompute read; opt read+write;
        # activation stash write+read (+ recompute rewrite under remat)
        traffic = 4.0 * w_pass + 2.0 * opt_dev
        traffic += (3.0 * bnd_dev + 2.0 * act_dev) if remat else 2.0 * act_dev
        resident = 2.0 * n_params / chips + opt_dev \
            + (bnd_dev if remat else act_dev)
    elif mode == "prefill":
        traffic = 2.0 * n_active / tp + 2.0 * act_dev
        resident = 2.0 * n_params / tp + act_dev / len(specs)  # one layer live
    else:  # decode
        traffic = 2.0 * n_active / tp + 2.0 * cache_dev
        resident = 2.0 * n_params / tp + cache_dev
    return MemoryModel(
        traffic_bytes_per_device=traffic,
        resident_bytes_per_device=resident,
        fits=resident <= hbm_capacity,
    )
