"""Roofline-term extraction from compiled dry-run artifacts.

Per (arch × shape × mesh):

    compute_s    = FLOPs_total      / (chips × peak FLOP/s)
    memory_s     = HBM_bytes_total  / (chips × HBM B/s)
    collective_s = wire_bytes_total / (chips × ICI link B/s)

The peaks come from :data:`PEAKS`, keyed by JAX's ``device_kind``; a
kind missing from the table is an error, never a default.

Two measurement sources are recorded side by side:

* **xla**: ``compiled.cost_analysis()`` — fused, but XLA:CPU counts each
  while-loop body ONCE (loop-blind; undercounts a 126-layer scan 126×).
* **jaxpr** (primary): the trip-count-exact walker in ``jaxpr_cost.py`` —
  exact matmul FLOPs (incl. remat recompute and causal-mask waste); bytes
  are a fusion-unaware upper bound.

Collective wire bytes come from the post-SPMD optimized HLO via the
call-graph walker in ``hlo_graph.py`` (loop-trip multiplied; all-reduce
counted 2× per the ring RS+AG wire model). HLO shapes are per-device shard
shapes, so per-device seconds fall out directly — equivalent to the
total/(chips×bw) formulation.

MODEL_FLOPS = 6·N·D (dense) / 6·N_active·D (MoE) per trained token;
2·N_active per prefill/decode token. ``useful_ratio`` =
MODEL_FLOPS / total jaxpr FLOPs — flags remat/causal/padding waste.
``peak_fraction`` = useful FLOP/s at the dominant-term step time vs peak.
"""
from __future__ import annotations

import dataclasses

from repro.launch.hlo_graph import collective_stats

__all__ = ["HW", "PEAKS", "peaks_for", "RooflineReport", "analyze",
           "model_flops_for_cell"]


@dataclasses.dataclass(frozen=True)
class HW:
    peak_flops: float               # bf16 FLOP/s per chip
    hbm_bw: float                   # HBM B/s per chip
    link_bw: float                  # B/s per ICI link


# published per-chip peaks by device_kind. TPU v5e ("TPU v5 lite"):
# Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s of
# HBM, 1,600 Gbit/s of interchip interconnect over 4 links
PEAKS: dict[str, HW] = {
    "TPU v5 lite": HW(peak_flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks_for(device_kind: str) -> HW:
    """The :data:`PEAKS` entry of ``device_kind``; raises for a kind
    without published peaks in the table."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks for device kind {device_kind!r}; add "
                       "its published peaks to PEAKS") from None


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # primary (jaxpr, trip-count-exact; FLOPs are global → /chips)
    flops_total: float
    bytes_total: float
    coll_wire_bytes_per_device: float
    compute_s: float
    memory_s: float
    collective_s: float
    bottleneck: str
    model_flops: float
    useful_ratio: float
    peak_fraction: float
    # secondary (xla per-device, loop-blind)
    xla_flops_per_device: float
    xla_bytes_per_device: float
    memory_stats: dict
    collectives: dict
    notes: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def model_flops_for_cell(cfg, shape) -> float:
    """Analytic useful FLOPs for one step of this cell."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        return 2.0 * n_active * tokens
    return 2.0 * n_active * shape.global_batch      # decode: 1 new token/seq


def analyze(arch: str, shape, mesh_name: str, chips: int, cost: dict,
            memory_stats: dict, hlo_text: str, cfg, jaxpr_stats: dict, *,
            device_kind: str, notes: str = "") -> RooflineReport:
    hw = peaks_for(device_kind)
    xla_flops_dev = float(cost.get("flops", 0.0))
    xla_bytes_dev = float(cost.get("bytes accessed", 0.0))
    flops_total = float(jaxpr_stats["flops"])
    jaxpr_bytes_ub = float(jaxpr_stats["bytes"])
    colls = collective_stats(hlo_text)
    # TPU-corrected wire bytes: XLA:CPU upconverts bf16 collectives to f32;
    # on the v5e target these move at bf16 width.
    wire_dev = float(colls["_total"].get("wire_bytes_tpu",
                                         colls["_total"]["wire_bytes"]))

    bytes_total = float(jaxpr_stats["bytes"])   # fusion-modelled

    compute_s = flops_total / (chips * hw.peak_flops)
    memory_s = bytes_total / (chips * hw.hbm_bw)
    collective_s = wire_dev / hw.link_bw            # already per-device
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    bottleneck = max(terms, key=terms.get)

    mflops = model_flops_for_cell(cfg, shape)
    useful = mflops / flops_total if flops_total else 0.0
    step_s = max(terms.values()) or 1e-30
    peak_fraction = (mflops / chips / step_s) / hw.peak_flops

    return RooflineReport(
        arch=arch, shape=shape.name, mesh=mesh_name, chips=chips,
        flops_total=flops_total, bytes_total=bytes_total,
        coll_wire_bytes_per_device=wire_dev,
        compute_s=compute_s, memory_s=memory_s, collective_s=collective_s,
        bottleneck=bottleneck, model_flops=mflops, useful_ratio=useful,
        peak_fraction=peak_fraction,
        xla_flops_per_device=xla_flops_dev,
        xla_bytes_per_device=xla_bytes_dev,
        memory_stats=memory_stats,
        collectives={k: v for k, v in colls.items() if k != "_loops"},
        notes=notes)
