"""Topology recommendation — the paper's stated future work, implemented.

    "...build a system framework that can take the input of various
     configured runs, and recommend the optimal system level topology
     for AI and HPC workloads."  (paper §VI)

Two modes:

  * **measured** — given dry-run artifacts for several compositions
    (``dryrun.py --mesh-shape ...`` outputs), rank them by predicted
    step time (max of the roofline terms).
  * **analytic** — no artifacts needed: a closed-form wire model ranks
    candidate (dp, tp) factorizations of the chip budget.  The model is
    deliberately coarse (documented term by term below) but reproduces
    the measured ordering on every cell we profiled (§Perf): it exists
    to pre-screen compositions so only the top few need a compile.

Hard feasibility constraints (each learned from a measured regression):
  * ``batch % dp == 0``        — otherwise GSPMD replicates the batch
                                 (command-r prefill at (64,4): 9 s -> 87 s);
  * per-device memory estimate — params+opt shards, activations, caches
    must fit HBM;
  * MoE: ``n_experts % tp == 0`` for the EP layout.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs import get_config
from repro.configs.base import ModelConfig, PolicyConfig, ShapeConfig, SHAPES
from repro.core import costmodel
from repro.core.costmodel import CalibratedCost
from repro.core.topology import (DEFAULT_LINKS, ChipSpec, ICI_BW, LinkClass,
                                 Topology)


@dataclasses.dataclass
class Candidate:
    shape: Tuple[int, ...]            # (dp, tp) or (pod, dp, tp)
    step_s: float                     # predicted step time
    terms: Dict[str, float]
    feasible: bool
    why: str = ""
    # bytes crossing one device's link per step, per mesh axis — the input
    # to cluster-level per-link traffic accounting (repro.cluster.telemetry)
    wire_bytes: Dict[str, float] = dataclasses.field(default_factory=dict)

    @property
    def label(self) -> str:
        return "x".join(str(x) for x in self.shape)


# ---------------------------------------------------------------------------
# analytic wire model (coarse, per-device seconds)
# ---------------------------------------------------------------------------
def _estimate(cfg: ModelConfig, shape: ShapeConfig, dp: int, tp: int,
              pods: int = 1, chip: ChipSpec = ChipSpec(),
              dcn_bw: float = 6.25e9,
              topology: Optional[Topology] = None,
              domain_chips: int = 0) -> Candidate:
    n = pods * dp * tp
    B = shape.global_batch
    mesh_shape = (pods, dp, tp) if pods > 1 else (dp, tp)
    dp_total = pods * dp

    # -------- feasibility --------
    if B % dp_total:
        return Candidate(mesh_shape, float("inf"), {}, False,
                         f"batch {B} % dp {dp_total} != 0")
    if cfg.moe is not None and tp > 1 and cfg.moe.n_experts % tp:
        return Candidate(mesh_shape, float("inf"), {}, False,
                         f"experts {cfg.moe.n_experts} % tp {tp} != 0")

    P = cfg.param_count()
    serve = shape.kind != "train"
    pbytes = 2 if serve else 4
    # params per device: serve = TP-only; train = ZeRO-3 over dp x tp
    p_dev = P * pbytes / (tp if serve else n)
    opt_dev = 0 if serve else 2 * P * 4 / n
    S = 1 if shape.kind == "decode" else shape.seq_len
    T_loc = (B // dp_total) * S
    act_dev = 4 * T_loc * cfg.d_model * 2 * (2 if shape.kind == "train"
                                             else 1)
    kv = 2 * cfg.n_kv_heads * cfg.head_dim
    n_attn = sum(1 for b in cfg.pattern if b == "attn")
    cache_dev = (shape.seq_len * kv * n_attn * (B // dp_total) * 2 / tp
                 if shape.kind == "decode" else 0)
    mem = p_dev + opt_dev + act_dev + cache_dev
    if mem > chip.hbm_bytes * 0.95:
        return Candidate(mesh_shape, float("inf"), {}, False,
                         f"memory {mem/2**30:.1f} GiB > HBM")

    # -------- terms --------
    flops = costmodel.step_flops(cfg, shape, PolicyConfig())
    compute = flops / (n * chip.peak_flops_bf16)
    memory = costmodel.analytic_hbm_bytes(
        cfg, shape, PolicyConfig(
            dp_axes=("pod", "data") if pods > 1 else ("data",)),
        dict(zip(("pod", "data", "model") if pods > 1 else
                 ("data", "model"), mesh_shape))) / chip.hbm_bw

    passes = 3 if shape.kind == "train" else 1
    wire_dp = wire_tp = pod_wire = 0.0
    if shape.kind == "train":
        # ZeRO-3 param gathers (bf16 on the wire) + grad reduce
        wire_dp += passes * (n - 1) / n * P * 2
        wire_dp += 2 * (dp - 1) / dp * P * 2
    # row-parallel / EP activation reductions over tp per layer
    if tp > 1:
        n_red = 2 * cfg.n_layers * (3 if shape.kind == "train" else 1)
        wire_tp += n_red * 2 * (tp - 1) / tp * T_loc * cfg.d_model * 2
    coll = (wire_dp + wire_tp) / ICI_BW
    if topology is not None and domain_chips > 0:
        # multi-tier admission hint: a candidate whose per-pod mesh
        # cannot fit one drawer (``domain_chips`` chips) must span the
        # composed fabric — derate its collective term by the topology's
        # cross-drawer bandwidth scale and charge the extra hop latency,
        # so admission ranks drawer-sized candidates above spanning ones
        # *before* placement.  The flat single-switch topology passes no
        # hint (scale 1, 1 hop) and prices exactly the legacy estimate.
        n_local = dp * tp
        n_dom = -(-n_local // domain_chips)       # drawers spanned
        if n_dom > 1:
            span = n_dom - 1
            flows = min(domain_chips, n_local)
            scale = topology.bw_scale(LinkClass.SWITCH, span, flows)
            hops = topology.hops(LinkClass.SWITCH, span)
            coll = ((wire_dp + wire_tp) / (ICI_BW * max(scale, 1e-9))
                    + (hops - 1) * DEFAULT_LINKS[LinkClass.SWITCH].latency)
    if pods > 1 and shape.kind == "train":
        pod_wire = 2 * (pods - 1) / pods * P * 2 / dp   # hierarchical
        coll += pod_wire / dcn_bw

    step = max(compute, memory, coll)
    wire = {"data": wire_dp, "model": wire_tp}
    if pods > 1:
        wire["pod"] = pod_wire
    return Candidate(mesh_shape, step,
                     {"compute": compute, "memory": memory,
                      "collective": coll}, True, wire_bytes=wire)


# ---------------------------------------------------------------------------
# measured-cost calibration hook
# ---------------------------------------------------------------------------
_calibration: Optional[CalibratedCost] = None


def set_calibration(cal: Optional[CalibratedCost]) -> None:
    """Install a process-wide CalibratedCost; every ranking that is not
    handed an explicit one (recommend, scheduler admission, cluster
    simulator pricing) will layer it over the analytic terms."""
    global _calibration
    _calibration = cal


def get_calibration() -> Optional[CalibratedCost]:
    return _calibration


def calibrate_candidate(cand: Candidate, arch: str, shape_name: str,
                        cal: Optional[CalibratedCost]) -> Candidate:
    """Re-price one analytic candidate from a measured cell: its step is
    the measurement where one exists, analytic otherwise (and always for
    infeasible candidates)."""
    if cal is None or not cand.feasible:
        return cand
    measured = cal.step_override(arch, shape_name, cand.label)
    if measured is None:
        return cand
    terms = dict(cand.terms, measured=measured)
    return dataclasses.replace(cand, step_s=measured, terms=terms)


def candidates(n_chips: int = 256, pods: int = 1
               ) -> List[Tuple[int, int]]:
    out = []
    tp = 1
    while tp <= n_chips:
        if n_chips % tp == 0:
            out.append((n_chips // tp, tp))
        tp *= 2
    return out


def recommend(arch: str, shape_name: str, *, n_chips: int = 256,
              pods: int = 1, top: int = 3,
              calibration: Optional[CalibratedCost] = None,
              topology: Optional[Topology] = None,
              domain_chips: int = 0) -> List[Candidate]:
    """Analytic ranking of compositions for one workload.

    When a ``calibration`` layer is supplied (or installed process-wide
    via ``set_calibration``) the analytic terms are re-priced from
    measurements before ranking — measured cells override the whole
    step.  ``topology`` + ``domain_chips`` (chips per drawer) apply the
    multi-tier admission derate to candidates that must span drawers.
    """
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cal = calibration if calibration is not None else get_calibration()
    cands = [calibrate_candidate(
                 _estimate(cfg, shape, dp, tp, pods,
                           topology=topology, domain_chips=domain_chips),
                 arch, shape_name, cal)
             for dp, tp in candidates(n_chips, pods)]
    cands.sort(key=lambda c: c.step_s)
    return cands[:top]


def recommend_from_measurements(results_dirs: Sequence[str], arch: str,
                                shape_name: str) -> Optional[Candidate]:
    """Best measured composition among available dry-run artifacts."""
    best: Optional[Candidate] = None
    for d in results_dirs:
        for path in glob.glob(os.path.join(d, f"{arch}__{shape_name}__*.json")):
            with open(path) as f:
                js = json.load(f)
            rl = js["roofline"]
            c = Candidate(tuple(js["mesh"].values()), rl["step_time_s"],
                          {"compute": rl["compute_s"],
                           "memory": rl["memory_s"],
                           "collective": rl["collective_s"]}, True,
                          why=path)
            if best is None or c.step_s < best.step_s:
                best = c
    return best
