"""Cost-model unit tests: HLO parsing, trip counts, roofline pricing."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, SHAPES
from repro.configs.base import PolicyConfig, ShapeConfig
from repro.core import costmodel, compose


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------
SAMPLE_HLO = """
HloModule jit_step

%region_0.10 (a: f32[], b: f32[]) -> f32[] {
  ROOT %add = f32[] add(f32[] %a, f32[] %b)
}

%cond.5 (arg: (s32[], f32[128])) -> pred[] {
  %c = s32[] constant(48)
  ROOT %lt = pred[] compare(s32[] %x, s32[] %c), direction=LT
}

%body.7 (arg: (s32[], f32[128])) -> (s32[], f32[128]) {
  %ar = f32[128]{0} all-reduce(f32[128]{0} %g), channel_id=2, replica_groups={{0,1,2,3},{4,5,6,7}}, use_global_device_ids=true, to_apply=%region_0.10
  ROOT %t = (s32[], f32[128]) tuple(%i, %ar)
}

ENTRY %main (p: f32[512,256]) -> f32[512,256] {
  %ag = f32[512,256]{1,0} all-gather(f32[32,256]{1,0} %p), channel_id=1, replica_groups=[16,16]<=[256], dimensions={0}, use_global_device_ids=true
  %w = (s32[], f32[128]) while((s32[], f32[128]) %init), condition=%cond.5, body=%body.7
  ROOT %r = f32[512,256]{1,0} copy(%ag)
}
"""


def test_parse_collectives_and_trip_counts():
    mesh_axes = {"data": 16, "model": 16}
    ops = costmodel.parse_hlo_collectives(SAMPLE_HLO, mesh_axes)
    kinds = {o.kind for o in ops}
    assert kinds == {"all-gather", "all-reduce"}
    ar = next(o for o in ops if o.kind == "all-reduce")
    ag = next(o for o in ops if o.kind == "all-gather")
    # the all-reduce sits in a while body with trip count 48
    assert ar.trip_count == 48
    assert ag.trip_count == 1
    # group {0..3} varies only the model (innermost) axis
    assert ar.axes == ("model",)
    # iota groups [16,16]<=[256]: 16 consecutive ids -> model axis
    assert ag.axes == ("model",)
    # wire bytes: all-reduce 2(n-1)/n * payload * trips
    assert math.isclose(ar.wire_bytes,
                        2 * 3 / 4 * 128 * 4 * 48, rel_tol=1e-6)


def test_iota_replica_group_transpose():
    hlo = """
ENTRY %main (p: f32[64]) -> f32[64] {
  ROOT %ar = f32[64]{0} all-reduce(f32[64]{0} %p), replica_groups=[16,16]<=[16,16]T(1,0), to_apply=%add
}
"""
    ops = costmodel.parse_hlo_collectives(hlo, {"data": 16, "model": 16})
    assert len(ops) == 1
    # transposed iota: groups stride 16 -> data (outer) axis
    assert ops[0].axes == ("data",)


def test_shape_bytes_tuple():
    assert costmodel._shape_bytes("(f32[128], bf16[64,2])") == \
        128 * 4 + 64 * 2 * 2


# ---------------------------------------------------------------------------
# analytic FLOPs vs XLA cost analysis (single device, no sharding)
# ---------------------------------------------------------------------------
def test_analytic_flops_close_to_hlo_on_dense_matmul():
    """XLA's flops for a pure matmul == 2*M*N*K; our conventions match."""
    M, N, K = 128, 256, 512
    f = jax.jit(lambda a, b: a @ b)
    lowered = f.lower(jax.ShapeDtypeStruct((M, K), jnp.float32),
                      jax.ShapeDtypeStruct((K, N), jnp.float32))
    ca = lowered.compile().cost_analysis()
    ca = ca[0] if isinstance(ca, (list, tuple)) else ca
    assert math.isclose(float(ca["flops"]), 2 * M * N * K, rel_tol=0.01)


def test_model_flops_6nd():
    cfg = get_config("llama3.2-3b")
    sh = SHAPES["train_4k"]
    mf = costmodel.model_flops(cfg, sh)
    assert math.isclose(mf, 6 * cfg.active_param_count() * sh.tokens,
                        rel_tol=1e-9)


def test_step_flops_remat_multiplier():
    cfg = get_config("qwen2-0.5b")
    sh = SHAPES["train_4k"]
    p_none = PolicyConfig(remat="none")
    p_blk = PolicyConfig(remat="block")
    f0 = costmodel.step_flops(cfg, sh, p_none)
    f1 = costmodel.step_flops(cfg, sh, p_blk)
    assert math.isclose(f1 / f0, 4.0 / 3.0, rel_tol=1e-9)


# ---------------------------------------------------------------------------
# fabric pricing reproduces the paper's orderings
# ---------------------------------------------------------------------------
def _report_with_collectives(frac_collective: float) -> costmodel.CostReport:
    r = costmodel.CostReport(
        arch="x", shape="train_4k", mesh={"data": 16, "model": 16},
        flops_hlo=1e12, flops_analytic=256e12, model_flops=200e12,
        hbm_bytes=1e9, peak_memory=None)
    wire = frac_collective * 1e9
    r.collectives = [costmodel.CollectiveOp("all-reduce", wire, 16,
                                            ("data",))]
    return r


def test_fabric_pricing_order_local_hybrid_falcon():
    """Fig 11's ordering: localGPUs <= hybridGPUs <= falconGPUs, and the
    overhead grows with communication fraction (model size proxy)."""
    systems = {name: compose.preset(name)
               for name in ("localGPUs", "hybridGPUs", "falconGPUs")}
    small = costmodel.price_on_fabrics(_report_with_collectives(0.1),
                                       systems, overlap=0.0)
    large = costmodel.price_on_fabrics(_report_with_collectives(30.0),
                                       systems, overlap=0.0)
    assert small["localGPUs"] <= small["hybridGPUs"] + 1e-12
    assert small["hybridGPUs"] <= small["falconGPUs"] + 1e-12
    ovh_small = small["falconGPUs"] / small["localGPUs"]
    ovh_large = large["falconGPUs"] / large["localGPUs"]
    assert ovh_large > ovh_small          # overhead grows with comm volume


def test_roofline_dominant_term():
    sys_ = compose.preset("localGPUs")
    r = _report_with_collectives(1e5)     # huge collective volume
    rl = costmodel.roofline(r, sys_)
    assert rl.dominant == "collective"
    assert rl.collective_s > rl.compute_s
    r2 = _report_with_collectives(0.0)
    rl2 = costmodel.roofline(r2, sys_)
    assert rl2.dominant in ("compute", "memory")


# ---------------------------------------------------------------------------
# measured-cost calibration: a measured cell replaces the analytic step
# ---------------------------------------------------------------------------
def test_calibrated_utilization_stays_bounded():
    """A measured cell far below the analytic compute bound must not
    push busy fractions past 1 (review regression: AUU went negative)."""
    from repro.cluster import TraceConfig, run_trace
    from repro.core import recommend
    cal = costmodel.CalibratedCost()
    plan = recommend.recommend("qwen2-0.5b", "train_4k", n_chips=16,
                               top=1)[0]
    cal.measure_cell("qwen2-0.5b", "train_4k", plan.label,
                     plan.step_s / 100.0)
    rep = run_trace(TraceConfig(n_jobs=8, seed=2, calibration=cal))
    assert 0.0 <= rep["auu"] <= 1.0
    assert rep["accelerator_utilization"] <= 1.0


def test_calibration_changes_recommend_ranking():
    """A measured step time for a non-winning mesh must be able to
    re-rank recommend()."""
    from repro.core import recommend
    arch, shape, chips = "qwen2-0.5b", "train_4k", 64
    plain = recommend.recommend(arch, shape, n_chips=chips, top=2)
    winner, runner_up = plain[0], plain[1]
    cal = costmodel.CalibratedCost()
    # measurement says the analytic runner-up actually runs 10x faster
    cal.measure_cell(arch, shape, runner_up.label, winner.step_s / 10.0)
    cald = recommend.recommend(arch, shape, n_chips=chips, top=2,
                               calibration=cal)
    assert cald[0].label == runner_up.label
    assert cald[0].label != plain[0].label
    assert cald[0].terms.get("measured") == pytest.approx(
        winner.step_s / 10.0)
    # the cell with no measurement keeps its analytic step and terms
    other = [c for c in cald if c.label == winner.label]
    assert other and other[0].step_s == winner.step_s
    assert other[0].terms == winner.terms


def test_set_calibration_reaches_existing_scheduler():
    """Process-wide set_calibration() must be honored by schedulers
    built before the call (review regression: construction-time
    snapshot)."""
    from repro.cluster.scheduler import Scheduler
    from repro.core import recommend
    from repro.core.topology import make_pool
    sched = Scheduler(make_pool(n_local=8, n_switch=0, pods=1))
    assert sched.calibration is None
    cal = costmodel.CalibratedCost(
        cell_step_s={"qwen2-0.5b|train_4k|8x1": 0.5})
    recommend.set_calibration(cal)
    try:
        assert sched.calibration is cal
    finally:
        recommend.set_calibration(None)
    assert sched.calibration is None


def test_calibration_flows_into_scheduler_admission_pricing():
    """The scheduler's plan (and therefore simulator pricing) uses the
    measured step time, changing which mesh a job is admitted on."""
    from repro.cluster.scheduler import Job, Scheduler
    from repro.core import recommend
    from repro.core.topology import make_pool

    def best_plan(calibration):
        pool = make_pool(n_local=64, n_switch=0, pods=1)
        sched = Scheduler(pool, calibration=calibration)
        job = Job(name="j", arch="qwen2-0.5b", shape_name="train_4k",
                  n_chips=64)
        assert sched.submit(job, 0.0)
        return job.plan

    plain = best_plan(None)
    cal = costmodel.CalibratedCost()
    # measure a different factorization as dramatically faster
    alt = [c for c in recommend.recommend(
        "qwen2-0.5b", "train_4k", n_chips=64, top=5)
        if c.label != plain.label][0]
    cal.measure_cell("qwen2-0.5b", "train_4k", alt.label,
                     plain.step_s / 100.0)
    cald = best_plan(cal)
    assert cald.label == alt.label
    assert cald.label != plain.label


def test_calibration_json_round_trip():
    cal = costmodel.CalibratedCost()
    assert not cal
    cal.measure_cell("mamba2-780m", "train_4k", "16x4", 1.25)
    assert cal
    js = cal.to_json()
    assert js == {"cell_step_s": {"mamba2-780m|train_4k|16x4": 1.25}}
    back = costmodel.CalibratedCost.from_json(js)
    assert back == cal
    assert back.step_override("mamba2-780m", "train_4k", "16x4") == 1.25
    assert back.step_override("mamba2-780m", "train_4k", "8x8") is None
