"""Integrity gate over the shipped dry-run artifacts (results/).

These are the §Dry-run / §Roofline deliverables; the suite fails if the
artifact set regresses (missing cells, OOM cells, malformed reports).
"""
import glob
import json
import os

import pytest

from repro.configs import ASSIGNED_ARCHS, applicable_shapes, get_config

RESULTS_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "results")
RESULTS = os.path.join(RESULTS_DIR, "dryrun")

needs_dryrun = pytest.mark.skipif(
    not os.path.isdir(RESULTS),
    reason="dry-run artifacts not generated (run repro.launch.dryrun)")

HBM = 16 * 2 ** 30


def _cells():
    out = []
    for arch in ASSIGNED_ARCHS:
        for s in applicable_shapes(get_config(arch)):
            for mesh in ("single", "multi"):
                out.append((arch, s.name, mesh))
    return out


@needs_dryrun
def test_every_assigned_cell_has_an_artifact():
    missing = [c for c in _cells()
               if not os.path.exists(os.path.join(
                   RESULTS, f"{c[0]}__{c[1]}__{c[2]}.json"))]
    assert not missing, missing
    assert len(_cells()) == 64


@needs_dryrun
@pytest.mark.parametrize("path", sorted(glob.glob(
    os.path.join(RESULTS, "*.json"))))
def test_artifact_well_formed_and_fits_hbm(path):
    with open(path) as f:
        js = json.load(f)
    rl = js["roofline"]
    for k in ("compute_s", "memory_s", "collective_s", "dominant",
              "roofline_fraction", "useful_ratio", "step_time_s"):
        assert k in rl, (path, k)
    assert rl["step_time_s"] >= max(rl["compute_s"], rl["collective_s"]) \
        - 1e-12
    assert 0 <= rl["roofline_fraction"] <= 1.0 + 1e-9
    # argument bytes per device must fit the 16 GiB HBM
    args = js["memory_analysis"].get("argument_size_in_bytes", 0)
    assert args <= HBM, (path, args / 2**30)
    # mesh coherence
    n = 1
    for v in js["mesh"].values():
        n *= v
    assert n in (256, 512)


@needs_dryrun
def test_multi_pod_cells_exercise_the_pod_axis():
    """At least the training cells must put traffic on the pod (DCN) axis
    — that is what the multi-pod dry-run proves."""
    hits = 0
    for path in glob.glob(os.path.join(RESULTS, "*train_4k__multi.json")):
        with open(path) as f:
            js = json.load(f)
        if js["per_axis_wire_bytes"].get("pod", 0) > 0:
            hits += 1
    assert hits >= 8, hits


# ---------------------------------------------------------------------------
# cluster-sim artifact (results/cluster_sim.json)
# ---------------------------------------------------------------------------
CLUSTER_SIM = os.path.join(RESULTS_DIR, "cluster_sim.json")

_SIM_REPORT_KEYS = ("span_s", "pool_utilization", "auu",
                    "accelerator_utilization", "link_traffic_gb",
                    "recomposition", "job_wait_s", "jobs", "gangs",
                    "fairness", "lease_conflicts", "storage", "policy",
                    "faults")


@pytest.mark.skipif(
    not os.path.exists(CLUSTER_SIM),
    reason="cluster_sim artifact not generated "
           "(run benchmarks/run.py --bench cluster_sim)")
def test_cluster_sim_artifact_schema():
    with open(CLUSTER_SIM) as f:
        js = json.load(f)
    assert js["bench"] == "cluster_sim"
    # base trace: the PR-1 regression anchor stays healthy
    jobs = js["jobs"]
    assert jobs["completed"] + jobs["rejected"] == jobs["submitted"]
    assert jobs["stranded"] == 0
    assert jobs["failed"] == 0                  # no faults in the base trace
    assert js["lease_conflicts"] == 0
    assert js["faults"]["injected"] == 0
    # per-policy sweep: every policy ran the gang scenario
    assert set(js["policies"]) == {"easy", "fair_share", "priority_preempt"}
    for name, rep in js["policies"].items():
        for k in _SIM_REPORT_KEYS:
            assert k in rep, (name, k)
        assert rep["policy"] == name
        assert rep["gangs"]["started"] >= 1, name
        assert rep["jobs"]["stranded"] == 0, name
        ten = rep["fairness"]["tenants"]
        assert set(ten) >= {"heavy", "blue", "green", "gang"}, name
        for row in ten.values():
            for q in ("p50", "p95", "p99", "mean"):
                assert row["wait_s"][q] >= 0
    # acceptance: the headline policy claims hold in the shipped artifact
    acc = js["acceptance"]
    assert acc["fair_share_improves_tenant_p95_wait"] is True
    assert acc["fair_share_tenant_p95_wait_mean_s"] < \
        acc["easy_tenant_p95_wait_mean_s"]
    assert acc["priority_preempt_evictions"] >= 1
    assert acc["priority_preempt_starts_gang_sooner"] is True
    assert all(n >= 1 for n in acc["gangs_started_per_policy"].values())


# ---------------------------------------------------------------------------
# serving benchmark artifact (results/serve_bench.json)
# ---------------------------------------------------------------------------
SERVE_BENCH = os.path.join(RESULTS_DIR, "serve_bench.json")

_DIST_KEYS = ("p50", "p99", "mean")
_SCENARIO_KEYS = ("requests", "ttft_s", "tpot_s", "queue_wait_s",
                  "slo_attainment", "throughput_tok_s", "cache_hit_rate",
                  "output_tokens")


@pytest.mark.skipif(
    not os.path.exists(SERVE_BENCH),
    reason="serve_bench artifact not generated "
           "(run benchmarks/run.py --bench serve_bench)")
def test_serve_bench_artifact_schema():
    with open(SERVE_BENCH) as f:
        js = json.load(f)
    assert js["bench"] == "serve_bench"
    # engine layer: burst/paced plus the continuous-batching comparison
    assert set(js["engine"]) >= {"burst", "paced", "burst_unfused"}
    for name, sc in js["engine"].items():
        for k in _SCENARIO_KEYS:
            assert k in sc, (name, k)
        for dist in ("ttft_s", "tpot_s", "queue_wait_s"):
            for q in _DIST_KEYS:
                assert sc[dist][q] >= 0, (name, dist, q)
        assert sc["requests"]["completed"] == sc["requests"]["submitted"]
        assert sc["compile_s"] >= 0           # warmup reported separately
        kv = sc["kv_pages"]
        assert 0.0 <= kv["hit_rate"] <= 1.0
        assert kv["in_use"] == 0              # all pages recycled
        assert 0.0 < kv["peak_utilization"] <= 1.0
        assert 0.0 < kv["mean_utilization"] <= kv["peak_utilization"]
    assert js["engine"]["burst"]["fused"] is True
    assert js["engine"]["burst_unfused"]["fused"] is False
    # the headline: continuous batching takes burst SLO attainment to ~1
    assert js["engine"]["burst"]["slo_attainment"] >= 0.9
    # cluster layer: ServeJob replicas simulated alongside training jobs
    assert set(js["cluster"]) >= {"poisson", "burst",
                                  "overload_fixed_2x",
                                  "overload_autoscale_2x"}
    for name, sc in js["cluster"].items():
        if not name.startswith("overload"):
            jobs = sc["jobs"]
            assert jobs["completed"] + jobs["rejected"] == jobs["submitted"]
        for svc in sc["serving"].values():
            assert svc["requests"]["stranded"] == 0
            assert svc["ttft_s"]["p99"] > 0
            assert svc["tpot_s"]["p50"] > 0
            assert svc["throughput_tok_s"] > 0
            assert len(svc["replicas"]) >= 1
            for row in svc["replicas"].values():
                assert "cache_hit_rate" in row
                assert 0.0 <= row["cache_hit_rate"] <= 1.0
    # SLO-driven autoscaling: grows under load, beats the fixed fleet
    fixed = js["cluster"]["overload_fixed_2x"]["serving"]["chat"]
    auto = js["cluster"]["overload_autoscale_2x"]["serving"]["chat"]
    assert "autoscale" not in fixed
    scale = auto["autoscale"]
    assert scale["scale_ups"] >= 1
    assert scale["peak_replicas"] > 1
    assert len(scale["windows"]) >= 1
    assert auto["slo_attainment"] >= fixed["slo_attainment"]
    assert auto["ttft_s"]["p99"] <= fixed["ttft_s"]["p99"]


# ---------------------------------------------------------------------------
# chaos benchmark artifact (results/chaos_bench.json)
# ---------------------------------------------------------------------------
CHAOS_BENCH = os.path.join(RESULTS_DIR, "chaos_bench.json")


@pytest.mark.skipif(
    not os.path.exists(CHAOS_BENCH),
    reason="chaos_bench artifact not generated "
           "(run benchmarks/run.py --bench chaos_bench)")
def test_chaos_bench_artifact_schema():
    with open(CHAOS_BENCH) as f:
        js = json.load(f)
    assert js["bench"] == "chaos_bench"
    # the fault plane must be free when unused
    assert js["baseline_identical"] is True
    assert 0.0 <= js["availability"] <= 1.0
    assert 0.0 <= js["goodput_fraction"] <= 1.0
    assert js["recovery"]["samples"] >= 1
    assert js["recovery"]["p95_s"] >= js["recovery"]["mean_s"] - 1e-9
    assert set(js["scenarios"]) >= {"domain_outage", "degradation", "churn"}
    for name, sc in js["scenarios"].items():
        jobs = sc["jobs"]
        assert (jobs["completed"] + jobs["rejected"] + jobs["failed"]
                == jobs["submitted"]), name
        assert jobs["stranded"] == 0, name
        assert sc["faults"]["injected"] >= 1, name
    acc = js["acceptance"]
    assert acc["outage_availability_above_0_9"] is True
    assert acc["outage_all_jobs_recovered"] is True
    assert acc["degradation_graceful"] is True
    assert acc["serve_failed_rate_below_1pct"] is True
    assert acc["serve_unbounded_without_retries"] is True
    # the serve comparison: resilience on beats resilience off
    sv = js["serve"]
    assert sv["resilient"]["failed_request_rate"] < 0.01
    assert (sv["no_retries"]["failed_request_rate"]
            > sv["resilient"]["failed_request_rate"]
            or sv["no_resilience"]["requests"]["stranded"] > 0)


# ---------------------------------------------------------------------------
# fabric-topology scaling artifact (results/fabric_bench.json)
# ---------------------------------------------------------------------------
FABRIC_BENCH = os.path.join(RESULTS_DIR, "fabric_bench.json")


@pytest.mark.skipif(
    not os.path.exists(FABRIC_BENCH),
    reason="fabric_bench artifact not generated "
           "(run benchmarks/run.py --bench fabric_bench)")
def test_fabric_bench_artifact_schema():
    with open(FABRIC_BENCH) as f:
        js = json.load(f)
    assert js["bench"] == "fabric_bench"
    assert set(js["curves"]) == {"single_switch", "pcie_cascade",
                                 "oversubscribed_spine"}
    sizes = js["config"]["sizes"]
    for name, curve in js["curves"].items():
        assert [p["devices"] for p in curve] == sizes, name
        for p in curve:
            assert p["step_s"] > 0
            assert 0.0 < p["efficiency"] <= 1.0 + 1e-9, (name, p)
            assert set(p["axis_links"]) == set(p["axis_hops"]) \
                == set(p["axis_bw_scale"])
        # efficiency at the smallest size is 1.0 by construction
        assert curve[0]["efficiency"] == pytest.approx(1.0)
    acc = js["acceptance"]
    assert acc["single_switch_matches_flat_model"] is True
    assert acc["oversub_knee_ge_10pct"] is True
    assert acc["oversub_knee_drop_32"] >= 0.10
    assert acc["cross_domain_never_beats_dcn"] is True
    # the spine degrades fastest: its 32-device efficiency trails both
    eff32 = {n: c[-1]["efficiency"] for n, c in js["curves"].items()}
    assert eff32["oversubscribed_spine"] <= eff32["pcie_cascade"] \
        <= eff32["single_switch"]


# ---------------------------------------------------------------------------
# storage benchmark artifact (results/storage_bench.json)
# ---------------------------------------------------------------------------
STORAGE_BENCH = os.path.join(RESULTS_DIR, "storage_bench.json")

_TRANCHE_KEYS = ("attach", "leases_granted", "peak_lessees", "mean_lessees",
                 "read_gb", "write_gb", "input_stall_s")


@pytest.mark.skipif(
    not os.path.exists(STORAGE_BENCH),
    reason="storage_bench artifact not generated "
           "(run benchmarks/run.py --bench storage_bench)")
def test_storage_bench_artifact_schema():
    with open(STORAGE_BENCH) as f:
        js = json.load(f)
    assert js["bench"] == "storage_bench"
    # analytic sweep: 1..4 tenants, shared-switch vs local-per-tenant
    sweep = js["sweep"]
    assert set(sweep) >= {f"tenants_{n}" for n in (1, 2, 3, 4)}
    for name, row in sweep.items():
        for side in ("shared_switch", "local_per_tenant"):
            assert row[side]["input_stall_s"] >= 0
            assert row[side]["per_tenant_read_bw_gbps"] > 0
        assert row["contention_slowdown"] >= 1.0 - 1e-9
    # contention must be visible from 2 tenants on
    assert sweep["tenants_2"]["shared_switch"]["input_stall_s"] > \
        sweep["tenants_2"]["local_per_tenant"]["input_stall_s"]
    assert sweep["tenants_4"]["contention_slowdown"] > \
        sweep["tenants_2"]["contention_slowdown"]
    # cluster layer: the simulator shows the same ordering end-to-end
    cl = js["cluster"]
    assert cl["n_tenants"] >= 2
    for side in ("shared_switch_tranche", "separate_local_tranches"):
        sc = cl[side]
        jobs = sc["jobs"]
        assert jobs["completed"] + jobs["rejected"] == jobs["submitted"]
        assert sc["storage"], side
        for st in sc["storage"].values():
            for k in _TRANCHE_KEYS:
                assert k in st, (side, k)
    acc = cl["acceptance"]
    assert acc["contention_visible"] is True
    assert acc["shared_stall_s"] > acc["separate_stall_s"]


# ---------------------------------------------------------------------------
# schema_version + run provenance (the tracking plane's artifact stamp)
# ---------------------------------------------------------------------------
def _shipped_results():
    return sorted(glob.glob(os.path.join(RESULTS_DIR, "*.json")))


@pytest.mark.parametrize("path", _shipped_results() or
                         [pytest.param("", marks=pytest.mark.skip(
                             reason="no shipped results/*.json"))])
def test_every_result_artifact_is_schema_versioned(path):
    with open(path) as f:
        js = json.load(f)
    assert js.get("schema_version") == 1, os.path.basename(path)


@pytest.mark.parametrize("bench", ["cluster_sim", "serve_bench",
                                   "storage_bench", "chaos_bench",
                                   "fabric_bench"])
def test_bench_artifacts_record_their_run_id(bench):
    path = os.path.join(RESULTS_DIR, f"{bench}.json")
    if not os.path.exists(path):
        pytest.skip(f"{bench} artifact not generated")
    with open(path) as f:
        js = json.load(f)
    assert js["run_id"].startswith(f"{bench}-"), js["run_id"]


@pytest.mark.skipif(
    not os.path.exists(CLUSTER_SIM),
    reason="cluster_sim artifact not generated")
def test_cluster_sim_reports_eviction_suppression_telemetry():
    with open(CLUSTER_SIM) as f:
        js = json.load(f)
    assert js["jobs"]["evictions_suppressed"] >= 0
    for name, rep in js["policies"].items():
        assert "evictions_suppressed" in rep["jobs"], name


# ---------------------------------------------------------------------------
# BENCH_<bench>.json perf trajectories (docs/tracking.md)
# ---------------------------------------------------------------------------
def _trajectories():
    return sorted(glob.glob(os.path.join(RESULTS_DIR, "BENCH_*.json")))


@pytest.mark.parametrize("path", _trajectories() or
                         [pytest.param("", marks=pytest.mark.skip(
                             reason="no BENCH_*.json trajectories shipped"))])
def test_bench_trajectory_schema(path):
    with open(path) as f:
        js = json.load(f)
    fname = os.path.basename(path)
    assert js["schema_version"] == 1
    assert fname == f"BENCH_{js['bench']}.json"
    assert js["baseline_run_id"] is None or \
        isinstance(js["baseline_run_id"], str)
    assert js["metrics"], fname
    for name, spec in js["metrics"].items():
        assert spec["direction"] in ("up", "down", "info"), (fname, name)
    assert js["rows"], f"{fname}: trajectory shipped with no baseline row"
    gated = {k for k, m in js["metrics"].items()
             if m["direction"] in ("up", "down")}
    for row in js["rows"]:
        assert row["run_id"] and row["ts"] > 0
        assert "git_sha" in row
        missing = gated - set(row["metrics"])
        assert not missing, (fname, row["run_id"], missing)
        for v in row["metrics"].values():
            assert isinstance(v, (int, float)), (fname, row["run_id"])
    # run ids are unique (appends are idempotent per run id)
    ids = [r["run_id"] for r in js["rows"]]
    assert len(ids) == len(set(ids)), fname


@pytest.mark.parametrize("bench", ["cluster_sim", "serve_bench",
                                   "storage_bench", "chaos_bench",
                                   "fabric_bench"])
def test_each_shipped_bench_has_a_seeded_trajectory(bench):
    art = os.path.join(RESULTS_DIR, f"{bench}.json")
    traj = os.path.join(RESULTS_DIR, f"BENCH_{bench}.json")
    if not os.path.exists(art):
        pytest.skip(f"{bench} artifact not generated")
    assert os.path.exists(traj), \
        f"{bench}.json shipped without its BENCH_{bench}.json trajectory"
    with open(art) as f:
        run_id = json.load(f)["run_id"]
    with open(traj) as f:
        rows = json.load(f)["rows"]
    # the artifact's producing run appears in its own trajectory
    assert any(r["run_id"] == run_id for r in rows)


def test_shipped_trajectories_pass_the_perf_gate():
    if not _trajectories():
        pytest.skip("no BENCH_*.json trajectories shipped")
    from repro.tracking import gate, trajectory
    for path in _trajectories():
        verdicts = gate.check_trajectory(trajectory.load(path))
        bad = [v for v in verdicts if v.regressed]
        assert not bad, gate.format_table(bad)
