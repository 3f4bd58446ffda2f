"""Tracking-plane coverage: run streams, trajectories, and the perf gate.

Pins the tentpole contracts of the ``repro.tracking`` plane:

  * JSONL round-trip — every record kind survives a write/read cycle
    with ``schema_version`` stamped and steps monotonic;
  * deterministic run ids under clock + seed injection;
  * trajectory appends are idempotent per run id and atomic;
  * the gate passes inside the noise band, catches a 20% regression in
    either direction, and never gates ``info`` metrics;
  * ``scripts/check_perf.py`` exits 0 on a healthy history, non-zero on
    a regression (naming the metric), and its ``--demo-regression``
    self-test passes.
"""
import json
import os
import subprocess
import sys

import pytest

import repro.tracking as tracking
from repro.tracking import gate, trajectory

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHECK_PERF = os.path.join(ROOT, "scripts", "check_perf.py")


def _clock(t0=1_754_000_000.0, dt=1.0):
    """Deterministic injectable clock: t0, t0+dt, t0+2dt, ..."""
    state = {"n": -1}

    def tick():
        state["n"] += 1
        return t0 + state["n"] * dt
    return tick


# ---------------------------------------------------------------------------
# run ids + event stream round-trip
# ---------------------------------------------------------------------------
def test_run_id_deterministic_under_seed():
    a = tracking.make_run_id("cluster_sim", 1_754_000_000.0, seed=7)
    b = tracking.make_run_id("cluster_sim", 1_754_000_000.0, seed=7)
    assert a == b
    assert a.startswith("cluster_sim-")
    assert tracking.make_run_id("cluster_sim", 1_754_000_000.0, seed=8) != a
    # slashes/spaces never leak into the directory name
    assert "/" not in tracking.make_run_id("a/b c", 0.0, seed=1)


def test_event_stream_roundtrip(tmp_path):
    run = tracking.Run("demo", config={"lr": 3e-4}, tags=("t1",),
                       dir=str(tmp_path), run_id="demo-0", sha="abc1234",
                       clock=_clock())
    run.log({"loss": 2.5})
    run.log({"loss": 2.1}, step=5)
    run.log_event("evict", {"job": "j0"}, sim_t=12.5)
    run.log_system({"sim.auu": 0.4})
    run.log_summary({"final_loss": 2.1})
    run.finish()
    events = tracking.read_events(run.path)
    kinds = [e["kind"] for e in events]
    assert kinds == ["run", "metrics", "metrics", "event", "system",
                     "summary", "summary", "finish"]
    head = events[0]
    assert head["schema_version"] == tracking.SCHEMA_VERSION == 1
    assert head["run_id"] == "demo-0"
    assert head["git_sha"] == "abc1234"
    assert head["config"] == {"lr": 3e-4}
    assert events[1]["step"] == 1
    assert events[2]["step"] == 5            # explicit step honoured
    assert events[3]["sim_t"] == 12.5
    assert events[4]["metrics"] == {"sim.auu": 0.4}
    assert events[-2]["summary"] == {"final_loss": 2.1}
    assert events[-1]["status"] == "ok"
    # injected clock: strictly increasing wall-clock per record
    ts = [e["t"] for e in events if "t" in e]
    assert ts == sorted(ts)


def test_steps_are_monotonic(tmp_path):
    run = tracking.Run("m", dir=str(tmp_path), run_id="m-0", sha="")
    assert run.log({"x": 1.0}, step=10) == 10
    assert run.log({"x": 2.0}, step=3) == 11   # backwards step -> +1
    assert run.log({"x": 3.0}) == 12
    run.finish()


def test_log_after_finish_is_noop_and_current_run_cleared(tmp_path):
    run = tracking.init("p", dir=str(tmp_path), run_id="p-0", sha="")
    assert tracking.current_run() is run
    run.finish()
    assert tracking.current_run() is None
    run.log({"x": 1.0})                        # silently dropped
    assert [e["kind"] for e in tracking.read_events(run.path)] == \
        ["run", "finish"]


def test_context_manager_records_error_status(tmp_path):
    with pytest.raises(RuntimeError):
        with tracking.Run("e", dir=str(tmp_path), run_id="e-0", sha="") as r:
            r.log({"x": 1.0})
            raise RuntimeError("boom")
    assert tracking.read_events(r.path)[-1]["status"] == "error"


def test_crashed_stream_leaves_readable_prefix(tmp_path):
    run = tracking.Run("c", dir=str(tmp_path), run_id="c-0", sha="")
    run.log({"x": 1.0})                        # never finish()ed
    events = tracking.read_events(run.path)    # flushed per line
    assert [e["kind"] for e in events] == ["run", "metrics"]
    run.finish()


# ---------------------------------------------------------------------------
# samplers
# ---------------------------------------------------------------------------
def test_proc_sampler_reports_rss_and_cpu():
    s = tracking.ProcSampler()
    out = s.sample()
    if not out:                                # no procfs on this host
        pytest.skip("procfs unavailable")
    assert out["proc.rss_mb"] > 0
    assert out["proc.cpu_s"] >= 0


def test_counter_sampler_prefixes(tmp_path):
    s = tracking.CounterSampler(prefix="sim", initial={"auu": 0.5})
    s.update({"pool_utilization": 0.9})
    assert s.sample() == {"sim.auu": 0.5, "sim.pool_utilization": 0.9}
    run = tracking.Run("s", dir=str(tmp_path), run_id="s-0", sha="",
                       samplers=[s])
    merged = run.log_system({"extra": 1.0})
    assert merged == {"sim.auu": 0.5, "sim.pool_utilization": 0.9,
                      "extra": 1.0}
    run.finish()


# ---------------------------------------------------------------------------
# trajectories: idempotent append, spec refresh
# ---------------------------------------------------------------------------
SPEC = {"makespan_s": {"direction": "down"},
        "throughput": {"direction": "up"},
        "wall_s": {"direction": "info"}}


def _append(path, run_id, ts, metrics, spec=SPEC):
    return trajectory.append_summary(
        str(path), "toy", spec, run_id=run_id, git_sha="cafe123",
        ts=ts, metrics=metrics)


def test_append_is_idempotent_per_run_id(tmp_path):
    p = tmp_path / "BENCH_toy.json"
    _append(p, "r1", 1.0, {"makespan_s": 100.0, "throughput": 10.0})
    _append(p, "r2", 2.0, {"makespan_s": 101.0, "throughput": 10.1})
    traj = _append(p, "r2", 3.0, {"makespan_s": 99.0, "throughput": 10.2})
    rows = traj["rows"]
    assert [r["run_id"] for r in rows] == ["r1", "r2"]   # replaced, not dup
    assert rows[1]["metrics"]["makespan_s"] == 99.0
    assert traj["schema_version"] == trajectory.SCHEMA_VERSION
    assert traj["bench"] == "toy"
    # no .tmp litter from the atomic write
    assert sorted(os.listdir(tmp_path)) == ["BENCH_toy.json"]


def test_append_refreshes_spec_and_filters_unknown_metrics(tmp_path):
    p = tmp_path / "BENCH_toy.json"
    _append(p, "r1", 1.0, {"makespan_s": 100.0, "bogus": 1.0})
    spec2 = {"makespan_s": {"direction": "down", "band": 0.25}}
    traj = _append(p, "r2", 2.0, {"makespan_s": 90.0}, spec=spec2)
    assert traj["metrics"] == spec2            # spec ships with the code
    assert "bogus" not in traj["rows"][0]["metrics"]


# ---------------------------------------------------------------------------
# gate semantics
# ---------------------------------------------------------------------------
def _traj(rows, spec=SPEC, baseline=None):
    return {"schema_version": 1, "bench": "toy", "metrics": spec,
            "baseline_run_id": baseline,
            "rows": [{"run_id": f"r{i}", "git_sha": "", "ts": float(i),
                      "metrics": m} for i, m in enumerate(rows)]}


def test_gate_fresh_baseline_and_in_band_pass():
    # single row: nothing to regress against
    one = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0, "throughput": 10.0}]))
    assert not any(v.regressed for v in one)
    # 5% drift on a down-metric stays inside the ±10% band
    vs = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0, "throughput": 10.0}] * 5
        + [{"makespan_s": 105.0, "throughput": 9.5}]))
    assert not any(v.regressed for v in vs)


def test_gate_catches_20pct_regression_both_directions():
    vs = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0, "throughput": 10.0, "wall_s": 1.0}] * 5
        + [{"makespan_s": 120.0, "throughput": 8.0, "wall_s": 99.0}]))
    bad = {v.metric for v in vs if v.regressed}
    assert bad == {"makespan_s", "throughput"}   # wall_s is info: never
    mk = next(v for v in vs if v.metric == "makespan_s")
    assert mk.baseline == pytest.approx(100.0)
    assert mk.delta_pct == pytest.approx(20.0)
    # improvements never trip the direction-aware gate
    ok = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0, "throughput": 10.0}] * 5
        + [{"makespan_s": 80.0, "throughput": 12.0}]))
    assert not any(v.regressed for v in ok)


def test_gate_uses_median_of_trailing_window():
    # one noisy historical run must not poison the baseline
    rows = [{"makespan_s": 100.0}, {"makespan_s": 1000.0},
            {"makespan_s": 100.0}, {"makespan_s": 100.0},
            {"makespan_s": 100.0}, {"makespan_s": 105.0}]
    vs = gate.check_trajectory(_traj(rows))
    mk = next(v for v in vs if v.metric == "makespan_s")
    assert mk.baseline == pytest.approx(100.0)   # median, not mean
    assert not mk.regressed


def test_gate_missing_gated_metric_regresses():
    vs = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0, "throughput": 10.0}] * 3
        + [{"makespan_s": 100.0}]))              # throughput vanished
    bad = next(v for v in vs if v.regressed)
    assert bad.metric == "throughput"
    assert "missing" in bad.note


def test_gate_per_metric_band_override():
    spec = {"makespan_s": {"direction": "down", "band": 0.50}}
    vs = gate.check_trajectory(_traj(
        [{"makespan_s": 100.0}] * 3 + [{"makespan_s": 130.0}], spec=spec))
    assert not any(v.regressed for v in vs)      # +30% < the 50% band


def test_update_baseline_anchors_window():
    # a 2x intentional change: regressed against the old history...
    rows = [{"makespan_s": 100.0}] * 5 + [{"makespan_s": 200.0}]
    traj = _traj(rows, spec={"makespan_s": {"direction": "down"}})
    assert any(v.regressed for v in gate.check_trajectory(traj))
    # ...anchoring at the newest row accepts it
    gate.update_baseline(traj)
    assert traj["baseline_run_id"] == "r5"
    assert not any(v.regressed for v in gate.check_trajectory(traj))
    # and the next in-band row gates against the new anchor only
    traj["rows"].append({"run_id": "r6", "git_sha": "", "ts": 6.0,
                         "metrics": {"makespan_s": 205.0}})
    vs = gate.check_trajectory(traj)
    mk = next(v for v in vs if v.metric == "makespan_s")
    assert mk.baseline == pytest.approx(200.0) and not mk.regressed


# ---------------------------------------------------------------------------
# scripts/check_perf.py end-to-end
# ---------------------------------------------------------------------------
def _check_perf(results_dir, *argv):
    return subprocess.run(
        [sys.executable, CHECK_PERF, "--results-dir", str(results_dir),
         *argv], capture_output=True, text=True)


def test_check_perf_cli_gate_and_demo(tmp_path):
    p = tmp_path / "BENCH_toy.json"
    for i in range(5):
        _append(p, f"r{i}", float(i),
                {"makespan_s": 100.0 + i, "throughput": 10.0, "wall_s": 1.0})
    out = _check_perf(tmp_path)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "check_perf: OK" in out.stdout
    # a 20% regression exits non-zero and names the metric
    _append(p, "bad", 9.0,
            {"makespan_s": 125.0, "throughput": 10.0, "wall_s": 1.0})
    out = _check_perf(tmp_path)
    assert out.returncode == 1
    assert "toy/makespan_s" in out.stdout
    assert "REGRESSED" in out.stdout
    # --update-baseline accepts the change; the gate is green again
    assert _check_perf(tmp_path, "--update-baseline").returncode == 0
    assert trajectory.load(str(p))["baseline_run_id"] == "bad"
    assert _check_perf(tmp_path).returncode == 0
    # the self-test proves the gate still trips on synthetic data
    out = _check_perf(tmp_path, "--demo-regression")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "demo OK" in out.stdout
    # ... without touching the real trajectory
    assert trajectory.load(str(p))["rows"][-1]["run_id"] == "bad"


def test_check_perf_cli_empty_dir_passes(tmp_path):
    assert _check_perf(tmp_path).returncode == 0


def test_check_perf_demo_survives_spiky_rows_and_wide_bands(tmp_path):
    """Self-test regression: the synthetic degradation must beat the
    gate's *median* baseline and each metric's *own* band.  A newest
    row sitting above the median (case counts of 6, 6, 7) or
    a wide custom band (serve_bench's wall-clock throughput at 0.5)
    used to absorb the flat 20%-off-the-last-row nudge and falsely
    fail the demo."""
    spec = {"n_cases": {"direction": "up"},
            "throughput": {"direction": "up", "band": 0.5}}
    p = tmp_path / "BENCH_toy.json"
    for i, n in enumerate((6.0, 6.0, 7.0)):
        _append(p, f"r{i}", float(i),
                {"n_cases": n, "throughput": 40.0}, spec=spec)
    out = _check_perf(tmp_path, "--demo-regression")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "demo OK" in out.stdout and "demo FAIL" not in out.stdout


# ---------------------------------------------------------------------------
# producer integration: bench specs + the simulator telemetry mirror
# ---------------------------------------------------------------------------
def test_bench_trajectory_specs_are_wellformed():
    from benchmarks import cluster_sim, storage_bench
    for mod in (cluster_sim, storage_bench):
        assert mod.TRAJECTORY
        for name, m in mod.TRAJECTORY.items():
            assert m["direction"] in ("up", "down", "info"), (mod, name)


def test_cluster_sim_trajectory_row_from_shipped_artifact():
    path = os.path.join(ROOT, "results", "cluster_sim.json")
    if not os.path.exists(path):
        pytest.skip("cluster_sim artifact not generated")
    from benchmarks import cluster_sim
    with open(path) as f:
        row = cluster_sim.trajectory_row(json.load(f))
    assert set(row) == set(cluster_sim.TRAJECTORY)
    assert all(isinstance(v, float) for v in row.values())
    assert row["makespan_s"] > 0


def test_simulator_mirrors_telemetry_into_current_run(tmp_path):
    from repro.cluster import ClusterSimulator, TraceConfig
    cfg = TraceConfig(n_jobs=4, arrival_rate_hz=0.5, seed=3, failures=())
    baseline = ClusterSimulator(cfg).run()
    run = tracking.init("sim-test", dir=str(tmp_path), run_id="sim-0",
                        sha="")
    tracked = ClusterSimulator(cfg).run()
    run.finish()
    # the mirror never perturbs the deterministic report
    assert tracked == baseline
    events = tracking.read_events(run.path)
    metrics = [e for e in events if e["kind"] == "metrics"]
    assert metrics and metrics[-1]["metrics"]["makespan_s"] == \
        pytest.approx(baseline["makespan_s"])
    system = [e for e in events if e["kind"] == "system"]
    assert any("sim.auu" in e["metrics"] for e in system)
