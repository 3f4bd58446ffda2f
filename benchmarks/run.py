"""Benchmark harness: one module per paper table/figure.

Usage: PYTHONPATH=src python -m benchmarks.run [--only fig11]
Prints ``name,us_per_call,derived`` CSV per row.

``--bench <name>`` runs one module and, when it exposes ``report()``,
emits the JSON artifact to stdout and ``results/<name>.json``.  Every
``--bench`` invocation is a **tracked run** (``repro.tracking``): the
report is produced under an active ``tracking.init(...)`` scope (so the
simulator/engine mirror their telemetry into the run's
``events.jsonl``), the artifact is stamped with ``schema_version`` and
``run_id``, and — when the module declares a ``TRAJECTORY`` metric spec
plus ``trajectory_row()`` — exactly one summary row is appended to
``results/BENCH_<name>.json`` for ``scripts/check_perf.py`` to gate.
Pass ``--no-track`` to skip tracking (pure artifact regeneration).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ARTIFACT_SCHEMA_VERSION = 1


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="")
    ap.add_argument("--bench", default="",
                    help="run one module; write its JSON report artifact")
    ap.add_argument("--out-dir", default="results")
    ap.add_argument("--no-track", action="store_true",
                    help="skip run tracking / trajectory append")
    ap.add_argument("--run-id", default="",
                    help="override the tracked run id (idempotent "
                         "trajectory append per run id)")
    args = ap.parse_args()

    from repro.launch import compile_cache
    compile_cache.enable()
    from benchmarks import (beyond_paper, chaos_bench, cluster_sim,
                            fabric_bench, fig10_utilization,
                            fig11_switch_overhead, fig12_traffic,
                            fig15_storage, fig16_sw_opt, kernel_tune,
                            recompose, recompose_bench, roofline,
                            serve_bench, storage_bench, table2_models,
                            table4_links)
    modules = {
        "table2": table2_models,
        "table4": table4_links,
        "fig10": fig10_utilization,
        "fig11": fig11_switch_overhead,
        "fig12": fig12_traffic,
        "fig15": fig15_storage,
        "fig16": fig16_sw_opt,
        "beyond": beyond_paper,
        "recompose": recompose,
        "recompose_bench": recompose_bench,
        "roofline": roofline,
        "chaos_bench": chaos_bench,
        "cluster_sim": cluster_sim,
        "fabric_bench": fabric_bench,
        "kernel_tune": kernel_tune,
        "serve_bench": serve_bench,
        "storage_bench": storage_bench,
    }

    if args.bench:
        mod = modules.get(args.bench)
        if mod is None:
            print(f"unknown bench {args.bench!r}; known: {sorted(modules)}",
                  file=sys.stderr)
            return 2
        if not hasattr(mod, "report"):
            print(f"bench {args.bench!r} has no report(); use --only",
                  file=sys.stderr)
            return 2

        run = None
        if not args.no_track:
            import repro.tracking as tracking
            run = tracking.init(
                args.bench, config={"bench": args.bench},
                tags=("bench",),
                dir=os.path.join(args.out_dir, "runs"),
                run_id=args.run_id or None,
                samplers=[tracking.ProcSampler()])
            run.log_system()

        try:
            rep = mod.report()
            rep["schema_version"] = ARTIFACT_SCHEMA_VERSION
            if run is not None:
                rep["run_id"] = run.id
                run.log_system()
                spec = getattr(mod, "TRAJECTORY", None)
                if spec is not None:
                    from repro.tracking import trajectory
                    row = mod.trajectory_row(rep)
                    run.log_summary(row)
                    trajectory.append_summary(
                        trajectory.path_for(args.bench, args.out_dir),
                        args.bench, spec, run_id=run.id,
                        git_sha=run.git_sha, ts=run.clock(), metrics=row)
                    print(f"appended trajectory row {run.id} to "
                          f"{trajectory.path_for(args.bench, args.out_dir)}",
                          file=sys.stderr)
        except BaseException:
            if run is not None:
                run.finish("error")
            raise
        if run is not None:
            run.finish()

        out = json.dumps(rep, indent=2, default=str)
        print(out)
        os.makedirs(args.out_dir, exist_ok=True)
        path = os.path.join(args.out_dir, f"{args.bench}.json")
        with open(path, "w") as f:
            f.write(out + "\n")
        print(f"wrote {path}", file=sys.stderr)
        return 0

    print("name,us_per_call,derived")
    failed = 0
    for name, mod in modules.items():
        if args.only and args.only != name:
            continue
        try:
            for row_name, us, derived in mod.run():
                print(f"{row_name},{us:.1f},{derived}")
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name}/ERROR,0.0,{type(e).__name__}: {e}",
                  file=sys.stdout)
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
