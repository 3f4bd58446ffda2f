"""Run one benchmark cell once, on the chip, and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json`` at the root of
the checkout; everything else is found by name under ``bench/`` (see
``bench/harness.py``).  The run checks that JAX finds the cell's TPU chips
(it never falls back to the CPU), turns on JAX's persistent compilation
cache, makes its weights and inputs from ``--seed``, warms up every
program the window uses, measures for ``--seconds``, then checks what the
timed path produced against the plain float32 reference.  With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are printed instead of the end-to-end ones.

The last lines of standard error name each compared number beside its
limit; the last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``compared``.  Exit status is 0 when a result was
printed, 2 when there is no chip or the cell cannot run.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def run_cell(cell, devs, seed: int, seconds: float, trace: bool,
             t_start: float = T_START):
    """One run of ``cell`` on ``devs``; returns the result object."""
    from bench import harness, trace as tr

    counter = harness.CompileCounter()
    driver = harness.load_module(harness.BENCH / "drivers"
                                 / f"{cell.kind}.py")
    trace_dir = pathlib.Path(tempfile.mkdtemp(prefix="bench-trace-")) \
        if trace else None
    try:
        res = driver.run(cell, devs, seed, seconds, trace_dir, t_start,
                         counter)
        compared, ok = harness.judge(res["readings"], cell.limits)
        meas = dict(res["meas"])
        out = {"correct": bool(ok and res.get("ok_extra", True)),
               "attempted": int(res["attempted"]),
               "failed": int(res["failed"])}
        if trace_dir:
            meas["peak"] = harness.peaks(devs[0].device_kind)
            red = tr.reduce(tr.load(tr.find_xplane(trace_dir)),
                            **driver.TRACE_ARGS)
            meas["trace"] = red
            out["metrics"] = harness.per_layer(cell, meas)
            out["device"] = harness.device_info(
                devs, peak_bytes=res["peak_bytes"], trace=red)
            out["breakdown"] = red["breakdown"]
        else:
            out["metrics"] = harness.end_to_end(cell, res["values"])
            out["device"] = harness.device_info(
                devs, peak_bytes=res["peak_bytes"])
        out["compared"] = compared
        print(f"compiles in the window: {res['compiles_in_window']}",
              file=sys.stderr)
        return out, res
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)


def main(argv=None) -> int:
    args = parse_args(argv)
    from bench import harness
    try:
        cell = harness.find_cell(args.workload)
        devs = harness.require_chips(cell.chips)
    except (harness.NoChip, KeyError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    out, _ = run_cell(cell, devs, args.seed, args.seconds, bool(args.trace))
    harness.emit(out)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
