"""Readings that the limits of ``bench/limits/<cell>.json`` are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--fault-seeds 1,2,3] [--seconds 10]

Runs on the cell's chips, in one process, and prints one JSON line per
reading:

* ``program``: the numbers ``correct`` compares, from the program as a run
  of the cell makes them (training: the check steps; serving: a run of
  ``--seconds`` at the cell's own load);
* ``control``: the same numbers with the plain reference computed in
  float8 (e4m3, per-tensor scaled) in the program's place, the precision
  below the bfloat16 the configuration states; for serving, the gap of
  the token the float8 model puts first at each served position;
* ``fault``: training only, the program with half of each batch left out
  (the mean taken over the rest).

The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import copy  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def _ints(s: str):
    return [int(x) for x in s.split(",") if x]


def emit(rec):
    print(json.dumps(rec), flush=True)


def train(cell, devs, args):
    drv = harness.load_module(harness.BENCH / "drivers" / "train.py")
    rule = cell.limits.get("leaf_rule", 1e-3)
    program = drv.Program(cell, devs)
    half = None
    for seed in args.seeds:
        t0 = time.monotonic()
        state, prog = program.check_steps(seed)
        del state
        gc.collect()
        ref = drv.reference_readings(cell, seed, devs, program.mesh,
                                     "float32")
        emit({"seed": seed, "kind": "program",
              **drv.compare(prog, ref, rule), "loss": prog["loss"],
              "ref_loss": ref["loss"], "s": time.monotonic() - t0})
        if seed in args.control_seeds:
            ctl = drv.reference_readings(cell, seed, devs, program.mesh,
                                         "float8")
            emit({"seed": seed, "kind": "control",
                  **drv.compare(ctl, ref, rule), "loss": ctl["loss"]})
        if seed in args.fault_seeds:
            if half is None:
                hc = copy.copy(cell)
                hc.traffic = dict(cell.traffic,
                                  batch=cell.traffic["batch"] // 2)
                half = drv.Program(hc, devs)
            state, hp = half.check_steps(seed)
            del state
            gc.collect()
            emit({"seed": seed, "kind": "fault", "fault": "half_batch",
                  **drv.compare(hp, ref, rule), "loss": hp["loss"]})


def serve(cell, devs, args):
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py")
    for seed in args.seeds:
        t0 = time.monotonic()
        counter = harness.CompileCounter()
        res = drv.run(cell, devs, seed, args.seconds, None,
                      time.monotonic(), counter)
        rec = {"seed": seed, "kind": "program", **res["readings"],
               "attempted": res["attempted"], "failed": res["failed"],
               "served_tokens": sum(len(o) for _, o in res["samples"]),
               "values": res["values"],
               "compiles_in_window": res["compiles_in_window"]}
        emit(dict(rec, s=time.monotonic() - t0))
        if seed in args.control_seeds:
            g = drv.gap_readings(cell, seed, res["samples"], devs,
                                 ["float8"])
            emit({"seed": seed, "kind": "control", "logit_gap": g["float8"]})
        gc.collect()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=_ints, required=True)
    ap.add_argument("--control-seeds", type=_ints, default=[])
    ap.add_argument("--fault-seeds", type=_ints, default=[])
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    {"train": train, "serve": serve}[cell.kind](cell, devs, args)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
