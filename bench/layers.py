"""Attribute a training cell's device time to the program's layers.

    python3 bench/layers.py --workload <train cell> --seed <n> --steps <k>
        [--save <dir>]

Builds the cell's compiled train step and its state as ``bench/run.py``
does (``bench/drivers/train.py``), runs the check steps, then ``--steps``
more under the profiler inside a ``bench:window`` span, and reduces the
trace twice: as ``bench/trace.py`` does for every traced run, and by the
program's named scopes (``bench/scopes.py``).  The last line of standard
output is one JSON object: ``scope_s`` (device seconds per scope over the
window, ``unscoped`` included), ``busy_s``, ``window_s``, ``steps``, the
operations of no known scope that took most time, and the per-layer
metrics of ``LAYER_METRICS``, each read by its ``bench/metrics/<name>.py``
from the same measurements a traced benchmark run would pass it.  It
checks nothing against the reference.  ``--save`` keeps the trace and the
step's HLO text there.  The step is compiled afresh, never read from the
persistent compilation cache.  Exit status 2 when there is no chip.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import sys
import tempfile

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

LAYER_METRICS = ("attn_core_ms.train", "mlp_ms.train", "head_ms.train",
                 "optimizer_ms.train", "attn_core_roofline.train")


def measure(cell, devs, seed: int, steps: int, save=None):
    import jax
    from bench import flops, harness, scopes, trace as tr
    driver = harness.load_module(harness.BENCH / "drivers" / "train.py")
    t, c = cell.traffic, cell.config
    program = driver.Program(cell, devs)
    state, _ = program.check_steps(seed)
    step = t["check_steps"]
    nxt = program.feed(seed, step)
    jax.block_until_ready((state, nxt))
    d = pathlib.Path(tempfile.mkdtemp(prefix="bench-layers-"))
    try:
        with harness.profile(d), harness.span(d, "bench:window"):
            for _ in range(steps):
                state, metrics = program.step(state, nxt)
                step += 1
                nxt = program.feed(seed, step)
            jax.block_until_ready((state, metrics))
        path = tr.find_xplane(d)
        hlo = program.step.as_text()
        if save:
            out = pathlib.Path(save)
            out.mkdir(parents=True, exist_ok=True)
            shutil.copy(path, out / "train_step.xplane.pb")
            (out / "train_step.hlo.txt").write_text(hlo)
        trace = tr.load(path)
    finally:
        shutil.rmtree(d, ignore_errors=True)
    red = tr.reduce(trace)
    op_scopes = scopes.op_scopes(hlo)
    red["scope_s"] = scopes.scope_seconds(trace, op_scopes)
    meas = {"kind": "train", "chips": cell.chips, "steps": steps,
            "window_s": red["window_s"],
            "step_flops": flops.train_step(c, t["batch"], t["seq"]),
            "attn_core_flops": scopes.attention_core_train(
                c, t["batch"], t["seq"]),
            "peak": harness.peaks(devs[0].device_kind), "trace": red}
    metrics = {name: harness.load_module(harness.BENCH / "metrics"
                                         / f"{name}.py").read(meas)
               for name in LAYER_METRICS}
    return {"workload": cell.name, "seed": seed, "steps": steps,
            "device": devs[0].device_kind, "window_s": red["window_s"],
            "busy_s": red["busy_s"], "scope_s": red["scope_s"],
            "top_unscoped": scopes.top_unscoped(trace, op_scopes),
            "metrics": metrics}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--save", default=None)
    args = ap.parse_args(argv)
    from bench import harness
    try:
        cell = harness.find_cell(args.workload)
        devs = harness.require_chips(cell.chips)
    except (harness.NoChip, KeyError, FileNotFoundError) as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    if cell.kind != "train":
        print(f"error: {cell.name} is not a training cell", file=sys.stderr)
        return 2
    # compile afresh: an executable read from the persistent cache carries
    # the HLO metadata of the program that first wrote it, whose scopes
    # need not be this program's (the cache key leaves metadata out)
    import jax
    jax.config.update("jax_enable_compilation_cache", False)
    print(json.dumps(measure(cell, devs, args.seed, args.steps, args.save)),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
