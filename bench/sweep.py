"""Find the serving knee: the highest arrival rate a serve cell sustains.

    python3 bench/sweep.py --workload <serve cell> --seed <n> \
        --rates 1,2,3,4 [--seconds 30]

One process on the cell's chip: the engine is built and warmed once, then
the cell's traffic runs at each rate in turn (``preroll_s`` of it, a
window of ``--seconds``, the window's requests waited for).  One JSON line
per rate: output tokens/s, TTFT and ITL p95, requests due, finished and
still waiting at the close.  The rate a cell runs at is then written into
its traffic file as a number; the benchmark never searches for it.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rates", type=lambda s: [float(x) for x in
                                                s.split(",")], required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    cell = harness.find_cell(args.workload)
    try:
        devs = harness.require_chips(cell.chips)
    except harness.NoChip as e:
        print(f"error: {e}", file=sys.stderr)
        return 2
    from repro.launch import compile_cache
    compile_cache.enable()
    drv = harness.load_module(harness.BENCH / "drivers" / "serve.py")
    eng, _ = drv.build(cell, devs, args.seed)
    drv.warm(eng, cell.traffic)
    for rate in args.rates:
        c = copy.copy(cell)
        c.traffic = copy.deepcopy(cell.traffic)
        c.traffic["arrivals"]["rate_per_s"] = rate
        counter = harness.CompileCounter()
        res = drv.drive(eng, c, args.seed, args.seconds, None,
                        time.monotonic(), counter)
        print(json.dumps({"rate_per_s": rate, **res["values"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "waiting_at_close": res["waiting_at_close"],
                          "compiles_in_window": res["compiles_in_window"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    raise SystemExit(main())
