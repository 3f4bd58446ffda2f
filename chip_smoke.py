"""Smoke run of the main path on a TPU, at the full width of qwen2-0.5b.

    python chip_smoke.py               # one chip: train, then paged serving
    python chip_smoke.py --four-chips  # four chips: the sharded train step

With no option, two phases run in this one process, one after the other
(a chip belongs to one process at a time):

  train  ``repro.launch.train`` at full width for a few steps, with the
         state donated; every loss must be finite.
  serve  ``repro.launch.serve --no-reduced`` in paged mode, once for each
         of two ``--slots`` values; every request must be served and the
         greedy outputs must be identical under both.

``--four-chips`` runs only the train step over a ``("data", "model")``
mesh of 4x1, 2x2 and 1x4 chips (``trainer.make_train_step(mesh=...)`` +
``trainer.jit_train_step``), and what it is compared with: the same steps
on one chip through ``repro.launch.train``.  Loss and gradient norm must
agree within ``RTOL``, and the parameters and optimizer state must span
the four chips.

The weights are random, made from a fixed seed.  Each phase prints its
numbers on earlier lines.  The last line of standard output is one JSON
object naming the device, printed only when every phase passed.  On a
host whose JAX finds no TPU the script exits 1 before any phase runs.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

TRAIN_ARGS = ("--arch", "qwen2-0.5b", "--steps", "5", "--batch", "4",
              "--seq", "2048", "--dtype", "bfloat16", "--log-every", "1")
SERVE_ARGS = ("--arch", "qwen2-0.5b", "--no-reduced", "--mode", "paged",
              "--requests", "8", "--prompt-len", "512",
              "--min-prompt-len", "64", "--max-new", "32",
              "--max-seq", "1024")
SERVE_SLOTS = (4, 2)
# --warmup 1: the full learning rate from the first step, so that three
# steps move the weights enough for a wrong gradient to show in the loss
FOUR_CHIP_ARGS = ("--arch", "qwen2-0.5b", "--steps", "3", "--batch", "4",
                  "--seq", "2048", "--dtype", "bfloat16", "--warmup", "1",
                  "--log-every", "1")
MESHES = ((4, 1), (2, 2), (1, 4))
# bf16 compute: five units in the last place of a bfloat16 (2**-8 each),
# relative, between the one-chip and the mesh runs of the same steps
RTOL = 5 * 2.0 ** -8


class PhaseFailed(RuntimeError):
    """A phase ran but its result is wrong."""


def _peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    if peak is None:
        return "not reported"
    return f"{peak / 2**30:.2f} GiB" + (
        f" of {limit / 2**30:.2f} GiB" if limit else "")


def train_phase(argv=TRAIN_ARGS) -> dict:
    """Run ``repro.launch.train`` with ``argv``; every loss finite."""
    from repro.launch import train
    res = train.run(train.parse_args(list(argv)))
    losses = res["losses"]
    if res["exit_code"] or len(losses) < 3:
        raise PhaseFailed(f"train: exit {res['exit_code']}, "
                          f"{len(losses)} steps")
    if not all(math.isfinite(x) for x in losses):
        raise PhaseFailed(f"train: non-finite loss in {losses}")
    return {"compile_s": res["compile_s"], "step_s": res["step_s"],
            "losses": losses}


def serve_phase(argv=SERVE_ARGS, slots=SERVE_SLOTS) -> dict:
    """Run ``repro.launch.serve`` with ``argv`` once per slot count; every
    request served, greedy outputs identical across slot counts."""
    from repro.launch import serve
    outputs, served, compile_s = [], [], []
    for n in slots:
        res = serve.run(serve.parse_args([*argv, "--slots", str(n)]))
        if res["exit_code"]:
            raise PhaseFailed(f"serve --slots {n}: exit {res['exit_code']}")
        if res["report"]["mode"] != "paged":
            raise PhaseFailed(f"serve: ran in {res['report']['mode']} mode")
        outputs.append(res["outputs"])
        served.append(res["served"])
        compile_s.append(res["report"]["compile_s"])
    differ = sorted(rid for rid in outputs[0]
                    if any(o[rid] != outputs[0][rid] for o in outputs[1:]))
    if differ:
        raise PhaseFailed(f"serve: greedy outputs differ between --slots "
                          f"{slots} for requests {differ}")
    return {"served": served, "requests": len(outputs[0]),
            "compile_s": compile_s, "identical": True}


def four_chip_phase(argv=FOUR_CHIP_ARGS, meshes=MESHES) -> dict:
    """The sharded train step on each mesh against ``repro.launch.train``
    on one device, step by step."""
    import jax
    from repro.configs.base import ShapeConfig
    from repro.data import SyntheticDataset
    from repro.launch import train
    from repro.launch.mesh import make_mesh
    from repro.train import trainer

    args = train.parse_args(list(argv))
    ref = train.run(args)
    if ref["exit_code"]:
        raise PhaseFailed(f"one-chip reference: exit {ref['exit_code']}")
    cfg, policy, optcfg, schedcfg = train.build(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    ds = SyntheticDataset(cfg, shape)
    out = {"one_chip": {"loss": ref["losses"],
                        "grad_norm": ref["grad_norms"]}}
    for dims in meshes:
        mesh = make_mesh(dims, ("data", "model"))
        state = trainer.init_state(jax.random.PRNGKey(0), cfg, policy,
                                   optcfg)
        step = trainer.jit_train_step(
            trainer.make_train_step(cfg, policy, optcfg, schedcfg,
                                    mesh=mesh, shape=shape),
            state, cfg, policy, mesh, ds.batch_at(0))
        got = {"loss": [], "grad_norm": []}
        t0 = time.perf_counter()
        with mesh:
            for i in range(args.steps):
                state, metrics = step(state, ds.batch_at(i))
                got["loss"].append(float(metrics["loss"]))
                got["grad_norm"].append(float(metrics["grad_norm"]))
        wall = time.perf_counter() - t0
        want = set(mesh.devices.flat)
        leaves = jax.tree.leaves((state.params, state.opt))
        off = [x.shape for x in leaves if x.sharding.device_set != want]
        n_split = sum(not x.sharding.is_fully_replicated for x in leaves)
        name = "x".join(map(str, dims))
        print(f"mesh {name}: loss {got['loss']} grad_norm "
              f"{got['grad_norm']} ({wall:.1f}s incl. compile); "
              f"{n_split}/{len(leaves)} state leaves split over "
              f"{len(want)} devices")
        if off or not n_split:
            raise PhaseFailed(f"mesh {name}: state not spread over "
                              f"{len(want)} devices ({len(off)} leaves "
                              f"elsewhere, {n_split} split)")
        for key, ref_vals in out["one_chip"].items():
            for a, b in zip(got[key], ref_vals):
                if not abs(a - b) <= RTOL * abs(b):
                    raise PhaseFailed(f"mesh {name}: {key} {got[key]} vs "
                                      f"one chip {ref_vals} (rtol {RTOL})")
        out[name] = got
        del state
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded train step on 4 chips and "
                         "its one-chip comparison")
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"error: JAX finds no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"error: {want} chips needed, JAX finds {len(devices)}",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    print(f"device: {dev.device_kind} x{len(devices)}; compile cache "
          f"{compile_cache.enable()}")

    if args.four_chips:
        res = four_chip_phase()
        print(f"four chips: {len(MESHES)} meshes agree with one chip "
              f"within rtol {RTOL:g}")
        print(f"four chips: peak HBM (chip 0) {_peak_bytes()}")
    else:
        t = train_phase()
        print(f"train: compile {t['compile_s']:.1f}s, steps "
              f"{[round(s, 3) for s in t['step_s']]}s, final loss "
              f"{t['losses'][-1]:.4f}, peak HBM {_peak_bytes()}")
        del t
        s = serve_phase()
        print(f"serve: served {s['served']} of {s['requests']} per slot "
              f"count {list(SERVE_SLOTS)}, warmup compile "
              f"{[round(c, 1) for c in s['compile_s']]}s, outputs "
              f"identical: {s['identical']}, peak HBM {_peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
