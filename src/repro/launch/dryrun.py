import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. builds the jitted step (train / prefill / decode) with in/out
     shardings from the policy engine,
  3. ``.lower(**input_specs)`` -> ``.compile()``  (ShapeDtypeStructs only —
     no arrays are ever allocated),
  4. prints ``memory_analysis()`` (proves the cell fits 16 GB/chip) and
     ``cost_analysis()`` (FLOPs/bytes),
  5. parses the compiled HLO for collectives and writes a JSON CostReport
     consumed by benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                 # all cells
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-0.5b \
      --shape train_4k --mesh single
  PYTHONPATH=src python -m repro.launch.dryrun --mesh multi --zero 3
"""
import argparse
import dataclasses
import json
import time
import traceback
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import (ASSIGNED_ARCHS, SHAPES, applicable_shapes,
                           get_config)
from repro.configs.base import PolicyConfig
from repro.core import costmodel, policy as pol
from repro.core.compose import production_system
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.serve import engine
from repro.train import trainer


def _ns(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s) if s is not None else None,
        spec_tree,
        is_leaf=lambda x: isinstance(x, P) or x is None)


def make_policy(args, multi_pod: bool) -> PolicyConfig:
    dp = ("pod", "data") if multi_pod else ("data",)
    return PolicyConfig(
        dp_axes=dp,
        fsdp_axes=("data",),
        tp_axis="model",
        zero_stage=args.zero,
        compute_dtype=args.dtype,
        param_dtype=getattr(args, "param_dtype", "float32"),
        remat=args.remat,
        attn_impl="xla",
        grad_accum=args.grad_accum,
        grad_compression=args.compress,
    )


def lower_cell(arch: str, shape_name: str, mesh, policy: PolicyConfig,
               *, donate: bool = True):
    """Build + lower + compile one cell. Returns (lowered, compiled, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh_axes = dict(mesh.shape)
    n_pods = mesh_axes.get("pod", 1)
    if shape.kind == "decode":
        # serving layout: weights stationary (TP-only, bf16, no ZeRO) —
        # ZeRO-3 decode re-gathers the whole model for every token
        # (measured 100-490 ms/token of pure weight traffic)
        policy = dataclasses.replace(policy, zero_stage=0,
                                     param_dtype="bfloat16")
    ins = specs_lib.input_specs(arch, shape_name, policy, n_pods=n_pods)

    if ins["kind"] == "train":
        step = trainer.make_train_step(cfg, policy, mesh=mesh, shape=shape)
        sspec = trainer.state_specs(ins["state"], cfg, policy, mesh_axes)
        bspec = pol.batch_specs(ins["batch"], policy, mesh_axes)
        jf = jax.jit(step,
                     in_shardings=(_ns(mesh, dataclasses_asdict(sspec)),
                                   _ns(mesh, bspec)),
                     out_shardings=(_ns(mesh, dataclasses_asdict(sspec)),
                                    None),
                     donate_argnums=(0,) if donate else ())
        with mesh:
            lowered = jf.lower(ins["state"], ins["batch"])
        flops = costmodel.step_flops(cfg, shape, policy)
    elif ins["kind"] == "prefill":
        step = engine.make_prefill_step(cfg, policy,
                                        cache_capacity=shape.seq_len,
                                        mesh=mesh)
        pspec = pol.param_specs(ins["params"], cfg, policy, mesh_axes)
        bspec = pol.batch_specs(ins["batch"], policy, mesh_axes)
        cspec_out = None   # let GSPMD lay out the produced caches
        jf = jax.jit(step,
                     in_shardings=(_ns(mesh, pspec),
                                   _ns(mesh, bspec["inputs"])),
                     out_shardings=None)
        with mesh:
            lowered = jf.lower(ins["params"], ins["batch"]["inputs"])
        flops = (costmodel.forward_flops(cfg, shape, with_logits=False)
                 + 2 * shape.global_batch * cfg.d_model * cfg.padded_vocab)
    else:  # decode
        step = engine.make_decode_step(cfg, policy, mesh=mesh)
        pspec = pol.param_specs(ins["params"], cfg, policy, mesh_axes)
        cspec = pol.cache_specs(ins["caches"], policy, mesh_axes)
        tspec = pol.batch_specs(
            {"t": ins["tokens"], "p": ins["positions"]}, policy, mesh_axes)
        jf = jax.jit(step,
                     in_shardings=(_ns(mesh, pspec), _ns(mesh, cspec),
                                   _ns(mesh, tspec["t"]),
                                   _ns(mesh, tspec["p"])),
                     out_shardings=(None, _ns(mesh, cspec)),
                     donate_argnums=(1,) if donate else ())
        with mesh:
            lowered = jf.lower(ins["params"], ins["caches"], ins["tokens"],
                               ins["positions"])
        flops = costmodel.forward_flops(cfg, shape)

    compiled = lowered.compile()
    report = costmodel.extract(
        compiled, arch=arch, shape_name=shape_name, mesh_axes=mesh_axes,
        flops_analytic=flops,
        model_fl=costmodel.model_flops(cfg, shape),
        hbm_analytic=costmodel.analytic_hbm_bytes(cfg, shape, policy,
                                                  mesh_axes))
    return lowered, compiled, report


def dataclasses_asdict(state_spec):
    """TrainState spec -> same TrainState (already a pytree); identity
    hook kept for clarity at the call site."""
    return state_spec


def report_to_json(report: costmodel.CostReport, compiled,
                   wall_s: float) -> Dict[str, Any]:
    mem: Dict[str, Any] = {}
    try:
        m = compiled.memory_analysis()
        if m is not None:
            for k in ("argument_size_in_bytes", "output_size_in_bytes",
                      "temp_size_in_bytes", "generated_code_size_in_bytes",
                      "alias_size_in_bytes"):
                v = getattr(m, k, None)
                if v is not None:
                    mem[k] = int(v)
    except Exception:
        pass
    colls: Dict[str, Dict[str, float]] = {}
    for op in report.collectives:
        key = op.kind
        c = colls.setdefault(key, {"count": 0, "wire_bytes": 0.0})
        c["count"] += op.trip_count
        c["wire_bytes"] += op.wire_bytes
    return {
        "arch": report.arch, "shape": report.shape, "mesh": report.mesh,
        "flops_hlo_per_device": report.flops_hlo,
        "flops_analytic_total": report.flops_analytic,
        "model_flops": report.model_flops,
        "hbm_bytes_per_device": report.hbm_bytes,
        "memory_analysis": mem,
        "collectives_by_kind": colls,
        "per_axis_wire_bytes": report.per_axis_wire_bytes(),
        "collective_wire_bytes_total": report.collective_bytes_total(),
        "compile_wall_s": wall_s,
    }


def run_cell(arch: str, shape_name: str, mesh_kind: str, args,
             out_dir: str) -> Optional[Dict[str, Any]]:
    multi = mesh_kind == "multi"
    t0 = time.time()
    tag = f"{arch}__{shape_name}__{mesh_kind}"
    out_path = os.path.join(out_dir, tag + ".json")
    if args.skip_existing and os.path.exists(out_path):
        print(f"[skip] {tag} (cached)")
        with open(out_path) as f:
            return json.load(f)
    try:
        if getattr(args, "mesh_shape", ""):
            from repro.launch.mesh import make_mesh
            sizes = tuple(int(x) for x in args.mesh_shape.split(","))
            names = (("pod", "data", "model") if len(sizes) == 3
                     else ("data", "model"))
            mesh = make_mesh(sizes, names)
        else:
            mesh = make_production_mesh(multi_pod=multi)
        policy = make_policy(args, multi)
        lowered, compiled, report = lower_cell(arch, shape_name, mesh,
                                               policy)
        wall = time.time() - t0
        js = report_to_json(report, compiled, wall)
        # the roofline needs the fabric: price on the localGPUs system
        system = production_system(multi_pod=multi)
        rl = costmodel.roofline(report, system)
        js["hbm_bytes_analytic"] = report.hbm_bytes_analytic
        js["roofline"] = {
            "compute_s": rl.compute_s, "memory_s": rl.memory_s,
            "memory_hlo_s": rl.memory_hlo_s,
            "collective_s": rl.collective_s, "per_axis_s": rl.per_axis_s,
            "dominant": rl.dominant, "useful_ratio": rl.useful_ratio,
            "roofline_fraction": rl.roofline_fraction,
            "step_time_s": rl.step_time_s,
        }
        os.makedirs(out_dir, exist_ok=True)
        with open(out_path, "w") as f:
            json.dump(js, f, indent=1)
        mem_gb = js["memory_analysis"].get("argument_size_in_bytes", 0) / 2**30
        print(f"[ok]   {tag}: compile {wall:.1f}s | args/dev "
              f"{mem_gb:.2f}GiB | {rl.summary()}")
        return js
    except Exception as e:  # noqa: BLE001 — report every failing cell
        print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
        if args.verbose:
            traceback.print_exc()
        return None


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both",
                    choices=["single", "multi", "both"])
    ap.add_argument("--zero", type=int, default=3)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--param-dtype", default="float32",
                    choices=["float32", "bfloat16"],
                    help="bfloat16 = bf16 params + fp32 master weights "
                         "(halves grad reductions and ZeRO gathers)")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--compress", default="none")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action=argparse.BooleanOptionalAction,
                    default=False)
    ap.add_argument("--verbose", action=argparse.BooleanOptionalAction,
                    default=False)
    ap.add_argument("--mesh-shape", default="",
                    help="logical re-composition of the same chips, e.g. "
                         "'64,4' (data,model) — the paper's recompose knob")
    args = ap.parse_args()

    archs = list(ASSIGNED_ARCHS) if args.arch == "all" else [args.arch]
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    n_ok = n_fail = 0
    for arch in archs:
        cfg = get_config(arch)
        shapes = ([s.name for s in applicable_shapes(cfg)]
                  if args.shape == "all" else [args.shape])
        for shape_name in shapes:
            for mesh_kind in meshes:
                r = run_cell(arch, shape_name, mesh_kind, args, args.out)
                n_ok += r is not None
                n_fail += r is None
    print(f"\ndry-run complete: {n_ok} ok, {n_fail} failed")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
