"""End-to-end training driver.

Runs a real training loop (synthetic data, AdamW, checkpoints, elastic
restart) on the default device: the CPU for the examples and tests, one
chip on a TPU host.  The step over a mesh of several chips is
``trainer.make_train_step(mesh=...)`` + ``trainer.jit_train_step``.
Compile time is printed apart from step time.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --steps 5 --batch 4 --seq 2048 --dtype bfloat16   # full width, 1 chip
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b \
      --preset 100m --steps 200 --batch 8 --seq 256 --ckpt /tmp/ck
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-3b \
      --reduced --steps 20 --resume auto
  PYTHONPATH=src python -m repro.launch.train --arch mamba2-780m \
      --reduced --steps 30 --fail-at 12   # simulated failure + elastic resume
"""
from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from repro.configs import get_config, reduced
from repro.configs.base import ModelConfig, PolicyConfig, ShapeConfig
from repro.data import SyntheticDataset
from repro.launch import compile_cache
from repro.optim import AdamWConfig, ScheduleConfig
from repro.train import checkpoint, trainer


def preset_100m(cfg: ModelConfig) -> ModelConfig:
    """~100M-param same-family config (the deliverable-(b) target size)."""
    return dataclasses.replace(
        reduced(cfg, n_layers=min(12, cfg.n_layers), width_div=4,
                vocab=32768),
        name=cfg.name + "-100m")


def build(args):
    cfg = get_config(args.arch)
    if args.preset == "100m":
        cfg = preset_100m(cfg)
    elif args.reduced:
        cfg = reduced(cfg)
    policy = PolicyConfig(
        compute_dtype=args.dtype, remat=args.remat,
        attn_impl="xla", zero_stage=args.zero,
        grad_accum=args.grad_accum)
    optcfg = AdamWConfig(lr=args.lr)
    schedcfg = ScheduleConfig(peak_lr=args.lr, warmup_steps=args.warmup,
                              total_steps=args.steps)
    return cfg, policy, optcfg, schedcfg


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--preset", default="", choices=["", "100m"])
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=False)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=10)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--remat", default="block")
    ap.add_argument("--zero", type=int, default=3)
    ap.add_argument("--grad-accum", type=int, default=1)
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", default="", choices=["", "auto"])
    ap.add_argument("--fail-at", type=int, default=0,
                    help="simulate a crash at this step (elastic test)")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--track", action="store_true",
                    help="record the run via repro.tracking "
                         "(results/runs/<run_id>/events.jsonl)")
    return ap.parse_args(argv)


def run(args: argparse.Namespace) -> Dict[str, Any]:
    """The training loop.  Returns ``losses`` and ``grad_norms`` (one
    float each per step run), ``compile_s`` (lowering and compiling the
    step, apart from the steps), ``step_s`` (per step, each ended by
    ``block_until_ready``) and ``exit_code`` (17 after a simulated
    failure, else 0)."""
    cfg, policy, optcfg, schedcfg = build(args)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")

    tracked = None
    if args.track:
        from repro import tracking
        tracked = tracking.init(
            f"train-{args.arch}",
            config={"arch": args.arch, "preset": args.preset,
                    "steps": args.steps, "batch": args.batch,
                    "seq": args.seq, "lr": args.lr, "dtype": args.dtype,
                    "zero": args.zero, "grad_accum": args.grad_accum},
            tags=("train",), samplers=[tracking.ProcSampler()])
        print(f"tracking run {tracked.id} -> {tracked.path}")
    print(f"training {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"batch {args.batch} x seq {args.seq}, {args.steps} steps")

    state = trainer.init_state(jax.random.PRNGKey(0), cfg, policy, optcfg)
    start = 0
    if args.resume == "auto" and args.ckpt and \
            checkpoint.latest_step(args.ckpt) is not None:
        state, start = checkpoint.restore(args.ckpt, state)
        print(f"resumed from step {start}")

    # the state is donated: the old and the new state are never both
    # alive, which a full-width step needs to fit one chip
    ds = SyntheticDataset(cfg, shape)
    t0 = time.perf_counter()
    step_fn = jax.jit(
        trainer.make_train_step(cfg, policy, optcfg, schedcfg, shape=shape),
        donate_argnums=(0,)).lower(
            state, {k: jnp.asarray(v) for k, v in
                    ds.batch_at(start).items()}).compile()
    out: Dict[str, Any] = {"compile_s": time.perf_counter() - t0,
                           "losses": [], "grad_norms": [], "step_s": [],
                           "exit_code": 0}
    print(f"compiled train step in {out['compile_s']:.1f}s")
    stepper = trainer.StepTracker(shape.tokens, tracked)
    for step in range(start, args.steps):
        batch = {k: jnp.asarray(v) for k, v in ds.batch_at(step).items()}
        t = time.perf_counter()
        state, metrics = step_fn(state, batch)
        jax.block_until_ready(metrics)
        out["step_s"].append(time.perf_counter() - t)
        out["losses"].append(float(metrics["loss"]))
        out["grad_norms"].append(float(metrics["grad_norm"]))
        stepper.step(step, metrics)
        if args.ckpt and (step + 1) % args.ckpt_every == 0:
            checkpoint.save(args.ckpt, step + 1, state)
        if args.fail_at and step + 1 == args.fail_at:
            if args.ckpt:
                checkpoint.save(args.ckpt, step + 1, state)
            print(f"simulated failure at step {step + 1} — restart with "
                  f"--resume auto")
            if tracked is not None:
                stepper.summary()
                tracked.finish("failed")
            out["exit_code"] = 17
            return out
        if (step + 1) % args.log_every == 0 or step == start:
            toks = shape.tokens * (step + 1 - start)
            print(f"step {step + 1:5d}  loss {out['losses'][-1]:.4f}"
                  f"  grad_norm {out['grad_norms'][-1]:.3f}"
                  f"  step_s {out['step_s'][-1]:.3f}"
                  f"  tok/s {toks / sum(out['step_s']):.0f}")
    if args.ckpt:
        checkpoint.save(args.ckpt, args.steps, state)
    if tracked is not None:
        stepper.summary()
        tracked.finish()
    print(f"done: {len(out['step_s'])} steps in {sum(out['step_s']):.1f}s "
          f"(+{out['compile_s']:.1f}s compile)")
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    compile_cache.enable()
    return run(args)["exit_code"]


if __name__ == "__main__":
    raise SystemExit(main())
