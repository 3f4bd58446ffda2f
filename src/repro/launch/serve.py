"""Serving driver: batched requests through the async serving engine.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-0.5b \
      --requests 8 --max-new 16      # paged engine, continuous batching
  PYTHONPATH=src python -m repro.launch.serve --no-fused ...  # legacy
  PYTHONPATH=src python -m repro.launch.serve --no-reduced \
      --prompt-len 512 --min-prompt-len 64 --max-new 32 --max-seq 1024

The paged engine warms up (pre-compiles its jit traces) before serving
so TTFT/TPOT percentiles measure steady state; compile time is printed
separately (``--no-warmup`` to skip).

Requests whose prompt + decode budget exceed ``--max-seq`` are rejected
up front (exit code 2) — the engine never truncates silently.

``--request-timeout SECONDS`` puts a deadline on every request: instead
of hanging on a wedged engine, requests past the deadline are cancelled,
a per-request timeout report is printed, and the driver exits 3.
"""
from __future__ import annotations

import argparse
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import numpy as np

from repro.configs import get_config, reduced
from repro.configs.base import PolicyConfig
from repro.launch import compile_cache
from repro.models import lm
from repro.serve import AsyncServeEngine, ServeRequest


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="prompt length, or the longest with "
                         "--min-prompt-len")
    ap.add_argument("--min-prompt-len", type=int, default=0,
                    help="draw prompt lengths uniformly from "
                         "[min, --prompt-len] (0 = all --prompt-len)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=32)
    ap.add_argument("--sched", default="slo",
                    choices=["slo", "priority", "fcfs"])
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "paged", "dense"])
    ap.add_argument("--fused", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="continuous batching: fuse prefill chunks and "
                         "decode rows into one iteration (--no-fused "
                         "falls back to alternating batches)")
    ap.add_argument("--warmup", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="pre-compile the paged step's jit traces so "
                         "reported latencies are steady-state")
    ap.add_argument("--request-timeout", type=float, default=0.0,
                    help="per-request deadline in seconds (0 = none); "
                         "timed-out requests are cancelled and reported "
                         "instead of hanging the driver")
    return ap.parse_args(argv)


def make_prompts(args: argparse.Namespace, vocab: int) -> List[List[int]]:
    """Seeded prompts: ``--requests`` of them, lengths from
    ``[--min-prompt-len, --prompt-len]``."""
    rng = np.random.default_rng(0)
    lo = args.min_prompt_len or args.prompt_len
    lens = rng.integers(lo, args.prompt_len + 1, size=args.requests)
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def run(args: argparse.Namespace) -> Dict[str, Any]:
    """Serve the prompts.  Returns ``exit_code`` (0 when every request
    was served), ``outputs`` (generated tokens per request id) and the
    engine's ``report``."""
    if args.prompt_len + args.max_new > args.max_seq:
        print(f"error: prompt ({args.prompt_len}) + max-new "
              f"({args.max_new}) tokens exceed --max-seq ({args.max_seq}); "
              f"raise --max-seq or shorten the request")
        return {"exit_code": 2}
    if not 0 <= args.min_prompt_len <= args.prompt_len:
        print(f"error: --min-prompt-len ({args.min_prompt_len}) must lie "
              f"in [0, --prompt-len ({args.prompt_len})]")
        return {"exit_code": 2}

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = reduced(cfg)
    policy = PolicyConfig(compute_dtype="float32", remat="none",
                          attn_impl="full")
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    eng = AsyncServeEngine(
        cfg, params, policy, n_slots=args.slots, max_seq=args.max_seq,
        page_size=args.page_size, prefill_chunk=args.prefill_chunk,
        sched_policy=args.sched, mode=args.mode, fused=args.fused,
        request_timeout_s=args.request_timeout)
    if args.warmup and eng.mode == "paged":
        print(f"warmup: compiled paged step in {eng.warmup():.1f}s")

    reqs = [ServeRequest(i, p, max_new=args.max_new)
            for i, p in enumerate(make_prompts(args, cfg.vocab_size))]
    t0 = time.time()
    for req in reqs:
        if not eng.submit(req):
            print(f"error: request {req.rid} rejected: {req.why_rejected}")
            return {"exit_code": 2}
    eng.run()
    dt = time.time() - t0

    rep = eng.report()
    done = sum(r.done for r in reqs)
    print(f"served {done}/{len(reqs)} requests in {dt:.1f}s "
          f"[{rep['mode']} mode"
          f"{', fused' if rep.get('fused') else ''}] "
          f"tput={rep['throughput_tok_s']:.1f} tok/s "
          f"ttft_p50={rep['ttft_s']['p50']*1e3:.0f}ms "
          f"tpot_p50={rep['tpot_s']['p50']*1e3:.0f}ms "
          f"compile={rep['compile_s']:.1f}s")
    if "kv_pages" in rep:
        kv = rep["kv_pages"]
        print(f"kv pages: {kv['n_pages']}x{kv['page_size']}tok "
              f"hit_rate={kv['hit_rate']*100:.0f}% "
              f"evictions={kv['evictions']}")
    for r in reqs[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    out = {"outputs": {r.rid: list(r.out) for r in reqs}, "served": done,
           "report": rep, "exit_code": 0 if done == len(reqs) else 1}
    if eng.sched.cancelled:
        print(f"error: {len(eng.sched.cancelled)}/{len(reqs)} requests "
              f"timed out (--request-timeout {args.request_timeout:g}s):")
        for r in eng.sched.cancelled:
            print(f"  req {r.rid}: {r.why_rejected} "
                  f"({len(r.out)}/{r.max_new} tokens generated)")
        out["exit_code"] = 3
    return out


def main(argv: Optional[Sequence[str]] = None) -> int:
    args = parse_args(argv)
    compile_cache.enable()
    return run(args)["exit_code"]


if __name__ == "__main__":
    raise SystemExit(main())
