"""Serving cells: the program's paged ``AsyncServeEngine`` under open-loop
traffic, timed over the window, checked against the plain reference.

Set-up makes the weights from the seed in one jitted call (in the dtype
the traffic file serves), builds the engine with the deployment settings
of the traffic file, warms every program the window can reach (see
``warm``), and runs the traffic for ``preroll_s`` before the window opens,
so that the window opens on a steady queue.  Requests are submitted when
due; each is timed from its due time, not from when it was submitted.
The window's requests are waited for (up to ``tail_s`` past the close);
one that never finishes counts as failed.  Then a sample of the finished
requests, drawn from the seed and holding the one with the most output,
is compared with the plain float32 reference: for every served token, the
gap by which its reference logit lies below the reference's best.
"""
from __future__ import annotations

import gc
import sys
import time
from typing import Any, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops, harness, reference, traffic
from bench.harness import Cell

# the reduction of a traced window: the serve-step program, and the host
# span marking when requests were live
TRACE_ARGS: Dict[str, Any] = {"programs": ("_paged_step",),
                              "live": "bench:live"}


def bucket_pow2(n: int) -> int:
    return 1 << max(0, (int(n) - 1).bit_length())


def build(cell: Cell, devs, seed: int):
    from repro.configs.base import PolicyConfig
    from repro.serve import AsyncServeEngine

    t, c = cell.traffic, cell.config
    e = t["engine"]
    cfg = harness.program_config(c)
    harness.check_layout(cfg, c, cell.model)
    dt = jnp.dtype(t["weights_dtype"])
    one = jax.sharding.SingleDeviceSharding(devs[0])
    params = jax.jit(lambda k: harness.to_program(
        cell.model.init(k, c, dt), c), out_shardings=one)(
            harness.seed_key(seed))
    policy = PolicyConfig(compute_dtype=t["compute_dtype"], remat="none")
    eng = AsyncServeEngine(
        cfg, params, policy, n_slots=e["n_slots"], max_seq=e["max_seq"],
        page_size=e["page_size"], prefill_chunk=e["prefill_chunk"],
        prefill_batch=e["prefill_batch"], sched_policy=e["sched_policy"],
        mode="paged", fused=True)
    return eng, params


def table_widths(t: Dict[str, Any]) -> List[int]:
    """Every block-table width (pages, pow2) a batch of this mix can use."""
    e = t["engine"]
    pages = lambda n: -(-n // e["page_size"])
    cap = pages(e["max_seq"])
    lo = min(bucket_pow2(pages(traffic.shortest_request(t))), cap)
    hi = min(bucket_pow2(pages(traffic.longest_request(t))), cap)
    out, w = [], lo
    while w <= hi:
        out.append(w)
        w *= 2
    return out


def warm(eng, t: Dict[str, Any]) -> int:
    """Compile, before the window, every program the window can reach.

    * the serve step at every (batch bucket, row width) for each table
      width the mix can use (``AsyncServeEngine.warmup``);
    * the page pool's reset of every allocation size a request can ask for
      (allocate, then release, through the pool's own calls);
    * the small array operations of the engine's host loop, at every batch
      size, bucket, row width and table width: making rows and block
      tables from lists, stacking tables, padding rows, reading rows back.
    Returns the number of serve-step programs compiled."""
    from repro.serve import kvcache
    e = t["engine"]
    widths = table_widths(t)
    for w in widths:
        s = eng.warmup(max_tokens=w * e["page_size"])
        harness.log(f"serve step warmed at table width {w}: {s:.1f}s")
    pool = eng.pool
    for n in range(1, pool.pages_for(traffic.longest_request(t)) + 1):
        pool.release(kvcache.BlockTable(pool.allocate(n)))
    harness.log("page resets warmed")
    S, C = e["n_slots"], e["prefill_chunk"]
    buckets = sorted({min(bucket_pow2(b), S) for b in range(1, S + 1)})
    for w in widths:
        jnp.asarray([0] * w, jnp.int32)
        jnp.full((w,), pool.trash, jnp.int32)
        for b in buckets:
            jnp.stack([jnp.zeros((w,), jnp.int32)] * b)
    for W in (1, C):
        for B in range(1, S + 1):
            Bp = min(bucket_pow2(B), S)
            pad = Bp - B
            rows = jnp.asarray([[0] * W] * B, jnp.int32)
            jnp.asarray([[True] * W] * B, bool)
            jnp.asarray([0] * B, jnp.int32)
            if pad:
                z = jnp.zeros((pad, W), jnp.int32)
                jnp.concatenate([rows, z])
                jnp.concatenate([jnp.zeros((B, W), bool),
                                 jnp.zeros((pad, W), bool)])
                jnp.concatenate([rows[:, 0], z[:, 0]])
    for B in range(1, S + 1):
        Bp = min(bucket_pow2(B), S)
        nxt = jnp.zeros((Bp,), jnp.int32)
        jnp.zeros((Bp, eng.cfg.padded_vocab), eng.ctx.compute_dtype)[:B]
        for i in range(B):
            int(nxt[i])
    harness.log("host-loop operations warmed")
    return eng._paged_step._cache_size()


def run(cell: Cell, devs, seed: int, seconds: float, trace_dir,
        t_start: float, counter) -> Dict[str, Any]:
    eng, params = build(cell, devs, seed)
    harness.log("weights made, engine built")
    n_programs = warm(eng, cell.traffic)
    jax.block_until_ready(eng.pool.pages)
    harness.log(f"{n_programs} serve-step programs warmed; traffic starts")
    out = drive(eng, cell, seed, seconds, trace_dir, t_start, counter)
    out["peak_bytes"] = harness.peak_bytes(devs)
    del eng, params
    gc.collect()
    out["readings"] = {"logit_gap": check(cell, seed, out["samples"], devs,
                                          "float32")}
    harness.log("reference compared")
    return out


def drive(eng, cell: Cell, seed: int, seconds: float, trace_dir,
          t_start: float, counter) -> Dict[str, Any]:
    """The traffic of ``cell`` through ``eng``: ``preroll_s`` of it, the
    window, then the window's requests waited for."""
    from repro.serve import ServeRequest
    from repro.serve.scheduler import WAITING
    t, c = cell.traffic, cell.config
    arr = t["arrivals"]
    preroll, tail = arr["preroll_s"], arr["tail_s"]
    sched = traffic.serve_schedule(t, seed, c["vocab_size"],
                                   preroll + seconds)
    clock = time.monotonic
    t_base = clock()
    t0, t_close = t_base + preroll, t_base + preroll + seconds
    reqs: List[Dict[str, Any]] = []
    live: List[Dict[str, Any]] = []
    nxt_i = 0
    in_window = False
    closed = False
    win_span, live_span, waiting_at_close = None, None, 0
    snap0 = snap1 = None
    useful = 0.0
    setup_s = None

    def snap():
        return (eng.pool.hit_tokens, eng.pool.miss_tokens)

    while True:
        now = clock()
        if not in_window and not closed and now >= t0:
            in_window = True
            setup_s = now - t_start
            counter.counting = True
            snap0 = snap()
            harness.log("window opens")
            if trace_dir:
                jax.profiler.start_trace(str(trace_dir))
                win_span = harness.span(trace_dir, "bench:window")
                win_span.__enter__()
        if in_window and now >= t_close:
            in_window, closed = False, True
            counter.counting = False
            snap1 = snap()
            waiting_at_close = len(eng.sched.waiting)
            harness.log("window closes")
            if trace_dir:
                if live_span is not None:
                    live_span.__exit__(None, None, None)
                    live_span = None
                win_span.__exit__(None, None, None)
                jax.profiler.stop_trace()
        if closed:
            pending = [r for r in reqs if r["window"] and not r["end"]]
            if not pending or now > t_close + tail:
                break
        # --- the load generator: submit what is due
        if not closed:
            with harness.span(trace_dir, "bench:generator"):
                while nxt_i < len(sched) and \
                        t_base + sched[nxt_i].due <= now:
                    a = sched[nxt_i]
                    req = ServeRequest(nxt_i, a.prompt, max_new=a.max_new)
                    due = t_base + a.due
                    r = {"req": req, "due": due, "lag": now - due,
                         "times": [], "end": False,
                         "window": t0 <= due < t_close}
                    reqs.append(r)
                    if eng.submit(req):
                        live.append(r)
                    else:
                        r["end"] = True
                    nxt_i += 1
        busy = bool(eng.sched.waiting or eng.sched.active)
        if trace_dir and in_window:
            if busy and live_span is None:
                live_span = harness.span(trace_dir, "bench:live")
                live_span.__enter__()
            elif not busy and live_span is not None:
                live_span.__exit__(None, None, None)
                live_span = None
        if not busy:
            nxt_due = (t_base + sched[nxt_i].due) if nxt_i < len(sched) \
                else now + 1e-3
            with harness.span(trace_dir, "bench:idle"):
                time.sleep(max(0.0, min(nxt_due, t_close + 1e-3) - now)
                           if not closed else 1e-3)
            continue
        before = [(r["req"].prefilled, len(r["req"].out),
                   r["req"].state == WAITING) for r in live]
        with harness.span(trace_dir, "bench:eng.step"):
            eng.step()
        tt = clock()
        with harness.span(trace_dir, "bench:bookkeeping"):
            still = []
            for r, (pre0, out0, waiting) in zip(live, before):
                q = r["req"]
                new = len(q.out) - out0
                r["times"] += [tt] * new
                if in_window:
                    useful += _useful(c, q, pre0, out0, waiting, new)
                if q.done or q.state in ("timed_out", "rejected"):
                    r["end"] = True
                else:
                    still.append(r)
            live = still

    # --- what the window measured
    win = [r for r in reqs if r["window"]]
    done = [r for r in win if r["req"].done]
    ttft = [r["times"][0] - r["due"] for r in done]
    itl = [b - a for r in done for a, b in zip(r["times"], r["times"][1:])]
    out_tok = sum(1 for r in reqs for x in r["times"] if t0 <= x < t_close)
    values = {
        "serve_output_tokens_per_s": out_tok / seconds,
        "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95)) if ttft
        else float("inf"),
        "itl_p95_ms": 1e3 * float(np.percentile(itl, 95)) if itl
        else float("inf"),
        "setup_s": setup_s,
    }
    print(f"window: {len(win)} requests due, {len(done)} finished, "
          f"{out_tok} tokens out, {len(itl)} token gaps, "
          f"{len(eng.sched.waiting)} waiting at the close",
          file=sys.stderr)
    meas = {"kind": "serve", "chips": cell.chips, "window_s": seconds,
            "useful_flops": useful,
            "hit_tokens": snap1[0] - snap0[0],
            "miss_tokens": snap1[1] - snap0[1],
            "gen_lag_s": [r["lag"] for r in win]}
    return {"values": values,
            "ok_extra": len(done) == len(win) and len(win) > 0,
            "attempted": len(win), "failed": len(win) - len(done),
            "compiles_in_window": counter.n,
            "compiles_named": counter.names, "meas": meas,
            "samples": _sample(done, seed, t["check"]),
            "waiting_at_close": waiting_at_close}


def _useful(c, q, pre0: int, out0: int, waiting: bool, new: int) -> float:
    """Model FLOPs of the work one step did for request ``q``: its prompt
    tokens computed (not those served from the prefix cache) and its
    decoded token, each at its position."""
    start = q.n_cached if waiting else pre0
    d_pre = q.prefilled - start
    if d_pre > 0:
        return flops.serve_tokens(c, start, d_pre, with_head=new)
    if new:
        return flops.serve_tokens(c, q.prompt_len + out0 - 1, 1, 1)
    return 0.0


def _sample(done, seed: int, chk: Dict[str, Any]):
    """The finished requests that are checked: the one with the most
    output, then others drawn from the seed until ``min_tokens`` served
    tokens or ``max_requests`` requests."""
    if not done:
        return []
    order = sorted(done, key=lambda r: -len(r["req"].out))
    first, rest = order[0], order[1:]
    rng = harness.seed_rng(seed, 7)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    picked = [first]
    n = len(first["req"].out)
    for r in rest:
        if n >= chk["min_tokens"] or len(picked) >= chk["max_requests"]:
            break
        picked.append(r)
        n += len(r["req"].out)
    return [(list(map(int, r["req"].prompt)), list(map(int, r["req"].out)))
            for r in picked]


def gap_readings(cell: Cell, seed: int, samples, devs, mm_names):
    """For each named precision: the widest gap, over every served token
    of ``samples``, by which the float32 reference's logit of a token lies
    below its best.  ``"float32"`` reads the served tokens; any other
    precision reads the tokens that precision's model puts first at the
    same positions (the control)."""
    t, c = cell.traffic, cell.config
    L, n_out = t["engine"]["max_seq"], t["output"]["max"]
    with jax.default_matmul_precision("highest"):
        params = jax.jit(lambda k: cell.model.init(k, c, jnp.float32),
                         out_shardings=jax.sharding.SingleDeviceSharding(
                             devs[0]))(harness.seed_key(seed))
        fns = {m: reference.make_served_logits(cell.model, c,
                                               reference.MM[m], n_out)
               for m in set(mm_names) | {"float32"}}
        worst = {m: 0.0 for m in mm_names}
        for prompt, out in samples:
            toks = np.zeros(L, np.int32)
            seq = prompt + out
            toks[:len(seq)] = seq
            toks = jax.device_put(toks, devs[0])
            start = jnp.int32(len(prompt))
            ref = fns["float32"](params, toks, start)[:len(out)]
            best = jnp.max(ref, -1)
            for m in mm_names:
                if m == "float32":
                    pick = jnp.asarray(out, jnp.int32)
                else:
                    pick = jnp.argmax(fns[m](params, toks, start)[:len(out)],
                                      -1)
                gap = best - jnp.take_along_axis(ref, pick[:, None], -1)[:, 0]
                worst[m] = max(worst[m], float(jnp.max(gap)))
    return worst


def check(cell: Cell, seed: int, samples, devs, mm_name: str) -> float:
    if not samples:
        return float("inf")
    return gap_readings(cell, seed, samples, devs, [mm_name])[mm_name]
