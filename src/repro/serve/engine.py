"""Serving engine: prefill + decode steps with distributed KV/state caches.

The decode path is what the ``decode_32k`` / ``long_500k`` cells lower:
one new token per sequence against a cache of ``seq_len`` history.  Cache
placement follows ``core.policy.cache_specs``:

  * batch over the dp axes,
  * attention cache *length* over the tp axis (flash-decode layout: each
    model rank holds a slice of history; the softmax combines partial
    max/sum via the collectives GSPMD inserts for the sharded reduction —
    no rank ever materializes the full cache, which for 32k x 128 x 40L
    would blow past HBM),
  * SSM / RG-LRU state channels over the tp axis.

Two engines sit on top of the jitted steps:

  * ``ServeEngine`` — the dense-slot baseline: sequences occupy slots of
    a fixed-size batch with per-slot ``max_seq``-wide caches.  Kept as
    the reference implementation (the paged path must match its logits
    bit-for-bit at fp32) and as the execution mode for architectures
    whose caches don't page (ring buffers, recurrent state);
  * ``AsyncServeEngine`` — the production shape: a paged KV cache
    (``serve.kvcache``: shared page pool, block tables, prefix-hash
    reuse), an SLO-aware request scheduler (``serve.scheduler``) with
    chunked prefill interleaved against the decode batch, decode-step
    batching in power-of-two batch buckets, and per-request telemetry
    (``cluster.telemetry.ServingStats``).
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.cluster.telemetry import ServingStats
from repro.configs.base import ATTN, ModelConfig, PolicyConfig, ShapeConfig
from repro.core import policy as pol
from repro.models import lm, scopes, transformer
from repro.serve import kvcache
from repro.serve.scheduler import (DECODE, PREFILL, RequestScheduler,
                                   ServeRequest)
from repro.train.trainer import make_run_ctx


def bucket_pow2(n: int, floor: int = 32) -> int:
    """Next power of two >= n (>= floor): the shape buckets of the
    jitted steps, so one trace serves every size in a bucket."""
    b = floor
    while b < n:
        b *= 2
    return b


# ---------------------------------------------------------------------------
# step builders (jit-able; used by launch.dryrun and ServeEngine)
# ---------------------------------------------------------------------------
def make_prefill_step(cfg: ModelConfig, policy: PolicyConfig, *,
                      cache_capacity: int, mesh=None,
                      bucketed: bool = False) -> Callable:
    """prefill(params, tokens) -> (last-token logits, caches).

    ``bucketed=True`` returns ``prefill(params, tokens, length)`` for
    pow2-padded prompts: ``tokens`` (B, S_bucket) right-padded, ``length``
    (B,) int32 real lengths.  Padded columns are masked end to end —
    attention caches mark them empty, recurrent/SSM state passes through
    them unchanged — and the logits are read at ``length - 1``, so one
    trace serves every prompt length in the bucket.
    """
    ctx = dataclasses.replace(
        make_run_ctx(cfg, policy, mesh),
        cache_capacity=cache_capacity)

    def prefill(params, tokens):
        hidden, caches, _ = lm.forward(params, tokens, cfg, ctx,
                                       caches="init", return_hidden=True)
        last = hidden[:, -1:]
        logits = lm.head_table(params, cfg)
        out = (last.astype(ctx.compute_dtype)
               @ logits.astype(ctx.compute_dtype).T)
        return out, caches

    def prefill_bucketed(params, tokens, length):
        B, S = tokens.shape[0], tokens.shape[1]
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        mask = positions < length[:, None]
        hidden, caches, _ = lm.forward(params, tokens, cfg, ctx,
                                       positions=positions, caches="init",
                                       kv_mask=mask, return_hidden=True)
        last = hidden[jnp.arange(B), length - 1][:, None]
        logits = lm.head_table(params, cfg)
        out = (last.astype(ctx.compute_dtype)
               @ logits.astype(ctx.compute_dtype).T)
        return out, caches

    return prefill_bucketed if bucketed else prefill


def make_decode_step(cfg: ModelConfig, policy: PolicyConfig,
                     mesh=None) -> Callable:
    """decode(params, caches, tokens, positions) -> (logits, caches).

    tokens (B, 1) int32 (or (B, 1, d) embeddings); positions (B, 1) int32.
    """
    ctx = make_run_ctx(cfg, policy, mesh)

    def decode(params, caches, tokens, positions):
        logits, new_caches, _ = lm.forward(params, tokens, cfg, ctx,
                                           positions=positions,
                                           caches=caches)
        return logits, new_caches

    return decode


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16):
    return transformer.init_stack_cache(cfg, batch, max_seq, dtype)


def greedy_sample(logits: jnp.ndarray) -> jnp.ndarray:
    return jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]


# ---------------------------------------------------------------------------
# slot-based continuous batching
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Request:
    rid: int
    prompt: jnp.ndarray            # (S,) int32
    max_new: int = 16
    out: List[int] = dataclasses.field(default_factory=list)
    done: bool = False


class ServeEngine:
    """Minimal continuous-batching server over the decode step.

    Slots are prefilling/decoding independently: a finished sequence frees
    its slot immediately (no head-of-line blocking).  Single-host demo
    semantics; the jitted steps themselves are the production artifacts.
    """

    def __init__(self, cfg: ModelConfig, params, policy: PolicyConfig, *,
                 n_slots: int = 4, max_seq: int = 512, mesh=None):
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.ctx_dtype = jnp.bfloat16 \
            if policy.compute_dtype == "bfloat16" else jnp.float32
        self.decode = jax.jit(make_decode_step(cfg, policy, mesh))
        self.prefill = jax.jit(
            make_prefill_step(cfg, policy, cache_capacity=max_seq,
                              mesh=mesh))
        self.caches = init_caches(cfg, n_slots, max_seq, self.ctx_dtype)
        self.slot_req: List[Optional[Request]] = [None] * n_slots
        self.slot_pos = jnp.zeros((n_slots,), jnp.int32)
        self.slot_tok = jnp.zeros((n_slots, 1), jnp.int32)

    # -- batched-prefill note: per-slot prefill keeps the demo simple; the
    # -- benchmark harness lowers the full-batch prefill step instead.
    def add_request(self, req: Request) -> bool:
        for s, cur in enumerate(self.slot_req):
            if cur is None:
                self._prefill_into_slot(s, req)
                return True
        return False

    def _prefill_into_slot(self, s: int, req: Request) -> None:
        toks = req.prompt[None, :]
        logits, caches = self.prefill(self.params, toks)
        nxt = greedy_sample(logits)
        # scatter the single-sequence cache into slot s; scanned segments
        # carry a leading layer-stack dim, so batch is dim 1 there
        segs = transformer.plan_segments(self.cfg.pattern)
        self.caches = kvcache.scatter_slot(self.caches, caches, s, segs)
        self.slot_req[s] = req
        self.slot_pos = self.slot_pos.at[s].set(req.prompt.shape[0])
        self.slot_tok = self.slot_tok.at[s].set(nxt[0])
        req.out.append(int(nxt[0, 0]))

    def step(self) -> int:
        """One decode step for all active slots; returns #active."""
        active = [s for s, r in enumerate(self.slot_req) if r is not None]
        if not active:
            return 0
        pos = self.slot_pos[:, None]
        logits, self.caches = self.decode(
            self.params, self.caches, self.slot_tok, pos)
        nxt = greedy_sample(logits)
        self.slot_tok = nxt
        self.slot_pos = self.slot_pos + jnp.asarray(
            [1 if self.slot_req[s] is not None else 0
             for s in range(self.n_slots)], jnp.int32)
        for s in active:
            req = self.slot_req[s]
            req.out.append(int(nxt[s, 0]))
            if len(req.out) >= req.max_new:
                req.done = True
                self.slot_req[s] = None
        return len(active)


# ---------------------------------------------------------------------------
# AsyncServeEngine: paged KV cache + SLO scheduler + chunked prefill
# ---------------------------------------------------------------------------
class AsyncServeEngine:
    """Production-shaped serving engine.

    One ``step()`` is one engine iteration.  In the default **fused**
    mode (true continuous batching) admission is followed by a SINGLE
    jitted step over a mixed batch: every decode row (one token each)
    plus prefill chunks packed up to the scheduler's ``token_budget``
    (``RequestScheduler.iteration_plan``) — prefill never runs as a
    separate step that stalls decode, and a long prompt trickles through
    the budget while queued short requests keep making their TTFT
    deadlines.  ``fused=False`` keeps the legacy two-step iteration (one
    batched prefill-chunk step, then one batched decode step) as the
    comparison/equivalence baseline; both orderings produce bit-identical
    fp32 logits per request because masking is purely positional.

    ``warmup()`` pre-compiles the paged step's jit traces so latency
    percentiles measure steady state; the compile time is reported
    separately (``report()["compile_s"]``).

    Execution modes:
      * ``paged``  — all-attention architectures: block tables over a
        shared page pool; the dense cache view exists only inside the
        jitted step (one gather), new K/V scatters straight back to the
        pool.  Prefill is *only* chunk steps — a prefix-cache hit simply
        starts the first chunk at the first uncached token;
      * ``dense``  — ring-buffer / recurrent-state architectures: per-slot
        dense caches (the ``ServeEngine`` layout) under the same
        scheduler, admission, and telemetry; no paging or prefix reuse.

    ``mode="auto"`` picks per architecture.  ``clock`` is injectable for
    deterministic tests (defaults to ``time.monotonic``).

    ``tracker`` is an optional ``repro.tracking.Run`` (default: the
    process-wide ``tracking.current_run()``); with one active, every
    ``track_every`` engine iterations one windowed metrics row is logged
    (TTFT/TPOT percentiles so far, queue depth, SLO attainment,
    window throughput) plus a system sample of KV-page occupancy.
    """

    def __init__(self, cfg: ModelConfig, params, policy: PolicyConfig, *,
                 n_slots: int = 4, max_seq: int = 512, page_size: int = 16,
                 n_pages: Optional[int] = None, prefill_chunk: int = 64,
                 prefill_batch: int = 2, token_budget: Optional[int] = None,
                 fused: bool = True, sched_policy: str = "slo",
                 mode: str = "auto", mesh=None, clock=None,
                 tracker=None, track_every: int = 16,
                 request_timeout_s: float = 0.0):
        self.cfg = cfg
        self.params = params
        self.policy = policy
        self.n_slots = n_slots
        self.max_seq = max_seq
        self.prefill_chunk = prefill_chunk
        self.fused = fused
        self.request_timeout_s = request_timeout_s
        self._draining = False
        self.clock = clock or time.monotonic
        self.ctx_dtype = jnp.bfloat16 \
            if policy.compute_dtype == "bfloat16" else jnp.float32
        if mode == "auto":
            mode = "paged" if all(b == ATTN for b in cfg.pattern) \
                else "dense"
        self.mode = mode
        self.segs = transformer.plan_segments(cfg.pattern)
        self.sched = RequestScheduler(
            max_slots=n_slots, max_prompt=max_seq,
            prefill_chunk=prefill_chunk, prefill_batch=prefill_batch,
            token_budget=token_budget, policy=sched_policy)
        self.stats = ServingStats()
        self.compile_s = 0.0           # accumulated warmup() compile time
        self._util_sum = 0.0           # sum of per-iteration utilization
        self.ctx = dataclasses.replace(make_run_ctx(cfg, policy, mesh),
                                       cache_capacity=max_seq)
        self._iters = 0
        self.tracker = tracker
        self.track_every = max(int(track_every), 1)
        self._win_completed = 0
        self._win_tokens = 0
        self._win_t: Optional[float] = None
        if self.mode == "paged":
            self.pool = kvcache.PagePool(
                cfg,
                n_pages=n_pages or n_slots * (-(-max_seq // page_size)),
                page_size=page_size, dtype=self.ctx_dtype)
            self._paged_step = jax.jit(self._paged_step_fn,
                                       donate_argnums=(1,))
        else:
            self.pool = None
            self.caches = init_caches(cfg, n_slots, max_seq, self.ctx_dtype)
            self.slot_req: List[Optional[ServeRequest]] = [None] * n_slots
            # pow2-bucketed one-shot prefill: prompts are right-padded to
            # the next power of two, so the trace count is O(log max_seq)
            # instead of one retrace per distinct prompt length
            self.prefill = jax.jit(make_prefill_step(
                cfg, policy, cache_capacity=max_seq, mesh=mesh,
                bucketed=True))
            self.decode = jax.jit(make_decode_step(cfg, policy, mesh))

    # ------------------------------------------------------------ plumbing --
    def now(self) -> float:
        return self.clock()

    def submit(self, req: ServeRequest) -> bool:
        """Admission-queue a request; False = rejected (with reason in
        ``req.why_rejected`` — the scheduler owns the capacity check)."""
        now = self.now()
        self.stats.mark(now)
        self.stats.requests_submitted += 1
        if self._draining:
            req.t_submit = now
            req.state = "rejected"
            req.why_rejected = "engine draining (planned detach)"
            self.sched.rejected.append(req)
            self.stats.requests_rejected += 1
            return False
        ok = self.sched.submit(req, now)
        if not ok:
            self.stats.requests_rejected += 1
        return ok

    def drain(self) -> None:
        """Planned detach announced: stop admitting new requests and let
        the in-flight ones finish (``run()`` then returns once the
        admitted population drains)."""
        self._draining = True

    def _expire_timeouts(self, now: float) -> None:
        """Cancel every request older than ``request_timeout_s`` and give
        its cache space back.  Half-written prefix pages are NOT
        registered for reuse — a timed-out prompt must not poison the
        prefix cache."""
        if self.request_timeout_s <= 0:
            return
        for req in (list(self.sched.waiting) + list(self.sched.active)):
            if now - req.t_submit <= self.request_timeout_s:
                continue
            was_active = req.state in (PREFILL, DECODE)
            if not self.sched.cancel(
                    req, f"timed out after {self.request_timeout_s:g}s"):
                continue
            self.stats.requests_timed_out += 1
            self.stats.requests_failed += 1
            if was_active and req.table is not None:
                if self.mode == "paged":
                    self.pool.release(req.table)
                else:
                    self.slot_req[req.table] = None
                req.table = None

    def _try_open(self, req: ServeRequest) -> bool:
        if self.mode == "paged":
            try:
                table, n_cached = self.pool.open_sequence(
                    req.prompt, req.max_new)
            except kvcache.PageError:
                return False
            req.table, req.n_cached = table, n_cached
            return True
        for s, cur in enumerate(self.slot_req):
            if cur is None:
                self.slot_req[s] = req
                req.table = s
                return True
        return False

    def _finish(self, req: ServeRequest, now: float) -> None:
        if self.mode == "paged":
            self.pool.close_sequence(req.prompt, req.table)
            req.table = None
        else:
            self.slot_req[req.table] = None
        self.stats.add_request(
            t_done=now, wait_s=req.queue_wait_s(), ttft_s=req.ttft_s(),
            tpot_s=req.tpot_s(), prompt_tokens=req.prompt_len,
            cached_tokens=req.n_cached, output_tokens=len(req.out),
            slo_ok=req.slo_met())

    # ------------------------------------------------------- paged stepping --
    def _paged_step_fn(self, params, pages, tables, toks, positions, valid,
                       last_idx):
        """One jitted paged step (chunk prefill when S>1, decode at S=1):
        gather the dense view, run the stack, scatter the new K/V back to
        the pool, return greedy next tokens at ``last_idx``."""
        dense = kvcache.gather_dense(pages, tables, self.segs)
        hidden, new_caches, _ = lm.forward(
            params, toks, self.cfg, self.ctx, positions=positions,
            caches=dense, return_hidden=True)
        pages = kvcache.scatter_tokens(
            pages, new_caches, tables, positions, valid,
            self.pool.page_size, self.segs, self.pool.trash)
        h = hidden[jnp.arange(toks.shape[0]), last_idx]
        table_w = lm.head_table(params, self.cfg)
        with jax.named_scope(scopes.HEAD):
            logits = (h.astype(self.ctx.compute_dtype)
                      @ table_w.astype(self.ctx.compute_dtype).T)
            nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        return nxt, logits, pages

    def _table_width(self, reqs: List[ServeRequest]) -> int:
        """Bucketed block-table width for this batch (shared jit key)."""
        need = max(len(r.table) for r in reqs)
        cap = self.pool.pages_for(self.max_seq)
        return min(bucket_pow2(need, floor=1), cap)

    def _paged_pass(self, plan: List[Tuple[ServeRequest, int]],
                    W: int) -> int:
        """ONE jitted paged step over ``plan``'s rows, each ``(request,
        tokens this step)``: a decode row carries its last token, a
        prefill row its next chunk, each padded to width ``W``.  Padded
        columns carry positions AFTER the row's valid tokens (causal
        masking excludes them) and their K/V scatter lands on the scratch
        page, so each row's logits do not depend on what it is batched
        with.  Returns the tokens processed."""
        reqs = [r for r, _ in plan]
        with scopes.serve_span("inputs"):
            toks, poss, vals, last = [], [], [], []
            for r, n in plan:
                if r.state == DECODE:
                    p0 = r.prompt_len + len(r.out) - 1
                    toks.append([r.out[-1]] + [0] * (W - 1))
                    poss.append([p0 + i for i in range(W)])
                    vals.append([True] + [False] * (W - 1))
                    last.append(0)
                else:
                    row = [int(t) for t in
                           r.prompt[r.prefilled:r.prefilled + n]]
                    toks.append(row + [0] * (W - n))
                    poss.append(list(range(r.prefilled, r.prefilled + W)))
                    vals.append([i < n for i in range(W)])
                    last.append(n - 1)
            args = self._paged_args(reqs, jnp.asarray(toks, jnp.int32),
                                    jnp.asarray(poss, jnp.int32),
                                    jnp.asarray(vals, bool),
                                    jnp.asarray(last, jnp.int32))
        with scopes.serve_span("device"):
            nxt, _, self.pool.pages = self._paged_step(
                self.params, self.pool.pages, *args)
            jax.block_until_ready(nxt)
        with scopes.serve_span("tokens"):
            now = self.now()    # token timestamps see the finished step
            done_tokens = 0
            for i, (r, n) in enumerate(plan):
                done_tokens += n
                if r.state == DECODE:
                    r.table.n_tokens += 1
                    if self.sched.note_token(r, int(nxt[i]), now):
                        self._finish(r, now)
                    continue
                r.table.n_tokens = r.prefilled + n
                self.sched.note_prefilled(r, n, now)
                if r.state == DECODE:
                    # prompt complete: register its full pages now — they
                    # are immutable from this point, so concurrent
                    # shared-prefix requests can hit them while this one
                    # is still decoding — and the chunk's last hidden IS
                    # the first generated token (no separate "first
                    # decode" step)
                    self.pool.register_prefix(r.prompt, r.table)
                    if self.sched.note_token(r, int(nxt[i]), now):
                        self._finish(r, now)
        return done_tokens

    def _paged_args(self, reqs: List[ServeRequest], toks, positions, valid,
                    last_idx):
        """The step's arguments after the pool: block tables at the
        bucketed width, every row array padded to the pow2 batch bucket
        (padding rows write to the scratch page)."""
        P = self._table_width(reqs)
        B = len(reqs)
        pad = min(bucket_pow2(B, floor=1), self.n_slots) - B
        tables = jnp.stack(
            [self.pool.padded_table(r.table, P) for r in reqs]
            + [jnp.full((P,), self.pool.trash, jnp.int32)] * pad)
        if pad:
            zcol = jnp.zeros((pad, toks.shape[1]), jnp.int32)
            toks = jnp.concatenate([toks, zcol])
            positions = jnp.concatenate([positions, zcol])
            valid = jnp.concatenate(
                [valid, jnp.zeros((pad, valid.shape[1]), bool)])
            last_idx = jnp.concatenate([last_idx, zcol[:, 0]])
        return tables, toks, positions, valid, last_idx

    def warmup(self, max_tokens: Optional[int] = None) -> float:
        """Pre-compile the paged step's jit traces: every pow2 batch
        bucket x row width (1 and ``prefill_chunk``) at the table width
        serving ``max_tokens`` (default ``max_seq``).  Rows are
        all-invalid — K/V writes land on the scratch page, so pool state,
        request stats, and the prefix cache are untouched.  Returns the
        compile seconds (also accumulated into ``self.compile_s`` and
        reported separately so latency percentiles measure steady
        state)."""
        if self.mode != "paged":
            return 0.0
        t0 = time.perf_counter()
        cap = self.pool.pages_for(self.max_seq)
        P = min(bucket_pow2(self.pool.pages_for(max_tokens or self.max_seq),
                            floor=1), cap)
        sizes = sorted({min(bucket_pow2(b, floor=1), self.n_slots)
                        for b in range(1, self.n_slots + 1)})
        nxt = None
        for B in sizes:
            for W in (1, self.prefill_chunk):
                tables = jnp.full((B, P), self.pool.trash, jnp.int32)
                zeros = jnp.zeros((B, W), jnp.int32)
                nxt, _, self.pool.pages = self._paged_step(
                    self.params, self.pool.pages, tables, zeros, zeros,
                    jnp.zeros((B, W), bool), jnp.zeros((B,), jnp.int32))
        if nxt is not None:
            jax.block_until_ready(nxt)
        dt = time.perf_counter() - t0
        self.compile_s += dt
        return dt

    # ------------------------------------------------------- dense stepping --
    def _dense_prefill(self, now: float) -> int:
        work = self.sched.prefill_work()
        if not work:
            return 0
        done = 0
        for req in work[:1]:          # one-shot prefill, one request/iter
            s = req.table
            L = req.prompt_len
            # pad to the pow2 bucket (capped at capacity): every length in
            # the bucket shares one compiled trace
            Spad = min(bucket_pow2(L, floor=16), self.max_seq)
            row = list(map(int, req.prompt)) + [0] * (Spad - L)
            toks = jnp.asarray([row], jnp.int32)
            length = jnp.asarray([L], jnp.int32)
            logits, one = self.prefill(self.params, toks, length)
            nxt = greedy_sample(logits)
            self.caches = kvcache.scatter_slot(self.caches, one, s,
                                               self.segs)
            done += req.prompt_len
            self.sched.note_prefilled(req, req.prompt_len, now)
            if self.sched.note_token(req, int(nxt[0, 0]), now):
                self._finish(req, now)
        return done

    def _dense_decode(self, now: float) -> int:
        work = [r for r in self.sched.decode_work() if r.out]
        if not work:
            return 0
        toks = [[0]] * self.n_slots
        pos = [[0]] * self.n_slots
        for r in work:
            toks[r.table] = [r.out[-1]]
            pos[r.table] = [r.prompt_len + len(r.out) - 1]
        logits, self.caches = self.decode(
            self.params, self.caches, jnp.asarray(toks, jnp.int32),
            jnp.asarray(pos, jnp.int32))
        nxt = greedy_sample(logits)
        for r in list(work):
            if self.sched.note_token(r, int(nxt[r.table, 0]), now):
                self._finish(r, now)
        return len(work)

    # ---------------------------------------------------------------- loop --
    def step(self) -> int:
        """One engine iteration; returns tokens processed (prefill +
        decode) so callers can loop ``while eng.step() or not
        eng.sched.all_done()``."""
        now = self.now()
        self._iters += 1
        with scopes.serve_span("admit"):
            self._expire_timeouts(now)
            self.sched.admit(now, self._try_open)
        if self.mode == "paged":
            if self.fused:
                # true continuous batching: decode rows and prefill chunks
                # in ONE step, per the token-budget plan; rows pad to 1
                # (pure decode) or the chunk, so the trace count stays
                # O(log n_slots) x 2
                with scopes.serve_span("plan"):
                    plan = self.sched.iteration_plan()
                    pure = all(r.state == DECODE for r, _ in plan)
                n = self._paged_pass(
                    plan, 1 if pure else self.prefill_chunk) if plan else 0
            else:
                # two steps: the batched prefill chunks, then every decode
                with scopes.serve_span("plan"):
                    plan = [(r, self.sched.chunk_for(r))
                            for r in self.sched.prefill_work()]
                n = self._paged_pass(plan, self.prefill_chunk) if plan else 0
                with scopes.serve_span("plan"):
                    plan = [(r, 1) for r in self.sched.decode_work() if r.out]
                n += self._paged_pass(plan, 1) if plan else 0
            self._util_sum += self.pool.utilization()
        else:
            n = self._dense_prefill(now)
            n += self._dense_decode(now)
        if self._iters % self.track_every == 0:
            self._track_window(now)
        return n

    def _track_window(self, now: float) -> None:
        """Log one windowed metrics row to the active tracking run."""
        from repro import tracking
        run = self.tracker or tracking.current_run()
        if run is None:
            return
        s = self.stats
        dt = now - self._win_t if self._win_t is not None else 0.0
        row = {
            "iter": self._iters,
            "queue_depth": len(self.sched.waiting),
            "active": len(self.sched.active),
            "completed": s.requests_completed,
            "window_completed": s.requests_completed - self._win_completed,
            "window_tok_s": ((s.output_tokens - self._win_tokens) / dt
                             if dt > 0 else 0.0),
            "slo_attainment": s.slo_met / max(s.requests_completed, 1),
        }
        if s.ttft_s:
            row["ttft_p50_s"] = ServingStats._dist(s.ttft_s)["p50"]
        if s.tpot_s:
            row["tpot_p50_s"] = ServingStats._dist(s.tpot_s)["p50"]
        run.log(row, step=self._iters)
        if self.pool is not None:
            kv = self.pool.stats()
            run.log_system({"kv.pages_in_use": kv["in_use"],
                            "kv.hit_rate": kv["hit_rate"]})
        self._win_completed = s.requests_completed
        self._win_tokens = s.output_tokens
        self._win_t = now

    def run(self, max_iters: int = 1_000_000) -> None:
        """Drive until every submitted request finished or nothing moves."""
        for _ in range(max_iters):
            if self.sched.all_done():
                return
            if self.step() == 0 and not self.sched.active:
                return            # starved: nothing admitted, nothing runs

    # -------------------------------------------------------------- report --
    def report(self) -> Dict[str, Any]:
        rep = self.stats.report()
        rep["mode"] = self.mode
        rep["fused"] = self.fused
        rep["iterations"] = self._iters
        rep["compile_s"] = self.compile_s
        if self.pool is not None:
            kv = self.pool.stats()
            # mean occupancy over engine iterations; "utilization" alone
            # is the post-drain sample (always 0 once requests finished)
            kv["mean_utilization"] = self._util_sum / max(self._iters, 1)
            rep["kv_pages"] = kv
        return rep
