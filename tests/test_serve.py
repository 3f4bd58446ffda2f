"""Serving stack: paged KV cache, chunked prefill, scheduler, engine.

The acceptance bar for the paged/chunked path is *exactness*: chunked
prefill over a paged pool must reproduce the one-shot dense-cache logits
(same greedy continuation) at fp32.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, reduced
from repro.configs.base import PolicyConfig
from repro.models import lm
from repro.serve import (SLO, AsyncServeEngine, PageError, PagePool,
                         Request, RequestScheduler, ServeEngine,
                         ServeRequest)
from repro.serve.engine import bucket_pow2
from repro.serve.scheduler import DECODE
from repro.train.trainer import make_run_ctx

POLICY = PolicyConfig(compute_dtype="float32", remat="none",
                      attn_impl="full")


@pytest.fixture(scope="module")
def small_lm(rng):
    cfg = reduced(get_config("qwen2-0.5b"))
    return cfg, lm.init_lm(rng, cfg)


def _prompt(seed: int, n: int, vocab: int):
    return list(np.random.RandomState(seed).randint(0, vocab, n))


# ---------------------------------------------------------------------------
# page pool unit behaviour
# ---------------------------------------------------------------------------
def _pool(cfg, n_pages=12, page_size=8):
    return PagePool(cfg, n_pages=n_pages, page_size=page_size)


def test_page_alloc_free_recycles(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg)
    t, cached = pool.open_sequence(_prompt(0, 20, 100), max_new=4)
    assert cached == 0
    assert len(t) == pool.pages_for(24) == 3
    assert pool.in_use == 3
    pool.release(t)
    assert pool.in_use == 0 and len(t) == 0


def test_page_pool_exhaustion_raises(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, n_pages=4)
    pool.open_sequence(_prompt(0, 20, 100), max_new=4)    # 3 pages
    with pytest.raises(PageError):
        pool.open_sequence(_prompt(1, 20, 100), max_new=4)
    assert pool.in_use == 3                   # failed open rolled back


def test_prefix_hash_hits_and_retention(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg)
    prompt = _prompt(7, 20, 100)              # 2 full pages + tail
    t1, c1 = pool.open_sequence(prompt, max_new=4)
    assert c1 == 0
    pool.close_sequence(prompt, t1)           # registers + retains
    t2, c2 = pool.open_sequence(prompt, max_new=4)
    assert c2 == 2 * pool.page_size           # both full pages reused
    assert pool.hit_tokens == 16
    # a different prompt shares nothing
    other = _prompt(8, 20, 100)
    _, c3 = pool.open_sequence(other, max_new=4)
    assert c3 == 0


def test_reused_prefix_page_is_not_evictable(small_lm):
    """Regression: a by_hash prefix hit must pull the page out of the
    retained LRU — otherwise eviction under pool pressure hands a page
    that a live sequence still references to a new sequence (silent KV
    corruption + later double-free)."""
    cfg, _ = small_lm
    pool = _pool(cfg, n_pages=6, page_size=8)
    prompt = _prompt(5, 17, 100)              # 3 pages, 2 hashable
    t1, _ = pool.open_sequence(prompt, max_new=4)
    pool.close_sequence(prompt, t1)           # 2 retained, 1 free
    t2, c2 = pool.open_sequence(prompt, max_new=4)   # reuse both pages
    assert c2 == 16
    assert not pool.retained                  # live pages left the LRU
    assert pool.in_use == 3                   # accounting sees them live
    with pytest.raises(PageError):            # only 3 pages truly free
        pool.open_sequence(_prompt(6, 28, 100), max_new=4)
    # the live table was never cannibalized
    assert all(pool.ref[p] == 1 for p in t2.pages)


def test_prefix_hit_verifies_token_content(small_lm):
    """A chain-hash collision must degrade to a miss, never re-link
    another prompt's KV pages."""
    cfg, _ = small_lm
    pool = _pool(cfg)
    prompt = _prompt(9, 20, 100)
    t1, _ = pool.open_sequence(prompt, max_new=4)
    pool.close_sequence(prompt, t1)
    page = next(p for p in range(pool.n_pages)
                if pool.page_hash[p] is not None)
    pool.page_key[page] = (0, ("collision",))    # same hash, other tokens
    _, cached = pool.open_sequence(prompt, max_new=4)
    assert cached == 0


def test_retained_pages_evicted_lru(small_lm):
    cfg, _ = small_lm
    pool = _pool(cfg, n_pages=6, page_size=8)
    p1 = _prompt(1, 17, 100)                  # 3 pages, 2 hashable
    t1, _ = pool.open_sequence(p1, max_new=4)
    pool.close_sequence(p1, t1)               # 2 retained + 1 free
    assert len(pool.retained) == 2
    p2 = _prompt(2, 40, 100)                  # needs 6 pages -> evicts
    t2, _ = pool.open_sequence(p2, max_new=4)
    assert len(t2) == 6 and pool.evictions >= 2


# ---------------------------------------------------------------------------
# chunked prefill == one-shot prefill (model level)
# ---------------------------------------------------------------------------
def test_chunked_prefill_matches_one_shot(small_lm):
    cfg, params = small_lm
    ctx = dataclasses.replace(
        make_run_ctx(cfg, POLICY, None), cache_capacity=32)
    toks = jnp.asarray([_prompt(3, 21, cfg.vocab_size)])
    h1, c1, _ = lm.forward(params, toks, cfg, ctx, caches="init",
                           return_hidden=True)
    caches = None
    h = None
    for s, e in ((0, 8), (8, 16), (16, 21)):      # uneven chunks
        pos = jnp.arange(s, e)[None, :]
        h, caches, _ = lm.forward(
            params, toks[:, s:e], cfg, ctx, positions=pos,
            caches=("init" if caches is None else caches),
            return_hidden=True)
    np.testing.assert_allclose(np.asarray(h1[:, -1]), np.asarray(h[:, -1]),
                               atol=1e-5)
    # every cache leaf identical too (positions, K, V)
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(caches)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_chunked_prefill_matches_windowed(small_lm):
    """Sliding-window layers: chunks larger than the window stay exact."""
    cfg, _ = small_lm
    cfg = dataclasses.replace(
        cfg, block_pattern=("attn_local",) * cfg.n_layers, local_window=6)
    params = lm.init_lm(jax.random.PRNGKey(1), cfg)
    ctx = dataclasses.replace(
        make_run_ctx(cfg, POLICY, None), cache_capacity=32)
    toks = jnp.asarray([_prompt(4, 20, cfg.vocab_size)])
    h1, _, _ = lm.forward(params, toks, cfg, ctx, caches="init",
                          return_hidden=True)
    h, caches = None, None
    for s, e in ((0, 8), (8, 16), (16, 20)):
        pos = jnp.arange(s, e)[None, :]
        h, caches, _ = lm.forward(
            params, toks[:, s:e], cfg, ctx, positions=pos,
            caches=("init" if caches is None else caches),
            return_hidden=True)
    np.testing.assert_allclose(np.asarray(h1[:, -1]), np.asarray(h[:, -1]),
                               atol=1e-5)


# ---------------------------------------------------------------------------
# engine end-to-end
# ---------------------------------------------------------------------------
def _engine(cfg, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("max_seq", 96)
    kw.setdefault("page_size", 8)
    kw.setdefault("prefill_chunk", 16)
    return AsyncServeEngine(cfg, params, POLICY, **kw)


def test_paged_engine_matches_teacher_forcing(small_lm):
    cfg, params = small_lm
    eng = _engine(cfg, params)
    assert eng.mode == "paged"
    reqs = [ServeRequest(i, _prompt(10 + i, 20 + 5 * i, cfg.vocab_size),
                         max_new=5) for i in range(4)]
    for r in reqs:
        assert eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    ctx = make_run_ctx(cfg, POLICY, None)
    for r in reqs[:2]:
        toks = list(r.prompt)
        for expect in r.out:
            logits, _, _ = lm.forward(params, jnp.asarray([toks]), cfg, ctx)
            assert int(jnp.argmax(logits[0, -1])) == expect
            toks.append(expect)


def test_paged_engine_output_invariant_under_reuse(small_lm):
    """Prefix-cache hits change TTFT, never tokens."""
    cfg, params = small_lm
    shared = _prompt(42, 33, cfg.vocab_size)

    def run(slots):
        eng = _engine(cfg, params, n_slots=slots)
        reqs = [ServeRequest(i, shared + _prompt(50 + i, 5, cfg.vocab_size),
                             max_new=4) for i in range(4)]
        for r in reqs:
            eng.submit(r)
        eng.run()
        return eng, reqs

    e1, r1 = run(2)
    e2, r2 = run(3)
    assert e1.pool.hit_tokens > 0             # later requests reuse prefix
    for a, b in zip(r1, r2):
        assert a.out == b.out
    assert e1.pool.in_use == 0                # full recycling


def test_engine_rejects_overlong_prompt(small_lm):
    cfg, params = small_lm
    eng = _engine(cfg, params)
    bad = ServeRequest(0, _prompt(0, 95, cfg.vocab_size), max_new=8)
    assert not eng.submit(bad)
    assert bad.state == "rejected" and "capacity" in bad.why_rejected


def test_dense_mode_serves_recurrent_arch(rng):
    cfg = reduced(get_config("recurrentgemma-2b"))
    params = lm.init_lm(rng, cfg)
    eng = AsyncServeEngine(cfg, params, POLICY, n_slots=2, max_seq=64)
    assert eng.mode == "dense"
    reqs = [ServeRequest(i, _prompt(i, 12, cfg.vocab_size), max_new=3)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)


def test_seq_dims_bucket_to_pow2():
    """The jitted steps' shape buckets: the next power of two, from the
    floor up, so one trace serves every size in a bucket."""
    assert bucket_pow2(384) == bucket_pow2(512) == 512
    assert bucket_pow2(513) == 1024
    assert bucket_pow2(3) == 32                   # the default floor
    assert [bucket_pow2(n, floor=1) for n in (1, 2, 3, 5, 8)] == \
        [1, 2, 4, 8, 8]


def test_dense_prefill_traces_constant_across_prompt_lengths(rng):
    """Regression (ROADMAP open item): the dense fallback's one-shot
    prefill pads prompts to pow2 buckets — four distinct lengths in one
    bucket compile ONE trace, and a second bucket adds exactly one."""
    cfg = reduced(get_config("recurrentgemma-2b"))
    params = lm.init_lm(rng, cfg)
    eng = AsyncServeEngine(cfg, params, POLICY, n_slots=2, max_seq=64)
    assert eng.mode == "dense"
    reqs = [ServeRequest(i, _prompt(i, n, cfg.vocab_size), max_new=2)
            for i, n in enumerate((9, 11, 13, 15))]   # all bucket to 16
    for r in reqs:
        eng.submit(r)
    eng.run()
    assert all(r.done for r in reqs)
    assert eng.prefill._cache_size() == 1
    eng.submit(ServeRequest(9, _prompt(9, 25, cfg.vocab_size), max_new=2))
    eng.run()
    assert eng.prefill._cache_size() == 2             # one new bucket


@pytest.mark.parametrize("arch", ["recurrentgemma-2b", "mamba2-780m"])
def test_bucketed_prefill_exact_for_recurrent_archs(arch):
    """Padded columns must not leak into recurrent/conv/ring state: the
    pow2-padded prefill reproduces the exact-length prefill bit-for-bit
    (fp32 tolerance) — logits, recurrent states, conv tails, and the
    masked attention cache slots."""
    from repro.serve.engine import make_prefill_step
    cfg = reduced(get_config(arch))
    params = lm.init_lm(jax.random.PRNGKey(0), cfg)
    exact = make_prefill_step(cfg, POLICY, cache_capacity=32)
    bucket = make_prefill_step(cfg, POLICY, cache_capacity=32,
                               bucketed=True)
    L = 21
    toks = _prompt(3, L, cfg.vocab_size)
    lo, c1 = exact(params, jnp.asarray([toks]))
    lb, c2 = bucket(params, jnp.asarray([toks + [0] * (32 - L)]),
                    jnp.asarray([L], jnp.int32))
    np.testing.assert_allclose(np.asarray(lo), np.asarray(lb), atol=1e-5)
    flat1 = jax.tree_util.tree_flatten_with_path(c1)[0]
    flat2 = jax.tree_util.tree_flatten_with_path(c2)[0]
    for (path, a), (_, b) in zip(flat1, flat2):
        a, b = np.asarray(a), np.asarray(b)
        assert a.shape == b.shape, path
        key = str(getattr(path[-1], "key", ""))
        if key == "pos":
            np.testing.assert_array_equal(a, b, err_msg=str(path))
        elif key in ("k", "v"):
            # padded slots are masked by pos = -1; real slots must match
            pos = next(np.asarray(x) for p, x in flat2
                       if p[:-1] == path[:-1]
                       and str(getattr(p[-1], "key", "")) == "pos")
            np.testing.assert_allclose(a[pos >= 0], b[pos >= 0],
                                       atol=1e-5, err_msg=str(path))
        else:          # recurrent state / conv tails: exact everywhere
            np.testing.assert_allclose(a, b, atol=1e-5, err_msg=str(path))


def test_engine_telemetry_report(small_lm):
    cfg, params = small_lm
    eng = _engine(cfg, params)
    for i in range(3):
        eng.submit(ServeRequest(i, _prompt(i, 20, cfg.vocab_size),
                                max_new=4))
    eng.run()
    rep = eng.report()
    assert rep["requests"]["completed"] == 3
    assert rep["ttft_s"]["p50"] > 0
    assert rep["output_tokens"] == 12
    assert rep["kv_pages"]["in_use"] == 0


def test_legacy_dense_engine_still_serves(small_lm):
    """The dense baseline ServeEngine keeps working (and is what the
    paged path is equivalence-tested against)."""
    cfg, params = small_lm
    eng = ServeEngine(cfg, params, POLICY, n_slots=2, max_seq=64)
    req = Request(0, jnp.asarray(_prompt(1, 16, cfg.vocab_size)), max_new=4)
    assert eng.add_request(req)
    while not req.done:
        eng.step()
    assert len(req.out) == 4


# ---------------------------------------------------------------------------
# fused continuous batching
# ---------------------------------------------------------------------------
def test_fused_engine_matches_unfused_tokens(small_lm):
    """Continuous batching changes latency, never tokens: the fused
    mixed-batch iteration and the legacy alternating prefill/decode
    iterations produce identical greedy streams."""
    cfg, params = small_lm

    def serve(fused):
        eng = _engine(cfg, params, n_slots=3, fused=fused)
        reqs = [ServeRequest(i, _prompt(20 + i, 12 + 7 * i, cfg.vocab_size),
                             max_new=5) for i in range(5)]
        for r in reqs:
            assert eng.submit(r)
        eng.run()
        return eng, reqs

    ef, rf = serve(True)
    eu, ru = serve(False)
    assert ef.fused and not eu.fused
    for a, b in zip(rf, ru):
        assert a.done and a.out == b.out
    assert ef.pool.in_use == 0 and eu.pool.in_use == 0


def test_fused_mixed_batch_rows_bit_exact(small_lm):
    """A decode row and a prefill-chunk row fused into ONE batch produce
    logits bit-identical to the same rows run alone (same row width, same
    table width, padding row in place) — rows in the mixed batch must not
    interact."""
    cfg, params = small_lm
    C = 8
    eng = _engine(cfg, params, n_slots=2, prefill_chunk=C)
    r0 = ServeRequest(0, _prompt(60, 12, cfg.vocab_size), max_new=4)
    assert eng.submit(r0)
    eng.step()                         # chunk 1 (8 tokens)
    eng.step()                         # chunk 2 -> DECODE, first token
    assert r0.state == DECODE and len(r0.out) == 1
    r1 = ServeRequest(1, _prompt(61, 20, cfg.vocab_size), max_new=4)
    assert eng.submit(r1)
    eng.sched.admit(eng.now(), eng._try_open)
    plan = eng.sched.iteration_plan()
    assert [(r.rid, n) for r, n in plan] == [(0, 1), (1, C)]

    # build the fused rows exactly as _paged_pass does (W = chunk)
    p0 = r0.prompt_len + len(r0.out) - 1
    toks = [[r0.out[-1]] + [0] * (C - 1),
            [int(t) for t in r1.prompt[:C]]]
    poss = [[p0 + i for i in range(C)], list(range(C))]
    vals = [[True] + [False] * (C - 1), [True] * C]
    last = [0, C - 1]
    P = eng._table_width([r0, r1])
    tables = jnp.stack([eng.pool.padded_table(r.table, P) for r in (r0, r1)])
    snap = jax.tree.map(jnp.array, eng.pool.pages)   # _paged_step donates

    def step(tab, tk, ps, vl, lx):
        _, logits, _ = eng._paged_step(
            eng.params, jax.tree.map(jnp.array, snap), tab,
            jnp.asarray(tk, jnp.int32), jnp.asarray(ps, jnp.int32),
            jnp.asarray(vl, bool), jnp.asarray(lx, jnp.int32))
        return np.asarray(logits)

    mixed = step(tables, toks, poss, vals, last)
    pad_t = jnp.full((P,), eng.pool.trash, jnp.int32)
    zrow = [0] * C
    for i in range(2):
        alone = step(jnp.stack([tables[i], pad_t]),
                     [toks[i], zrow], [poss[i], zrow],
                     [vals[i], [False] * C], [last[i], 0])
        assert np.array_equal(mixed[i], alone[0]), f"row {i} diverged"


def test_iteration_plan_packs_token_budget():
    """Decode rows always ride (1 token each); prefill chunks pack the
    remaining budget in policy order, the last clipped to fit."""
    sched = RequestScheduler(max_slots=8, max_prompt=64, prefill_chunk=8,
                             prefill_batch=2, token_budget=7)
    dec = [ServeRequest(i, [1] * 4, max_new=4) for i in (0, 1)]
    pre = [ServeRequest(i, [1] * 20, max_new=4) for i in (2, 3)]
    for r in dec:
        r.state = DECODE
        r.out = [1]
    for r in pre:
        r.state = "prefill"
    sched.active = dec + pre
    plan = [(r.rid, n) for r, n in sched.iteration_plan()]
    # budget 7: 2 decode tokens, then ONE chunk clipped 8 -> 5
    assert plan == [(0, 1), (1, 1), (2, 5)]
    sched.token_budget = 100           # roomy: both chunks, unclipped,
    plan = [(r.rid, n) for r, n in sched.iteration_plan()]
    assert plan == [(0, 1), (1, 1), (2, 8), (3, 8)]


def test_warmup_is_pure_and_reports_compile(small_lm):
    """warmup() compiles the paged step without touching pool accounting,
    stats, or the served tokens; compile time lands in ``compile_s`` (and
    report()), not in the latency percentiles."""
    cfg, params = small_lm
    eng = _engine(cfg, params)
    dt = eng.warmup()
    assert dt > 0 and eng.compile_s == dt
    kv = eng.pool.stats()
    assert kv["in_use"] == 0 and kv["allocations"] == 0
    assert kv["hit_tokens"] == 0 and kv["peak_in_use"] == 0
    assert eng.stats.requests_submitted == 0

    def serve(e):
        reqs = [ServeRequest(i, _prompt(70 + i, 14, cfg.vocab_size),
                             max_new=4) for i in range(3)]
        for r in reqs:
            e.submit(r)
        e.run()
        return [r.out for r in reqs]

    cold = _engine(cfg, params)        # never warmed
    assert serve(eng) == serve(cold)
    assert eng.report()["compile_s"] == dt
    assert cold.report()["compile_s"] == 0.0


def test_report_peak_and_mean_utilization(small_lm):
    """kv_pages reports the high-water mark and the per-iteration mean,
    not just the post-drain sample (always 0 once requests finish)."""
    cfg, params = small_lm
    eng = _engine(cfg, params)
    for i in range(4):
        eng.submit(ServeRequest(i, _prompt(80 + i, 20, cfg.vocab_size),
                                max_new=4))
    eng.run()
    kv = eng.report()["kv_pages"]
    assert kv["in_use"] == 0                       # drained
    assert 0 < kv["peak_utilization"] <= 1
    assert 0 < kv["mean_utilization"] <= kv["peak_utilization"]
    assert kv["peak_in_use"] == round(kv["peak_utilization"] * kv["n_pages"])


# ---------------------------------------------------------------------------
# request scheduler policies
# ---------------------------------------------------------------------------
def test_scheduler_slo_orders_by_deadline():
    sched = RequestScheduler(max_slots=4, max_prompt=64, policy="slo")
    lax_ = ServeRequest(0, [1] * 8, slo=SLO(ttft_s=9.0))
    tight = ServeRequest(1, [1] * 8, slo=SLO(ttft_s=0.5))
    sched.submit(lax_, now=0.0)
    sched.submit(tight, now=0.1)
    admitted = sched.admit(0.2, lambda r: True)
    for r in admitted:
        r.state = DECODE
    assert admitted[0].rid == 1               # tighter deadline first


def test_scheduler_priority_and_fcfs():
    for policy, first in (("priority", 1), ("fcfs", 0)):
        sched = RequestScheduler(max_slots=1, max_prompt=64, policy=policy)
        sched.submit(ServeRequest(0, [1] * 8, priority=0), now=0.0)
        sched.submit(ServeRequest(1, [1] * 8, priority=5), now=0.1)
        admitted = sched.admit(0.2, lambda r: True)
        assert admitted[0].rid == first, policy


def test_scheduler_rejects_oversized_and_interleaves_chunks():
    sched = RequestScheduler(max_slots=4, max_prompt=32, prefill_chunk=8,
                             prefill_batch=2)
    assert not sched.submit(ServeRequest(0, [1] * 40), now=0.0)
    # a 0-token decode budget can't be honored (first token comes from
    # the prefill's last hidden state)
    assert not sched.submit(ServeRequest(9, [1] * 8, max_new=0), now=0.0)
    long_req = ServeRequest(1, [1] * 24, max_new=4)
    sched.submit(long_req, now=0.0)
    sched.admit(0.0, lambda r: True)
    assert sched.chunk_for(long_req) == 8     # chunked, not all 24
    sched.note_prefilled(long_req, 8, 0.1)
    assert long_req.state != DECODE
    sched.note_prefilled(long_req, 16, 0.2)
    assert long_req.state == DECODE
