"""Autotuner + tuned-config registry + measured-cost calibration."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.costmodel import CalibratedCost
from repro.core import recommend
from repro.kernels import autotune, ops, registry

KEY = jax.random.PRNGKey(3)
K1, K2, K3 = jax.random.split(KEY, 3)


@pytest.fixture(autouse=True)
def _isolate_registry():
    """Tests control the active registry explicitly; no disk/env leakage."""
    registry.set_registry(None)
    yield
    registry.reset_registry()


# ---------------------------------------------------------------------------
# candidate enumeration
# ---------------------------------------------------------------------------
def test_candidate_enumeration_is_deterministic():
    for case in autotune.SMOKE_CASES + autotune.DEFAULT_CASES:
        a = autotune.candidates_for(case)
        b = autotune.candidates_for(case)
        assert a == b
        assert len(a) >= 1
        # deduped after clamping
        assert len({tuple(sorted(c.items())) for c in a}) == len(a)


def test_candidates_respect_divisibility():
    case = autotune.attn_case("flash_attention", S=96, D=32, G=2)
    for cand in autotune.candidates_for(case):
        assert 96 % cand["block_q"] == 0
        assert 96 % cand["block_k"] == 0


def test_ssd_rglru_candidates():
    assert autotune.candidates_for(autotune.ssd_case(S=128)) == [
        {"chunk": 32}, {"chunk": 64}, {"chunk": 128}]
    assert autotune.candidates_for(autotune.rglru_case(S=64)) == [
        {"block_seq": 16}, {"block_seq": 32}, {"block_seq": 64}]


# ---------------------------------------------------------------------------
# registry round-trip + dispatch resolution
# ---------------------------------------------------------------------------
def test_registry_round_trip(tmp_path):
    reg = registry.Registry()
    key = registry.make_key("flash_attention", dtype="float32",
                            variant="causal", s=128, t=128, d=32, g=2)
    reg.put(key, registry.TunedEntry(
        blocks={"block_q": 64, "block_k": 32}, us=10.0, default_us=20.0,
        n_candidates=9, backend="cpu"))
    path = reg.save(str(tmp_path / "tuned.json"))
    loaded = registry.Registry.load(path)
    assert len(loaded) == 1
    entry = loaded.get(key)
    assert entry.blocks == {"block_q": 64, "block_k": 32}
    assert entry.speedup == pytest.approx(2.0)
    # the resolver sees the same blocks after the round trip
    registry.set_registry(loaded)
    bq, bk = registry.attention_blocks(128, 128, 32, 2, jnp.float32,
                                       True, 0)
    assert (bq, bk) == (64, 32)


@pytest.mark.parametrize("backend,hit", [
    ("tpu", jax.default_backend() == "tpu"),
    ("cpu", jax.default_backend() == "cpu"),
    ("", True),
])
def test_lookup_misses_entries_tuned_on_another_backend(backend, hit):
    reg = registry.Registry()
    reg.put(registry.make_key("flash_attention", dtype="float32",
                              variant="causal", s=128, t=128, d=32, g=2),
            registry.TunedEntry(blocks={"block_q": 64, "block_k": 32},
                                backend=backend))
    registry.set_registry(reg)
    got = registry.attention_blocks(128, 128, 32, 2, jnp.float32, True, 0,
                                    defaults=(128, 128))
    assert got == ((64, 32) if hit else (128, 128))


def test_seq_dims_bucket_to_pow2():
    k1 = registry.make_key("flash_attention", dtype="float32",
                           variant="causal", s=384, t=384, d=64, g=4)
    k2 = registry.make_key("flash_attention", dtype="float32",
                           variant="causal", s=512, t=512, d=64, g=4)
    assert k1 == k2
    # head/feature dims stay exact
    k3 = registry.make_key("flash_attention", dtype="float32",
                           variant="causal", s=512, t=512, d=128, g=4)
    assert k3 != k2


def test_registry_miss_falls_back_to_defaults():
    registry.set_registry(registry.Registry())      # active but empty
    # at dims the defaults divide, the miss path returns them verbatim
    assert registry.attention_blocks(256, 256, 32, 2, jnp.float32,
                                     True, 0) == ops.DEFAULT_ATTN_BLOCKS
    assert registry.ssd_chunk(256, 4, 16, 1, 32, jnp.float32) == \
        ops.DEFAULT_SSD_CHUNK
    assert registry.rglru_block(128, 64, jnp.float32) == \
        ops.DEFAULT_RGLRU_BLOCK
    # at smaller dims they are fitted (same clamp the kernels apply)
    assert registry.attention_blocks(128, 128, 32, 2, jnp.float32,
                                     True, 0) == (128, 128)


def test_tuned_blocks_fit_non_pow2_sequences():
    """Pow2 bucketing may hand back blocks tuned at a neighbouring
    length; the resolver must fit them to the actual dim so the kernels'
    divisibility asserts hold (review regression: S=192 hitting a
    128-block cell tuned at the 256 bucket)."""
    reg = registry.Registry()
    reg.put(registry.make_key("flash_attention", dtype="float32",
                              variant="causal", s=192, t=192, d=32, g=2),
            registry.TunedEntry(blocks={"block_q": 128, "block_k": 128}))
    registry.set_registry(reg)
    bq, bk = registry.attention_blocks(192, 192, 32, 2, jnp.float32,
                                       True, 0)
    assert 192 % bq == 0 and 192 % bk == 0
    q = jax.random.normal(K1, (1, 192, 4, 32))
    k = jax.random.normal(K2, (1, 192, 2, 32))
    v = jax.random.normal(K3, (1, 192, 2, 32))
    out = ops.attention(q, k, v, impl="pallas")      # must not assert
    assert out.shape == q.shape


def test_xla_flash_fits_blocks_to_runtime_length():
    """Serve prefill traces with the actual prompt length, which need
    not be divisible by the build-time tuned tile (review regression:
    96-token prompt vs kv_block=64)."""
    from repro.models.attention import flash_attention_xla
    q = jax.random.normal(K1, (1, 96, 4, 32))
    k = jax.random.normal(K2, (1, 96, 2, 32))
    v = jax.random.normal(K3, (1, 96, 2, 32))
    out = flash_attention_xla(q, k, v, causal=True,
                              q_block=64, kv_block=64)   # 96 % 64 != 0
    ref = flash_attention_xla(q, k, v, causal=True,
                              q_block=96, kv_block=96)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


def test_default_blocks_divide_non_pow2_sequences():
    """The sweep's baseline config must be legal for every case (review
    regression: S=384 clamped default 256 crashed the fallback)."""
    for case in (autotune.attn_case("flash_attention", S=384, D=32, G=2),
                 autotune.attn_case("flash_attention_xla", S=96, D=32,
                                    G=2),
                 autotune.ssd_case(S=96), autotune.rglru_case(S=96)):
        d = autotune.default_blocks(case)
        for v in d.values():
            assert case.dim("s") % v == 0, (case.kernel, d)


def test_calibrated_utilization_stays_bounded():
    """A measured cell far below the analytic compute bound must not
    push busy fractions past 1 (review regression: AUU went negative)."""
    from repro.cluster import TraceConfig, run_trace
    cal = CalibratedCost()
    plan = recommend.recommend("qwen2-0.5b", "train_4k", n_chips=16,
                               top=1)[0]
    cal.measure_cell("qwen2-0.5b", "train_4k", plan.label,
                     plan.step_s / 100.0)
    rep = run_trace(TraceConfig(n_jobs=8, seed=2, calibration=cal))
    assert 0.0 <= rep["auu"] <= 1.0
    assert rep["accelerator_utilization"] <= 1.0


def test_fit_block():
    assert registry.fit_block(128, 192) == 96
    assert registry.fit_block(256, 256) == 256
    assert registry.fit_block(64, 64) == 64
    assert registry.fit_block(512, 100) == 100
    assert registry.fit_block(8, 97) == 1            # prime dim


def test_dispatch_keys_registry_by_impl():
    """pallas_vjp / xla lookups must hit their own kernels' cells, not
    the forward pallas cell (review regression)."""
    q = jax.random.normal(K1, (1, 64, 2, 32))
    k = jax.random.normal(K2, (1, 64, 2, 32))
    v = jax.random.normal(K3, (1, 64, 2, 32))
    reg = registry.Registry()
    # poison the forward cell with blocks that would fail if consumed
    # by the xla path's separate tuned entry
    reg.put(registry.make_key("flash_attention", dtype="float32",
                              variant="causal", s=64, t=64, d=32, g=1),
            registry.TunedEntry(blocks={"block_q": 16, "block_k": 16}))
    reg.put(registry.make_key("flash_attention_xla", dtype="float32",
                              variant="causal", s=64, t=64, d=32, g=1),
            registry.TunedEntry(blocks={"block_q": 32, "block_k": 32}))
    registry.set_registry(reg)
    a = ops.attention(q, k, v, impl="xla")
    b = ops.attention(q, k, v, impl="xla", block_q=32, block_k=32)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_registry_resolves_defaults():
    registry.set_registry(None)
    assert registry.attention_blocks(256, 256, 64, 4, jnp.bfloat16,
                                     True, 0) == (256, 256)


def test_malformed_registry_file_is_ignored(tmp_path, monkeypatch):
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    monkeypatch.setenv(registry.ENV_VAR, str(bad))
    registry.reset_registry()
    assert registry.get_registry() is None


# ---------------------------------------------------------------------------
# tuned configs preserve semantics
# ---------------------------------------------------------------------------
def test_tuned_rglru_bit_identical_to_default():
    """block_seq only re-tiles VMEM; the sequential recurrence order is
    unchanged, so tuned output must be bit-identical to the default."""
    log_a = -jax.nn.softplus(jax.random.normal(K1, (2, 128, 32)))
    gated = jax.random.normal(K2, (2, 128, 32))
    reg = registry.Registry()
    reg.put(registry.make_key("rglru", dtype="float32", s=128, w=32),
            registry.TunedEntry(blocks={"block_seq": 16}))
    default = ops.rglru(log_a, gated, impl="pallas")      # no registry
    registry.set_registry(reg)
    tuned = ops.rglru(log_a, gated, impl="pallas")
    np.testing.assert_array_equal(np.asarray(tuned), np.asarray(default))


def test_tuned_attention_matches_default():
    q = jax.random.normal(K1, (1, 128, 4, 32))
    k = jax.random.normal(K2, (1, 128, 2, 32))
    v = jax.random.normal(K3, (1, 128, 2, 32))
    default = ops.attention(q, k, v, impl="pallas")
    reg = registry.Registry()
    reg.put(registry.make_key("flash_attention", dtype="float32",
                              variant="causal", s=128, t=128, d=32, g=2),
            registry.TunedEntry(blocks={"block_q": 32, "block_k": 64}))
    registry.set_registry(reg)
    tuned = ops.attention(q, k, v, impl="pallas")
    np.testing.assert_allclose(tuned, default, atol=2e-5, rtol=2e-5)


def test_tuned_ssd_matches_default():
    x = jax.random.normal(K1, (1, 128, 4, 16))
    dt = jax.nn.softplus(jax.random.normal(K2, (1, 128, 4)))
    A = -jnp.exp(jax.random.normal(K3, (4,)))
    Bm = jax.random.normal(K1, (1, 128, 1, 32)) * 0.5
    Cm = jax.random.normal(K2, (1, 128, 1, 32)) * 0.5
    yd, hd = ops.ssd(x, dt, A, Bm, Cm, impl="pallas")
    reg = registry.Registry()
    reg.put(registry.make_key("ssd", dtype="float32",
                              s=128, h=4, p=16, g=1, n=32),
            registry.TunedEntry(blocks={"chunk": 32}))
    registry.set_registry(reg)
    yt, ht = ops.ssd(x, dt, A, Bm, Cm, impl="pallas")
    np.testing.assert_allclose(yt, yd, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(ht, hd, atol=2e-4, rtol=2e-4)


def test_explicit_blocks_override_registry():
    q = jax.random.normal(K1, (1, 64, 2, 32))
    k = jax.random.normal(K2, (1, 64, 2, 32))
    v = jax.random.normal(K3, (1, 64, 2, 32))
    reg = registry.Registry()
    reg.put(registry.make_key("flash_attention", dtype="float32",
                              variant="causal", s=64, t=64, d=32, g=1),
            registry.TunedEntry(blocks={"block_q": 32, "block_k": 32}))
    registry.set_registry(reg)
    out = ops.attention(q, k, v, impl="pallas", block_q=64, block_k=64)
    ref = ops.attention(q, k, v, impl="xla", block_q=64, block_k=64)
    np.testing.assert_allclose(out, ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the sweep itself (one small real cell)
# ---------------------------------------------------------------------------
def test_tune_case_rglru_end_to_end(tmp_path):
    case = autotune.rglru_case(S=64, W=16)
    res = autotune.tune_case(case, iters=1)
    assert res.entry.us > 0 and res.entry.default_us > 0
    assert res.entry.n_candidates == len(autotune.candidates_for(case))
    assert res.entry.blocks in autotune.candidates_for(case)
    # sweep persists + reloads
    reg, results = autotune.sweep([case], iters=1,
                                  path=str(tmp_path / "t.json"))
    assert len(reg) == 1 and len(results) == 1
    loaded = registry.Registry.load(str(tmp_path / "t.json"))
    assert loaded.get(case.key).blocks == reg.get(case.key).blocks
    js = json.load(open(str(tmp_path / "t.json")))
    assert js["version"] == 1 and case.key in js["configs"]


def test_decode_case_candidates_are_page_multiples():
    """Paged-decode kv superblocks gather whole pages: every candidate
    block_k is pages-per-block x page_size, block_q pinned to the single
    query row, ppb never exceeding the cache's page count."""
    case = autotune.decode_case(B=4, T=128, D=32, G=2, page_size=16)
    assert autotune.candidates_for(case) == [
        {"block_q": 1, "block_k": 16 * ppb} for ppb in (1, 2, 4, 8)]
    # a smaller cache clips the ppb ladder
    small = autotune.decode_case(B=2, T=32, D=32, G=2, page_size=16)
    assert autotune.candidates_for(small) == [
        {"block_q": 1, "block_k": 16}, {"block_q": 1, "block_k": 32}]


def test_tune_case_decode_end_to_end(tmp_path):
    """The decode cell sweeps like any other kernel: tune, persist,
    reload — and the serving-side resolver sees the winner."""
    case = autotune.decode_case(B=2, T=64, D=32, G=2, page_size=16)
    res = autotune.tune_case(case, iters=1)
    assert res.entry.us > 0 and res.entry.default_us > 0
    assert res.entry.blocks in autotune.candidates_for(case)
    reg, _ = autotune.sweep([case], iters=1,
                            path=str(tmp_path / "t.json"))
    loaded = registry.Registry.load(str(tmp_path / "t.json"))
    won = loaded.get(case.key).blocks
    assert won == reg.get(case.key).blocks
    registry.set_registry(loaded)
    assert registry.decode_attention_blocks(2, 64, 32, 2, jnp.float32) \
        == (won["block_q"], won["block_k"])


# ---------------------------------------------------------------------------
# measured-cost calibration changes decisions
# ---------------------------------------------------------------------------
def test_calibration_changes_recommend_ranking():
    """A measured step time for a non-winning mesh must be able to
    re-rank recommend() — the ISSUE's acceptance criterion."""
    arch, shape, chips = "qwen2-0.5b", "train_4k", 64
    plain = recommend.recommend(arch, shape, n_chips=chips, top=2)
    winner, runner_up = plain[0], plain[1]
    cal = CalibratedCost()
    # measurement says the analytic runner-up actually runs 10x faster
    cal.measure_cell(arch, shape, runner_up.label,
                     winner.step_s / 10.0)
    cald = recommend.recommend(arch, shape, n_chips=chips, top=2,
                               calibration=cal)
    assert cald[0].label == runner_up.label
    assert cald[0].label != plain[0].label
    assert cald[0].terms.get("measured") == pytest.approx(
        winner.step_s / 10.0)


def test_kernel_speedup_scales_compute_term():
    from repro.configs import get_config, SHAPES
    cfg = get_config("mamba2-780m")            # pure-SSM pattern
    shape = SHAPES["train_4k"]
    cal = CalibratedCost(kernel_speedup={"ssd": 2.0})
    scale = cal.compute_scale(cfg, shape)
    # FLOPs-weighted: only the SSD core accelerates; projections, FFN,
    # and logits keep weight 1.0, so 0.5 < scale < 1.0
    assert 0.5 < scale < 1.0
    # monotone in the measured speedup
    faster = CalibratedCost(kernel_speedup={"ssd": 4.0})
    assert faster.compute_scale(cfg, shape) < scale
    # untuned kernels change nothing
    other = CalibratedCost(kernel_speedup={"flash_attention": 4.0})
    assert other.compute_scale(cfg, shape) == pytest.approx(1.0)
    plain = recommend.recommend("mamba2-780m", "train_4k", n_chips=64,
                                top=1)[0]
    cald = recommend.recommend("mamba2-780m", "train_4k", n_chips=64,
                               top=1, calibration=cal)[0]
    assert cald.terms["compute"] == pytest.approx(
        plain.terms["compute"] * scale)


def test_set_calibration_reaches_existing_scheduler():
    """Process-wide set_calibration() must be honored by schedulers
    built before the call (review regression: construction-time
    snapshot)."""
    from repro.cluster.scheduler import Scheduler
    from repro.core.topology import make_pool
    sched = Scheduler(make_pool(n_local=8, n_switch=0, pods=1))
    assert sched.calibration is None
    cal = CalibratedCost(kernel_speedup={"ssd": 2.0})
    recommend.set_calibration(cal)
    try:
        assert sched.calibration is cal
    finally:
        recommend.set_calibration(None)
    assert sched.calibration is None


def test_calibration_flows_into_scheduler_admission_pricing():
    """The scheduler's plan (and therefore simulator pricing) uses the
    measured step time, changing which mesh a job is admitted on."""
    from repro.cluster.scheduler import Job, Scheduler
    from repro.core.topology import make_pool

    def best_plan(calibration):
        pool = make_pool(n_local=64, n_switch=0, pods=1)
        sched = Scheduler(pool, calibration=calibration)
        job = Job(name="j", arch="qwen2-0.5b", shape_name="train_4k",
                  n_chips=64)
        assert sched.submit(job, 0.0)
        return job.plan

    plain = best_plan(None)
    cal = CalibratedCost()
    # measure a different factorization as dramatically faster
    alt = [c for c in recommend.recommend(
        "qwen2-0.5b", "train_4k", n_chips=64, top=5)
        if c.label != plain.label][0]
    cal.measure_cell("qwen2-0.5b", "train_4k", alt.label,
                     plain.step_s / 100.0)
    cald = best_plan(cal)
    assert cald.label == alt.label
    assert cald.label != plain.label


def test_from_registry_builds_speedups():
    reg = registry.Registry()
    reg.put(registry.make_key("ssd", dtype="float32",
                              s=128, h=4, p=16, g=1, n=32),
            registry.TunedEntry(blocks={"chunk": 32}, us=50.0,
                                default_us=100.0))
    cal = CalibratedCost.from_registry(reg)
    assert cal.kernel_speedup["ssd"] == pytest.approx(2.0)
    # json round-trip
    cal2 = CalibratedCost.from_json(cal.to_json())
    assert cal2.kernel_speedup == cal.kernel_speedup


def test_interpret_default_is_backend_derived():
    # CPU test environment: the one-place default must say "interpret"
    assert ops.default_interpret() == (jax.default_backend() != "tpu")


# ---------------------------------------------------------------------------
# decode-shape buckets (the serving engine's (B, 1, cache_len) cells)
# ---------------------------------------------------------------------------
def test_decode_bucket_keys_batch_dim():
    # batch buckets pow2 from 1; cache length buckets like seq dims;
    # S is omitted from decode cells (always 1)
    k1 = registry.make_key("decode_attention", dtype="float32",
                           variant="causal", b=3, t=300, d=64, g=4)
    k2 = registry.make_key("decode_attention", dtype="float32",
                           variant="causal", b=4, t=512, d=64, g=4)
    assert k1 == k2                       # 3->4 and 300->512 share a cell
    assert "b=4" in k1 and "t=512" in k1 and "s=" not in k1


def test_decode_attention_blocks_resolve_and_fallback():
    # miss: defaults fitted (block_q pinned to the single query row;
    # block_k fitted to divide the cache length: 150 | 300)
    assert registry.decode_attention_blocks(
        4, 300, 64, 4, jnp.float32) == (1, 150)
    reg = registry.Registry()
    reg.put(registry.make_key("decode_attention", dtype="float32",
                              variant="causal", b=4, t=512, d=64, g=4),
            registry.TunedEntry(blocks={"block_q": 1, "block_k": 128},
                                us=10.0, default_us=20.0))
    registry.set_registry(reg)
    assert registry.decode_attention_blocks(
        3, 300, 64, 4, jnp.float32) == (1, 100)   # 128 fitted to T=300


def test_resolve_attn_blocks_covers_decode_shape():
    from repro.configs import get_config, reduced
    from repro.configs.base import PolicyConfig
    from repro.train.trainer import resolve_attn_blocks
    cfg = reduced(get_config("qwen2-0.5b"))
    pol = PolicyConfig(compute_dtype="float32")
    g = max(1, cfg.n_heads // max(cfg.n_kv_heads, 1))
    reg = registry.Registry()
    reg.put(registry.make_key("decode_attention", dtype="float32",
                              variant="causal", b=4, t=128,
                              d=cfg.head_dim, g=g),
            registry.TunedEntry(blocks={"block_q": 1, "block_k": 64}))
    registry.set_registry(reg)
    assert resolve_attn_blocks(cfg, pol, 128, decode=True,
                               batch=4) == (1, 64)
    # the prefill-shaped lookup is untouched by the decode cell
    # (defaults fitted to the 128-token shape)
    assert resolve_attn_blocks(cfg, pol, 128) == (128, 128)
