"""Model-substrate unit + property tests (MoE dispatch, segments, losses)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs import get_config, reduced
from repro.configs.base import (ATTN, ATTN_LOCAL, MoEConfig, ModelConfig,
                                RGLRU, SSM)
from repro.models import layers, moe
from repro.models.transformer import plan_segments

KEY = jax.random.PRNGKey(3)


# ---------------------------------------------------------------------------
# segment planning (scan-over-layers)
# ---------------------------------------------------------------------------
@given(st.lists(st.sampled_from([ATTN, ATTN_LOCAL, SSM, RGLRU]),
                min_size=1, max_size=60))
@settings(max_examples=200, deadline=None)
def test_plan_segments_reconstructs_pattern(pattern):
    """Invariant: concatenating unit*repeats over segments == pattern."""
    segs = plan_segments(tuple(pattern))
    flat = []
    for unit, k in segs:
        flat.extend(list(unit) * k)
    assert tuple(flat) == tuple(pattern)
    assert len(segs) <= 2


def test_plan_segments_griffin_pattern():
    pat = (RGLRU, RGLRU, ATTN_LOCAL) * 8 + (RGLRU, RGLRU)
    segs = plan_segments(pat)
    assert segs[0] == ((RGLRU, RGLRU, ATTN_LOCAL), 8)
    assert segs[1] == ((RGLRU, RGLRU), 1)


# ---------------------------------------------------------------------------
# MoE: dense oracle vs sorted dispatch; conservation properties
# ---------------------------------------------------------------------------
def _moe_cfg(E=8, k=2, d=64, f=128):
    return ModelConfig(
        name="moe-test", family="moe", n_layers=1, d_model=d, n_heads=4,
        n_kv_heads=4, d_ff=f, vocab_size=128,
        moe=MoEConfig(n_experts=E, top_k=k, d_ff_expert=f,
                      capacity_factor=8.0))  # high cf -> no drops


def test_moe_sorted_matches_dense_oracle():
    cfg = _moe_cfg()
    params = moe.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (64, cfg.d_model))
    y_dense, aux_d = moe.moe_dense(params, x, cfg, jnp.float32)
    y_sorted, aux_s = moe.moe_sorted(params, x, cfg,
                                     compute_dtype=jnp.float32)
    np.testing.assert_allclose(y_sorted, y_dense, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(aux_d, aux_s, atol=1e-6)


def test_moe_expert_slices_sum_to_full():
    """EP invariant: sum of per-slice partial outputs == full output."""
    cfg = _moe_cfg(E=8, k=2)
    params = moe.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (32, cfg.d_model))
    full, _ = moe.moe_sorted(params, x, cfg, compute_dtype=jnp.float32,
                             capacity=64)
    parts = []
    for e0 in range(0, 8, 2):
        y, _ = moe.moe_sorted(params, x, cfg, compute_dtype=jnp.float32,
                              capacity=64, expert_slice=(e0, 2))
        parts.append(y)
    np.testing.assert_allclose(sum(parts), full, atol=2e-5, rtol=2e-5)


@given(T=st.integers(4, 64), E=st.integers(2, 16), k=st.integers(1, 4),
       cf=st.floats(0.5, 4.0))
@settings(max_examples=40, deadline=None)
def test_capacity_bounds(T, E, k, cf):
    k = min(k, E)
    C = moe.default_capacity(T, E, k, cf)
    assert 4 <= C <= T or C == T or C >= 4
    assert C <= max(T, 4)


def test_router_gates_normalized():
    cfg = _moe_cfg()
    params = moe.init_moe(KEY, cfg)
    x = jax.random.normal(KEY, (32, cfg.d_model))
    gates, idx, aux = moe.route(x, params["router"], cfg.moe.top_k)
    np.testing.assert_allclose(jnp.sum(gates, -1), 1.0, atol=1e-5)
    assert int(jnp.max(idx)) < cfg.moe.n_experts
    assert float(aux) >= 1.0 - 1e-3   # Switch aux lower bound is ~1 at uniform


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
def test_chunked_xent_matches_full():
    B, S, D, V = 2, 32, 16, 64
    x = jax.random.normal(KEY, (B, S, D))
    table = jax.random.normal(KEY, (V, D)) * 0.1
    labels = jax.random.randint(KEY, (B, S), 0, V)
    full = layers.softmax_xent(x @ table.T, labels)
    for chunk in (4, 8, 32):
        ch = layers.chunked_softmax_xent(x, table, labels, chunk=chunk,
                                         compute_dtype=jnp.float32)
        np.testing.assert_allclose(ch, full, atol=1e-5, rtol=1e-5)


def test_chunked_xent_mask():
    B, S, D, V = 1, 16, 8, 32
    x = jax.random.normal(KEY, (B, S, D))
    table = jax.random.normal(KEY, (V, D)) * 0.1
    labels = jax.random.randint(KEY, (B, S), 0, V)
    mask = (jnp.arange(S) < 8)[None].astype(jnp.float32)
    a = layers.softmax_xent(x @ table.T, labels, mask)
    b = layers.chunked_softmax_xent(x, table, labels, chunk=4,
                                    compute_dtype=jnp.float32, mask=mask)
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_bf16_embedding_gradient_sums_a_frequent_token_in_f32():
    """8192 uses of one token: summed in bf16, the gradient stagnates far
    below the true sum (and then depends on how the batch is split over
    devices); summed in the table's f32 it stays within bf16 rounding."""
    tokens = jnp.zeros((4, 2048), jnp.int32)
    cot = jax.random.uniform(KEY, (4, 2048, 8), minval=0.5, maxval=1.5)
    table = jnp.zeros((16, 8), jnp.float32)

    def grad(dtype):
        return jax.grad(lambda t: jnp.sum(layers.embed_tokens(
            {"table": t}, tokens, dtype).astype(jnp.float32) * cot))(table)
    np.testing.assert_allclose(grad(jnp.bfloat16)[0], grad(jnp.float32)[0],
                               rtol=2.0 ** -7)


@given(st.integers(2, 128))
@settings(max_examples=20, deadline=None)
def test_gold_logit_equals_take_along_axis(V):
    logits = jax.random.normal(KEY, (3, 5, V))
    labels = jax.random.randint(KEY, (3, 5), 0, V)
    a = layers._gold_logit(logits, labels)
    b = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    np.testing.assert_allclose(a, b, atol=1e-6)


# ---------------------------------------------------------------------------
# rope / norms
# ---------------------------------------------------------------------------
def test_rope_preserves_norm():
    x = jax.random.normal(KEY, (2, 8, 4, 32))
    pos = jnp.broadcast_to(jnp.arange(8), (2, 8))
    y = layers.apply_rope(x, pos)
    np.testing.assert_allclose(jnp.linalg.norm(x, axis=-1),
                               jnp.linalg.norm(y, axis=-1),
                               atol=1e-4, rtol=1e-4)


def test_rope_relative_position_property():
    """Attention scores depend only on relative distance under RoPE."""
    D = 32
    q = jax.random.normal(KEY, (1, 1, 1, D))
    k = jax.random.normal(jax.random.PRNGKey(8), (1, 1, 1, D))
    def score(pq, pk):
        qq = layers.apply_rope(q, jnp.full((1, 1), pq))
        kk = layers.apply_rope(k, jnp.full((1, 1), pk))
        return float(jnp.sum(qq * kk))
    assert abs(score(5, 3) - score(105, 103)) < 1e-3


def test_partial_rotary():
    x = jax.random.normal(KEY, (1, 4, 2, 64))
    pos = jnp.broadcast_to(jnp.arange(4), (1, 4))
    y = layers.apply_rope(x, pos, fraction=0.25)
    # the pass-through part is untouched
    np.testing.assert_array_equal(x[..., 16:], y[..., 16:])
    assert not np.allclose(x[..., :16][:, 1:], y[..., :16][:, 1:])
