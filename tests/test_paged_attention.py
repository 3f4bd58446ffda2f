"""Pallas paged-attention decode kernel vs the dense-gather reference.

The acceptance bar: the kernel reads K/V straight from the paged pool
through scalar-prefetched block tables and must match the dense
``decode_attention`` math (gather + masked softmax) to fp32 tolerance
across page boundaries, ragged lengths, GQA/MQA groupings, and any
``block_k`` tiling, its default included.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.paged_attention import paged_decode_attention

KEY = jax.random.PRNGKey(11)
KQ, KKV, KP = jax.random.split(KEY, 3)


def _paged_inputs(B, T, D, G, K, ps, lengths, seed=0):
    """Random q + paged K/V pool with per-row exclusive, shuffled tables."""
    H = G * K
    P = T // ps
    n_pages = B * P + 1                      # +1 unreferenced page
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(kq, (B, H, D), jnp.float32)
    k_pages = jax.random.normal(kk, (n_pages, ps, K, D), jnp.float32)
    v_pages = jax.random.normal(kv, (n_pages, ps, K, D), jnp.float32)
    # deterministic shuffle: non-contiguous gather is the point
    perm = np.random.RandomState(seed).permutation(B * P)
    tables = jnp.asarray(perm.reshape(B, P), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    return q, k_pages, v_pages, tables, lengths


CASES = [
    # B, T, D, G, K, page_size, block_k, lengths
    (2, 64, 32, 2, 2, 16, 32, [64, 40]),       # ragged, mid-page end
    (1, 128, 64, 1, 4, 16, 48, [96]),          # non-pow2 ppb=3, MHA
    (4, 64, 32, 4, 1, 8, 256, [64, 8, 17, 33]),  # MQA, block_k > T clamps
    (2, 64, 32, 2, 2, 16, 16, [16, 32]),       # exact page boundaries
    (3, 32, 64, 2, 2, 8, 8, [1, 31, 32]),      # single-token history
]


@pytest.mark.parametrize("B,T,D,G,K,ps,bk,lengths", CASES)
def test_paged_kernel_matches_ref(B, T, D, G, K, ps, bk, lengths):
    q, kp, vp, tables, lens = _paged_inputs(B, T, D, G, K, ps, lengths)
    out = paged_decode_attention(q, kp, vp, tables, lens, block_k=bk,
                                 interpret=True)
    oracle = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out, oracle, atol=1e-5, rtol=1e-5)


def test_paged_block_shape_independence():
    """The result must not depend on pages-per-block tiling."""
    q, kp, vp, tables, lens = _paged_inputs(2, 128, 32, 2, 2, 16, [128, 70])
    outs = [paged_decode_attention(q, kp, vp, tables, lens, block_k=bk,
                                   interpret=True)
            for bk in (16, 48, 64, 128)]
    for o in outs[1:]:
        np.testing.assert_allclose(o, outs[0], atol=1e-5, rtol=1e-5)


def test_paged_zero_length_row_is_finite():
    """An empty history (freshly opened slot) must not NaN the batch."""
    q, kp, vp, tables, lens = _paged_inputs(2, 64, 32, 2, 2, 16, [0, 64])
    out = paged_decode_attention(q, kp, vp, tables, lens, block_k=32,
                                 interpret=True)
    assert bool(jnp.isfinite(out).all())
    oracle = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out[1], oracle[1], atol=1e-5, rtol=1e-5)


def test_paged_softcap_matches_ref():
    q, kp, vp, tables, lens = _paged_inputs(2, 64, 32, 2, 2, 16, [64, 50])
    out = paged_decode_attention(q, kp, vp, tables, lens, block_k=32,
                                 softcap=30.0, interpret=True)
    oracle = ref.paged_attention_ref(q, kp, vp, tables, lens, softcap=30.0)
    np.testing.assert_allclose(out, oracle, atol=1e-5, rtol=1e-5)


def test_paged_default_block_matches_ref():
    """The kernel at its default ``block_k`` (256, clamped to the cache's
    64 tokens) against the dense-gather reference."""
    q, kp, vp, tables, lens = _paged_inputs(2, 64, 32, 2, 2, 16, [64, 40])
    out = paged_decode_attention(q, kp, vp, tables, lens, interpret=True)
    oracle = ref.paged_attention_ref(q, kp, vp, tables, lens)
    np.testing.assert_allclose(out, oracle, atol=1e-5, rtol=1e-5)
