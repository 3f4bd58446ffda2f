"""The flash kernel's forward and the XLA tile scans against the oracle,
and the chunked SSD and RG-LRU scans against their one-token
recurrences, over shapes, dtypes and tilings (kernels in interpret
mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention_bwd import (flash_attention_vjp,
                                               kernel_blocks)
from repro.models.attention import (_fit_block, flash_attention_xla,
                                    full_attention, local_flash_xla)
from repro.models.rglru import (rglru_decode_step, rglru_scan,
                                rglru_scan_chunked)
from repro.models.ssm import ssd_chunked, ssd_decode_step

KEY = jax.random.PRNGKey(7)
K1, K2, K3, K4, K5 = jax.random.split(KEY, 5)
BF16 = jnp.bfloat16


def _flash(q, k, v, *, causal=True, window=0, softcap=0.0, blocks=(64, 64)):
    """The forward of the train step's flash kernel."""
    return flash_attention_vjp(q, k, v, causal, window, softcap, *blocks,
                               True)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------
ATTN_CASES = [
    # B, S, T, H, K, D, causal, window, softcap, dtype, (block_q, block_k)
    (2, 128, 128, 8, 2, 32, True, 0, 0.0, jnp.float32, (64, 64)),
    (1, 256, 256, 4, 4, 64, True, 0, 0.0, jnp.float32, (64, 64)),
    (2, 128, 128, 6, 1, 32, False, 0, 0.0, jnp.float32, (64, 64)),  # MQA
    (1, 256, 256, 8, 2, 32, True, 64, 0.0, jnp.float32, (64, 64)),  # window
    (1, 128, 128, 4, 2, 64, True, 0, 0.0, BF16, (64, 64)),
    (1, 64, 64, 2, 2, 128, True, 32, 0.0, jnp.float32, (64, 64)),  # D=128
    (1, 128, 128, 4, 2, 32, True, 0, 5.0, jnp.float32, (64, 64)),  # softcap
    # qwen2-0.5b's GQA shape at the kernel's own blocks
    (1, 512, 512, 14, 2, 64, True, 0, 0.0, BF16, kernel_blocks(7, 64)),
]


@pytest.mark.parametrize(
    "B,S,T,H,K,D,causal,window,softcap,dtype,blocks", ATTN_CASES)
def test_flash_attention_vs_oracle(B, S, T, H, K, D, causal, window,
                                   softcap, dtype, blocks):
    q = jax.random.normal(K1, (B, S, H, D), jnp.float32).astype(dtype)
    k = jax.random.normal(K2, (B, T, K, D), jnp.float32).astype(dtype)
    v = jax.random.normal(K3, (B, T, K, D), jnp.float32).astype(dtype)
    out = _flash(q, k, v, causal=causal, window=window, softcap=softcap,
                 blocks=blocks)
    oracle = ref.attention_ref(q.astype(jnp.float32),
                               k.astype(jnp.float32),
                               v.astype(jnp.float32),
                               causal=causal, window=window,
                               softcap=softcap)
    tol = 2e-2 if dtype == BF16 else 2e-5
    np.testing.assert_allclose(out.astype(jnp.float32), oracle,
                               atol=tol, rtol=tol)


def test_flash_block_shape_independence():
    """Result must not depend on the BlockSpec tiling."""
    q = jax.random.normal(K1, (1, 256, 4, 32))
    k = jax.random.normal(K2, (1, 256, 2, 32))
    v = jax.random.normal(K3, (1, 256, 2, 32))
    a = _flash(q, k, v, blocks=(32, 64))
    b = _flash(q, k, v, blocks=(128, 128))
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


def test_flash_xla_matches_pallas():
    """The XLA tile scan (every path off the TPU) and the kernel agree."""
    q = jax.random.normal(K1, (2, 128, 8, 32))
    k = jax.random.normal(K2, (2, 128, 4, 32))
    v = jax.random.normal(K3, (2, 128, 4, 32))
    a = _flash(q, k, v, blocks=(64, 64))
    b = flash_attention_xla(q, k, v, q_block=64, kv_block=64)
    np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# the XLA tile scans fit their tiles to any length
# ---------------------------------------------------------------------------
def test_fit_block():
    assert _fit_block(128, 192) == 96
    assert _fit_block(256, 256) == 256
    assert _fit_block(64, 64) == 64
    assert _fit_block(512, 100) == 100
    assert _fit_block(8, 97) == 1            # prime dim


WINDOW = 32


@pytest.mark.parametrize("scan", ["flash_attention_xla", "local_flash_xla"])
@pytest.mark.parametrize("S", [96, 192, 384, 100, 97])
def test_xla_scans_fit_tiles_to_the_length(scan, S):
    """A served prompt can be any length: the scans fit 64-wide tiles to
    it (96 -> 48, 100 -> 50, the prime 97 -> 1) instead of asserting,
    and match the oracle.  The windowed scan slides its key span where
    the tile divides the length and falls back to the full scan where
    it does not."""
    q = jax.random.normal(K1, (1, S, 4, 32))
    k = jax.random.normal(K2, (1, S, 2, 32))
    v = jax.random.normal(K3, (1, S, 2, 32))
    if scan == "flash_attention_xla":
        window = 0
        out = flash_attention_xla(q, k, v, q_block=64, kv_block=64)
    else:
        window = WINDOW
        out = local_flash_xla(q, k, v, window=WINDOW, q_block=64,
                              kv_block=64)
    oracle = full_attention(q, k, v, window=window)
    np.testing.assert_allclose(out, oracle, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD (Mamba-2): the chunked dual form against the one-token recurrence
# ---------------------------------------------------------------------------
def _ssd_inputs(B, S, H, P, G, N):
    x = jax.random.normal(K1, (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(K2, (B, S, H)))
    A = -jnp.exp(jax.random.normal(K3, (H,)))
    Bm = jax.random.normal(K4, (B, S, G, N)) * 0.5
    Cm = jax.random.normal(K5, (B, S, G, N)) * 0.5
    return x, dt, A, Bm, Cm


def _ssd_sequential(x, dt, A, Bm, Cm):
    """``ssd_decode_step`` token by token: (y (B,S,H,P), h (B,H,N,P))."""
    B, _, H, P = x.shape
    h0 = jnp.zeros((B, H, Bm.shape[-1], P), jnp.float32)

    def step(h, inp):
        xt, dtt, bt, ct = inp
        y, h = ssd_decode_step(xt, dtt, A, bt, ct, h)
        return h, y
    h, ys = jax.lax.scan(step, h0, tuple(jnp.moveaxis(a, 1, 0)
                                         for a in (x, dt, Bm, Cm)))
    return jnp.moveaxis(ys, 0, 1), h


SSD_CASES = [
    # B, S, H, P, G, N, chunk
    (2, 128, 4, 16, 1, 32, 32),
    (1, 64, 8, 32, 2, 16, 16),
    (1, 256, 2, 64, 1, 64, 64),
    (3, 100, 4, 16, 4, 16, 32),   # chunk doesn't divide S: padded
]


@pytest.mark.parametrize("B,S,H,P,G,N,chunk", SSD_CASES)
def test_ssd_vs_oracle(B, S, H, P, G, N, chunk):
    inputs = _ssd_inputs(B, S, H, P, G, N)
    y1, h1 = ssd_chunked(*inputs, chunk=chunk)
    y2, h2 = _ssd_sequential(*inputs)
    assert y1.shape == (B, S, H, P)
    np.testing.assert_allclose(y1, y2, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h1, h2, atol=2e-4, rtol=2e-4)


def test_ssd_chunk_independence():
    """SSD semantics must be chunk-size invariant (duality property)."""
    inputs = _ssd_inputs(1, 128, 4, 16, 1, 32)
    y32, h32 = ssd_chunked(*inputs, chunk=32)
    y128, h128 = ssd_chunked(*inputs, chunk=128)
    np.testing.assert_allclose(y32, y128, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h32, h128, atol=2e-4, rtol=2e-4)


def test_ssd_matches_sequential_recurrence():
    """Chunked dual form == step-by-step linear recurrence, the
    recurrence written out as a Python loop."""
    B, S, H, P, G, N = 1, 16, 2, 8, 1, 4
    x, dt, A, Bm, Cm = _ssd_inputs(B, S, H, P, G, N)
    y_k, h_k = ssd_chunked(x, dt, A, Bm, Cm, chunk=8)
    h = jnp.zeros((B, H, N, P))
    ys = []
    for t in range(S):
        y, h = ssd_decode_step(x[:, t], dt[:, t], A, Bm[:, t], Cm[:, t], h)
        ys.append(y)
    y_seq = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(y_k, y_seq, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(h_k, h, atol=2e-4, rtol=2e-4)


# ---------------------------------------------------------------------------
# RG-LRU: the associative scan against the one-token recurrence
# ---------------------------------------------------------------------------
def _rglru_sequential(log_a, gated):
    h0 = jnp.zeros(log_a.shape[::2], jnp.float32)

    def step(h, inp):
        h = rglru_decode_step(*inp, h)
        return h, h
    _, hs = jax.lax.scan(step, h0, (jnp.moveaxis(log_a, 1, 0),
                                    jnp.moveaxis(gated, 1, 0)))
    return jnp.moveaxis(hs, 0, 1)


@pytest.mark.parametrize("B,S,W", [
    (2, 128, 64), (1, 64, 256), (3, 96, 32), (1, 128, 8)])
def test_rglru_vs_oracle(B, S, W):
    log_a = -jax.nn.softplus(jax.random.normal(K1, (B, S, W)))
    gated = jax.random.normal(K2, (B, S, W))
    h1 = jax.jit(rglru_scan)(log_a, gated)
    h2 = _rglru_sequential(log_a, gated)
    np.testing.assert_allclose(h1, h2, atol=2e-5, rtol=2e-5)


def test_rglru_block_independence():
    log_a = -jax.nn.softplus(jax.random.normal(K1, (1, 128, 32)))
    gated = jax.random.normal(K2, (1, 128, 32))
    whole = jax.jit(rglru_scan)(log_a, gated)
    for chunk in (16, 128):
        chunked = jax.jit(rglru_scan_chunked, static_argnames="chunk")
        np.testing.assert_allclose(chunked(log_a, gated, chunk=chunk),
                                   whole, atol=2e-5, rtol=2e-5)
